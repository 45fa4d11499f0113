"""The table of peaks and the bytes and FLOPs a step needs, from shapes."""

import json
import os

import pytest

from benchmark import manifest, peaks, serve


def sizes(name):
    with open(os.path.join(manifest.HERE, "configs", f"{name}.json")) as f:
        return serve.model_sizes(json.load(f))


def test_v5e_peaks_are_the_published_ones():
    p = peaks.chip_peaks("TPU v5 lite")
    assert (p["bf16_tflops"], p["hbm_gbps"]) == (197.0, 819.0)


@pytest.mark.parametrize("kind", ["cpu", "", "TPU v9", None])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        peaks.chip_peaks(kind)


def test_mixtral_layer_is_2_82_gb():
    m = sizes("mixtral-8x7b-l4")
    p = peaks.matmul_params(m)
    layer = (p["attention"] + 8 * p["ffn"]) * 2
    assert layer / 1e9 == pytest.approx(2.90, abs=0.01)   # 2.82 experts + 0.08 attn
    assert 8 * p["ffn"] * 2 / 1e9 == pytest.approx(2.82, abs=0.01)


def test_mistral_7b_weights_are_14_5_gb():
    m = sizes("mistral-7b-v0.3-tp4")
    p = peaks.matmul_params(m)
    total = 32 * (p["attention"] + p["ffn"]) + 2 * p["head"]
    assert total * 2 / 1e9 == pytest.approx(14.5, abs=0.05)


@pytest.mark.parametrize("batch,want", [(0, 0.0), (1, 2.0), (1e9, 8.0)])
def test_experts_touched_limits(batch, want):
    assert peaks.experts_touched(sizes("mixtral-8x7b-l4"), batch) == \
        pytest.approx(want)


def test_experts_touched_grows_with_the_batch_and_a_dense_ffn_is_one():
    m = sizes("mixtral-8x7b-l4")
    got = [peaks.experts_touched(m, b) for b in (1, 2, 4, 8, 16, 32)]
    assert got == sorted(got) and got[-1] < 8.0
    assert peaks.experts_touched(sizes("mistral-7b-v0.3-tp4"), 8) == 1.0


def test_decode_bytes_add_the_resident_context():
    m = sizes("mistral-7b-v0.3-tp4")
    base = peaks.decode_bytes_per_step(m, 8, 0)
    assert base / 1e9 == pytest.approx(14.2, abs=0.2)       # no embedding
    with_ctx = peaks.decode_bytes_per_step(m, 8, 70000)
    assert (with_ctx - base) == 70000 * 32 * 2 * 8 * 128 * 2  # 131 KB a token


def test_prefill_flops_count_only_the_active_experts():
    m = sizes("mixtral-8x7b-l4")
    p = peaks.matmul_params(m)
    assert peaks.prefill_flops_per_token(m) == \
        2.0 * 4 * (p["attention"] + 2 * p["ffn"] + p["router"])
