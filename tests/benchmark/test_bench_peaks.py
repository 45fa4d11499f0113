"""The table of peaks, and the bytes and FLOPs a step needs, from shapes, as
each configuration's family counts them."""

import pytest

from benchmark import manifest, peaks

M = manifest.load()
MIXTRAL = "mixtral-8x7b-l4"         # the checks of its own numbers
MISTRAL = "mistral-7b-v0.3-tp4"     # and of its


def family(name):
    return manifest.family(manifest.load_config(M, name))


def sizes(name):
    return family(name).model_sizes(manifest.load_config(M, name))


def test_v5e_peaks_are_the_published_ones():
    p = peaks.chip_peaks("TPU v5 lite")
    assert (p["bf16_tflops"], p["hbm_gbps"]) == (197.0, 819.0)


@pytest.mark.parametrize("kind", ["cpu", "", "TPU v9", None])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        peaks.chip_peaks(kind)


def test_mixtral_layer_is_2_82_gb():
    m = sizes(MIXTRAL)
    p = family(MIXTRAL).matmul_params(m)
    layer = (p["attention"] + 8 * p["ffn"]) * 2
    assert layer / 1e9 == pytest.approx(2.90, abs=0.01)   # 2.82 experts + 0.08 attn
    assert 8 * p["ffn"] * 2 / 1e9 == pytest.approx(2.82, abs=0.01)


def test_mistral_7b_weights_are_14_5_gb():
    m = sizes(MISTRAL)
    p = family(MISTRAL).matmul_params(m)
    total = 32 * (p["attention"] + p["ffn"]) + 2 * p["head"]
    assert total * 2 / 1e9 == pytest.approx(14.5, abs=0.05)


@pytest.mark.parametrize("batch,want", [(0, 0.0), (1, 2.0), (1e9, 8.0)])
def test_experts_touched_limits(batch, want):
    assert family(MIXTRAL).experts_touched(sizes(MIXTRAL), batch) == \
        pytest.approx(want)


def test_experts_touched_grows_with_the_batch_and_a_dense_ffn_is_one():
    m = sizes(MIXTRAL)
    got = [family(MIXTRAL).experts_touched(m, b) for b in (1, 2, 4, 8, 16, 32)]
    assert got == sorted(got) and got[-1] < 8.0
    assert family(MISTRAL).experts_touched(sizes(MISTRAL), 8) == 1.0


def test_decode_bytes_add_the_resident_context():
    m = sizes(MISTRAL)
    base = family(MISTRAL).decode_bytes_per_step(m, 8, 0)
    assert base / 1e9 == pytest.approx(14.2, abs=0.2)       # no embedding
    with_ctx = family(MISTRAL).decode_bytes_per_step(m, 8, 70000)
    assert (with_ctx - base) == 70000 * 32 * 2 * 8 * 128 * 2  # 131 KB a token


def test_prefill_flops_count_only_the_active_experts():
    m = sizes(MIXTRAL)
    p = family(MIXTRAL).matmul_params(m)
    assert family(MIXTRAL).prefill_flops_per_token(m) == \
        2.0 * 4 * (p["attention"] + 2 * p["ffn"] + p["router"])


@pytest.mark.parametrize("name", [c["name"] for c in M["configs"]])
def test_every_configurations_family_counts_what_a_step_needs(name):
    """Whatever the architecture: a step needs bytes that grow with the
    resident context, a prompt token needs operations, the kernel that marks
    a step has a count of its own and an unknown kernel has none."""
    fam, m = family(name), sizes(name)
    engine = manifest.load_config(M, name)["engine"]
    base = fam.decode_bytes_per_step(m, 8, 0)
    assert 0 < base < fam.decode_bytes_per_step(m, 8, 1000)
    assert fam.prefill_flops_per_token(m) > 0
    assert fam.marker_calls_per_step(m) >= 1
    cost = fam.kernel_cost(fam.STEP_MARKER, m, engine, 8, 1000)
    assert cost["bytes"] > 0 and cost["flops"] > 0
    # the marker's bytes are part of what the whole step needs
    assert cost["bytes"] <= fam.decode_bytes_per_step(m, 8, 1000) - base + 1e-6
    assert fam.kernel_cost("no_such_kernel", m, engine, 8, 1000) is None


def test_paged_attention_needs_the_resident_keys_and_values():
    """Mistral on four chips at 74 blocks of 128 and batch 8: 77.6 MB a
    layer and chip, the builder's figure of PERF.md section 5."""
    m = sizes(MISTRAL)
    engine = manifest.load_config(M, MISTRAL)["engine"]
    cost = family(MISTRAL).kernel_cost("paged_decode_attention", m, engine,
                                       8, 8 * 74 * 128)
    assert cost["bytes"] / 32 / 4 / 1e6 == pytest.approx(77.6, abs=0.05)
    assert cost["flops"] == 32 * 4.0 * 32 * 128 * 8 * 74 * 128


def kernel_ctx(name, **over):
    """What ``paged_attn_bw_share`` reads: 100 counted steps whose kernel
    calls took 0.4 s on each chip; two requests at a step (402 tokens in 200
    steps, two of them first tokens), each decoding through half of a 10 s
    window over 8,000 + 100 tokens; four chips."""
    record = {"due_s": 0.0, "prompt_len": 8000,
              "token_s": [i * 0.025 for i in range(201)]}
    ctx = {"trace": {"programs": {"jit_decode": {"runs": 20, "seconds": 1.0,
                                                 "steps": 100}},
                     "op_seconds": {
                         "jit_decode/paged_decode_attention:bf16[8,8,4,128]": 0.3,
                         "jit_decode/paged_decode_attention:bf16[1,8,4,128]": 0.1,
                         "jit_chunk/paged_decode_attention:bf16[8,8,4,128]": 9.0,
                         "jit_decode/fusion:bf16[8,4096]": 0.5}},
           "health0": {"tokens_generated": 0, "decode_steps": 0,
                       "latency": {"ttft_count": 0}},
           "health1": {"tokens_generated": 402, "decode_steps": 200,
                       "latency": {"ttft_count": 2}},
           "records": [record, record], "seconds": 10.0,
           "family": family(name),
           "model": sizes(name),
           "engine": manifest.load_config(M, name)["engine"],
           "device": {"kind": "TPU v5 lite"}, "chips": 4}
    return dict(ctx, **over)


def test_paged_attn_bw_share_is_needed_bytes_over_kernel_time_over_the_peak():
    read = manifest.layer_reader("paged_attn_bw_share").read
    # 2 x 8,100.5 tokens resident at a step (the window's average holds half
    # of that) x 131,072 B a token over 32 layers, a quarter a chip, in 4 ms
    # of kernel time a step, of 819 GB/s
    want = 100 * (2 * 8100.5 * 131072 / 4) / 0.004 / 819e9
    assert read(kernel_ctx(MISTRAL)) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("over", [
    {"trace": {}}, {"trace": None},
    {"trace": {"programs": {"jit_decode": {"runs": 2, "seconds": 1.0}},
               "op_seconds": {"jit_decode/paged_decode_attention": 0.3}}},
    {"trace": {"programs": {"jit_decode": {"runs": 2, "steps": 9}},
               "op_seconds": {"jit_decode/fusion": 0.3}}},
    {"health1": {}}, {"records": []}],
    ids=["no-trace", "none", "no-steps", "no-kernel", "no-counters",
         "no-decoding"])
def test_paged_attn_bw_share_reads_nothing_where_nothing_is(over):
    read = manifest.layer_reader("paged_attn_bw_share").read
    assert read(kernel_ctx(MISTRAL, **over)) is None
