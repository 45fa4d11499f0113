"""Latent attention in EVERY layer (ISSUE 52: ``layer_group`` 1 — a query
latent, YaRN positions, the attention temperature, no output gate) held to the
plain reference ``benchmark/reference/kimi.py`` at tiny sizes on seeded
weights: the forward pass in float32 and bfloat16, YaRN's table against the
closed form, the blocked prefill against ``expanded_attention`` (its
``jax.numpy`` form and its kernel, interpreted), the chips' partial expert
sums against the uncut layer, and what the config refuses. Through the
serving path: ``test_kimi_serving.py``, which takes this file's tiny
configuration."""

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from tpu9.models import decoder_forward, init_decoder
from tpu9.models import kvstate
from tpu9.models.transformer import DecoderConfig
from tpu9.ops import latent_attention as la
from tpu9.ops.rotary import rope_rows, yarn_inv_freq, yarn_mscale

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
# 3 layers: one dense, two of experts; 16 experts routed, all held
SMALL = DecoderConfig(
    vocab_size=256, dim=128, n_layers=3, n_heads=4, n_kv_heads=4,
    head_dim=32, hidden_dim=256, norm_eps=1e-5, rope_theta=50000.0,
    max_seq_len=512, layer_group=1, mla_latent=64, mla_nope=32, mla_rope=16,
    mla_v=32, mla_q_latent=48, mla_out_gate=False,
    mla_mscale=yarn_mscale(8, 1), rope_yarn=(8.0, 64, 32.0, 1.0),
    n_experts=16, moe_top_k=4, moe_dense_layers=1, moe_hidden_dim=64,
    moe_routed=16, moe_held_first=0, moe_shared_dim=64, moe_score="sigmoid",
    moe_select_bias=True, moe_renormalise=True, moe_gate_scale=2.827,
    dtype=jnp.float32)
TOL = 2e-4


def _model(cfg=SMALL, **kw):
    """``cfg`` in the published config's vocabulary, as the reference reads
    it."""
    return dict({
        "num_attention_heads": cfg.n_heads, "qk_nope_head_dim": cfg.mla_nope,
        "qk_rope_head_dim": cfg.mla_rope, "v_head_dim": cfg.mla_v,
        "kv_lora_rank": cfg.mla_latent, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(YARN, factor=cfg.rope_yarn[0]) if cfg.rope_yarn
        else None,
        "num_experts_per_tok": cfg.moe_top_k,
        "routed_scaling_factor": cfg.moe_gate_scale,
        "experts_held": [cfg.moe_held_first, cfg.n_experts]}, **kw)


def _ref_logits(params, tokens, model=None):
    ref = correctness.load_reference("kimi")
    return np.asarray(ref.forward(params, jnp.asarray(tokens, jnp.int32),
                                  model or _model()))


def _margin(row, token):
    return float(row.max() - row[token])


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(52), SMALL)


# ---------------------------------------------------------------------------
# the pattern: MLA in every layer, no state a lane
# ---------------------------------------------------------------------------

def test_every_layer_is_latent_attention_and_no_lane_keeps_state():
    assert [SMALL.layer_kind(l) for l in range(3)] == [
        ("mla", "dense"), ("mla", "experts"), ("mla", "experts")]
    assert SMALL.layers_of("mla") == (0, 1, 2) and not SMALL.layers_of("kda")
    assert SMALL.kv_layers == 3
    assert SMALL.kv_row == ((1, 64), (1, 16))
    assert kvstate.lane_shapes(SMALL, 4) == {}
    assert kvstate.lane_bytes(SMALL, 4) == 0
    assert sorted(kvstate.dense_shapes(SMALL, 1, 64)) == ["k", "v"]
    # a page of 16 entries: 3 layers of (64 + 16) float32 numbers
    assert kvstate.block_bytes(SMALL, 16) == 3 * 16 * 80 * 4


def test_the_tree_has_a_query_latent_and_no_gate(params):
    mla = params["layers"][0]["mla"]
    assert sorted(mla) == ["kv_norm", "q_norm", "w_dkv", "w_dq", "w_ukv",
                           "w_uq", "wo"]
    assert mla["w_dq"].shape == (128, 48) and mla["q_norm"].shape == (48,)
    assert mla["w_uq"].shape == (48, 4 * 48)
    gated = replace(SMALL, mla_q_latent=0, mla_out_gate=True)
    other = init_decoder(jax.random.PRNGKey(52), gated)["layers"][0]["mla"]
    assert sorted(other) == ["kv_norm", "w_dkv", "w_gate", "w_ukv", "wo",
                             "wq"]
    # the leaves both trees have are drawn from the same keys
    for name in ("w_dkv", "w_ukv", "wo"):
        assert (np.asarray(other[name]) == np.asarray(mla[name])).all()


# ---------------------------------------------------------------------------
# the forward pass against the reference
# ---------------------------------------------------------------------------

def test_forward_equals_the_reference_in_float32(params):
    tokens = np.random.default_rng(0).integers(3, 256, 90)
    got = np.asarray(decoder_forward(params, jnp.asarray(tokens)[None],
                                     SMALL)[0])
    assert np.abs(got - _ref_logits(params, tokens)).max() < TOL


@pytest.mark.parametrize("control", ["no_mscale", "plain_rope", "no_q_norm",
                                     "no_shared", "int8_weights"])
def test_the_reference_tells_each_control_from_the_model(params, control):
    """Each control the chip sweep holds the tolerance against moves the
    float32 logits by far more than the program differs from the
    reference."""
    tokens = np.random.default_rng(1).integers(3, 256, 200)
    want = _ref_logits(params, tokens)
    bare = _ref_logits(params, tokens, _model(control=(control,)))
    assert np.abs(bare - want).max() > 100 * TOL


def test_forward_in_bfloat16_stays_near_the_reference():
    """bfloat16 weights and activations: every served position's logits
    within a few hundredths of a logit spread of 0.6 — the precision the
    chip's tolerance is swept at, here only as an order of magnitude."""
    cfg = replace(SMALL, dtype=jnp.bfloat16)
    params = init_decoder(jax.random.PRNGKey(52), cfg)
    tokens = np.random.default_rng(2).integers(3, 256, 64)
    got = np.asarray(decoder_forward(params, jnp.asarray(tokens)[None],
                                     cfg)[0])
    want = _ref_logits(params, tokens)
    assert np.isfinite(got).all()
    # a flipped choice among 16 experts moves a tiny model's logit by more
    # than rounding does: the median position is held, not the worst
    assert np.median(np.abs(got - want).max(-1)) < 0.1


def test_a_full_rank_gated_query_still_builds_and_differs(params):
    """The descriptors are read: with the gate on and the query full-rank
    the same seed gives another model (Ling's MLA layer's form)."""
    cfg = replace(SMALL, mla_q_latent=0, mla_out_gate=True)
    other = init_decoder(jax.random.PRNGKey(52), cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(3, 256, 20))[None]
    a = np.asarray(decoder_forward(params, tokens, SMALL)[0])
    b = np.asarray(decoder_forward(other, tokens, cfg)[0])
    assert np.isfinite(b).all() and np.abs(a - b).max() > 0.01


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("factor", [1.0, 64.0])
def test_yarn_frequencies_against_the_closed_form(factor):
    """Kimi-K2.6's numbers: theta 50,000, 64 rotary dimensions, beta 32 / 1
    over 4,096 original positions. At factor 1 the blend is ``f_i`` itself;
    at 64 the fast dimensions keep ``f_i``, the slow ones are ``f_i / 64``,
    and the ramp runs over dimensions 8..20."""
    half, theta = 32, 50000.0
    got = np.asarray(yarn_inv_freq(half, theta, factor, 4096, 32.0, 1.0))
    f = theta ** (-np.arange(half) / half)

    def dim_of(turns):
        return 64 * math.log(4096 / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (8, 20)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    want = f / factor * ramp + f * (1 - ramp)
    assert np.allclose(got, want, rtol=1e-6)
    assert np.allclose(got[:low + 1], f[:low + 1], rtol=1e-6)
    assert np.allclose(got[high:], f[high:] / factor, rtol=1e-6)
    sin, cos = rope_rows(jnp.arange(128), 64, theta,
                         (factor, 4096, 32.0, 1.0))
    ang = np.arange(128)[:, None] * want[None, :]
    assert np.allclose(np.asarray(sin), np.sin(ang), atol=2e-5)
    assert np.allclose(np.asarray(cos), np.cos(ang), atol=2e-5)
    if factor == 1.0:
        plain = rope_rows(jnp.arange(128), 64, theta)
        assert np.allclose(np.asarray(plain[0]), np.asarray(sin), atol=1e-6)


def test_the_attention_temperature():
    assert yarn_mscale(1, 1) == 1.0
    assert yarn_mscale(64, 1) == pytest.approx(0.1 * math.log(64) + 1)
    assert yarn_mscale(64, 1) == pytest.approx(1.4159, abs=1e-4)


# ---------------------------------------------------------------------------
# the blocked prefill
# ---------------------------------------------------------------------------

def _prefill_case(t, s, offset, heads=4, dn=32, dr=16, dv=32, dc=64,
                  layers=2, layer=1, dtype=jnp.float32, seed=0):
    r = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    q_nope = jax.random.normal(next(r), (t, heads, dn), dtype)
    q_rope = jax.random.normal(next(r), (t, heads, dr), dtype)
    c_cache = jax.random.normal(next(r), (layers, 1, s, 1, dc), dtype)
    r_cache = jax.random.normal(next(r), (layers, 1, s, 1, dr), dtype)
    w_ukv = (jax.random.normal(next(r), (dc, heads, dn + dv), jnp.float32)
             * dc ** -0.5).astype(dtype)
    latents, rotated = c_cache[layer, 0, :, 0], r_cache[layer, 0, :, 0]
    kv = jnp.einsum("sc,chd->shd", latents, w_ukv)
    want = la.expanded_attention(
        q_nope, q_rope, kv[..., :dn], rotated, kv[..., dn:],
        offset + jnp.arange(t), 0.2)
    return (q_nope, q_rope, c_cache, r_cache, w_ukv, offset, layer, 0.2), \
        np.asarray(want, np.float32)


@pytest.mark.parametrize("t,s,offset,block_k", [
    (16, 64, 0, 16), (16, 64, 48, 16), (32, 128, 40, 32), (16, 64, 7, 64)])
def test_the_blocked_prefill_in_jax_numpy_equals_the_expanded(t, s, offset,
                                                              block_k):
    args, want = _prefill_case(t, s, offset)
    got = la.blocked_prefill_attention_xla(*args, block_k=block_k)
    assert np.abs(np.asarray(got) - want).max() < 2e-5


@pytest.mark.parametrize("t,s,offset,block_k,heads", [
    (128, 512, 0, 128, 4), (128, 512, 256, 128, 4), (256, 512, 128, 256, 8),
    (128, 256, 100, 128, 2)])
def test_the_blocked_prefill_kernel_interpreted_equals_the_expanded(
        t, s, offset, block_k, heads):
    """The kernel's body in interpret mode: blocks before the tile's first
    query unmasked, the block the diagonal crosses masked, blocks past the
    last query neither copied nor touched (the scratch holds NaN there)."""
    args, want = _prefill_case(t, s, offset, heads=heads)
    q_nope, q_rope, c_cache, r_cache, w_ukv, offset, layer, scale = args
    # rows past the last query: whatever an earlier sequence left
    c_cache = c_cache.at[:, :, offset + t:].set(jnp.nan)
    got = la.blocked_prefill_attention_kernel(
        q_nope, q_rope, c_cache, r_cache, w_ukv, offset, layer, scale,
        block_k=block_k, interpret=True)
    got = np.asarray(jax.block_until_ready(got))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 2e-5


def test_the_blocked_prefill_kernel_in_bfloat16():
    args, want = _prefill_case(128, 256, 64, dtype=jnp.bfloat16)
    got = la.blocked_prefill_attention_kernel(*args, block_k=128,
                                              interpret=True)
    got = np.asarray(jax.block_until_ready(got), np.float32)
    assert np.abs(got - want).max() < 0.05


def test_a_short_scratch_keeps_the_expanded_form():
    """Ling's contexts stay under 4,096 rows: its programs lower as they
    did. A long scratch in whole blocks takes the blocked form."""
    assert "every row expanded" in la.blocked_prefill_declined(4096)
    assert la.blocked_prefill_declined(57344) == ""
    assert "not whole blocks" in la.blocked_prefill_declined(57344 + 64)
    assert la.prefill_kernel_declined(512, 128, 64, 128, 512,
                                      jnp.bfloat16) == "no TPU backend"


# ---------------------------------------------------------------------------
# the share: the chips' partial sums add up to the uncut layer
# ---------------------------------------------------------------------------

def test_the_chips_partial_expert_sums_add_up_to_the_uncut_layer(params):
    """Four chips hold four experts each of the 16 routed: each computes its
    own experts' terms (gates normalised over all chosen) and the shared
    expert. The sum of the partial results, the shared expert counted once,
    is the uncut reference's layer — in the reference and in the program."""
    from tpu9.models.moe import moe_ffn_held, moe_ffn_sorted
    from tpu9.models.transformer import moe_cfg
    ref = correctness.load_reference("kimi")
    moe = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(9), (40, 128), jnp.float32)
    whole = np.asarray(ref._experts(moe, h, _model()))
    shared = np.asarray(ref._swiglu(h, moe["shared"], _model()))
    parts, program = [], []
    for chip in range(4):
        held = dict(moe, **{n: moe[n][4 * chip:4 * chip + 4]
                            for n in ("w_gate", "w_up", "w_down")})
        parts.append(np.asarray(ref._experts(
            held, h, _model(experts_held=[4 * chip, 4]))))
        cfg = moe_cfg(replace(SMALL, n_experts=4, moe_held_first=4 * chip))
        y, picks = moe_ffn_held(held, h[None], cfg)
        program.append(np.asarray(y[0]))
        assert picks.shape == (1, 40, 4)
        wide, _ = moe_ffn_sorted(held, jnp.tile(h[None], (1, 8, 1)), cfg)
        assert np.abs(np.asarray(wide[0, :40]) - program[-1]).max() < TOL
    assert np.abs(sum(parts) - 3 * shared - whole).max() < TOL
    assert np.abs(sum(program) - 3 * shared - whole).max() < TOL


def test_no_group_limit_is_the_plain_top_k(params):
    ref = correctness.load_reference("kimi")
    moe = params["layers"][2]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (30, 128), jnp.float32)
    gates, chosen = ref.route(moe, h, _model())
    scores = jax.nn.sigmoid(h @ moe["router"])
    want = np.argsort(-np.asarray(scores + moe["bias"]), -1)[:, :4]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(want, -1)).all()
    assert np.allclose(np.asarray(gates).sum(-1), 2.827, rtol=1e-5)


# ---------------------------------------------------------------------------
# what the config refuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(kda_conv=4), "no KDA layer to read them"),
    (dict(kda_gate_bound=-5.0), "no KDA layer to read them"),
    (dict(mla_q_latent=-1), "a query latent is a width"),
    (dict(mla_mscale=0.0), "temperature positive"),
    (dict(rope_yarn=(8.0, 64, 32.0)), "YaRN is"),
    (dict(rope_yarn=(0.5, 64, 32.0, 1.0)), "YaRN is"),
    (dict(rope_yarn=(8.0, 64, 1.0, 32.0)), "YaRN is"),
    (dict(mla_latent=0), "latent-attention width"),
    (dict(loop_steps=2), "pass loop"),
    (dict(moe_groups=3), "groups divide"),
])
def test_the_config_refuses_what_is_not_built(kw, needle):
    with pytest.raises(ValueError, match=needle):
        replace(SMALL, **kw)


@pytest.mark.parametrize("kw", [
    dict(mla_q_latent=48), dict(rope_yarn=(8.0, 64, 32.0, 1.0)),
    dict(mla_mscale=1.2)])
def test_the_new_descriptors_build_for_a_pattern_only(kw):
    plain = DecoderConfig(vocab_size=256, dim=128, n_layers=2, n_heads=4,
                          n_kv_heads=2, head_dim=32, hidden_dim=256,
                          max_seq_len=256, dtype=jnp.float32)
    with pytest.raises(ValueError, match="layer pattern only"):
        replace(plain, **kw)
