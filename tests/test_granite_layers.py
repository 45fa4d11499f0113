"""A layer pattern given as a LIST (ISSUE 55: Mamba-2 state-space layers
around plain attention without positions, a tied head and four multipliers)
held to the plain reference ``benchmark/reference/granitehybrid.py`` at tiny
sizes on seeded weights: the forward pass over one period and over two,
prefill then decode through the cache against the reference's full forward
pass, every builder's control FAILING the tolerance in float32, what the
pattern's one rule says, and what the config refuses. Through the serving
path: ``test_granite_serving.py``, which takes this file's tiny
configuration."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from tpu9.models import decoder_forward, init_decoder
from tpu9.models import kvstate
from tpu9.models.transformer import DecoderConfig

PERIOD = ("ssm",) * 5 + ("full",) + ("ssm",) * 4
# two whole periods of ten layers: attention at layers 5 and 15
SMALL = DecoderConfig(
    vocab_size=256, dim=64, n_layers=20, n_heads=4, n_kv_heads=2,
    head_dim=16, hidden_dim=128, norm_eps=1e-5, max_seq_len=512,
    tie_embeddings=True, layer_pattern=PERIOD * 2, ssm_heads=4,
    ssm_head_dim=32, ssm_state=128, ssm_groups=1, ssm_conv=4, rope=False,
    attn_scale=1 / 16, embed_mult=12.0, residual_mult=0.22, logit_div=8.0,
    dtype=jnp.float32)
ONE = replace(SMALL, n_layers=10, layer_pattern=PERIOD)
# what the float32 program may differ from the reference by, as a share of
# a logit's std (a table seeded at 0.02 / embedding_multiplier makes that
# 0.0017 at these widths: an absolute limit would say nothing)
TOL = 2e-4
CONTROLS = ("int8_weights", "bf16_state", "no_decay", "no_d", "sqrt_scale",
            "rotary", "residual_one")


def _model(cfg=SMALL, **kw):
    """``cfg`` in the published config's vocabulary, as the reference reads
    it."""
    return dict({
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state, "mamba_n_groups": cfg.ssm_groups,
        "mamba_d_conv": cfg.ssm_conv,
        "attention_multiplier": cfg.attn_scale,
        "embedding_multiplier": cfg.embed_mult,
        "residual_multiplier": cfg.residual_mult,
        "logits_scaling": cfg.logit_div}, **kw)


def _ref_logits(params, tokens, model):
    ref = correctness.load_reference("granitehybrid")
    return np.asarray(ref.forward(params, jnp.asarray(tokens, jnp.int32),
                                  model))


def _tokens(n, seed=7):
    return np.random.default_rng(seed).integers(3, 250, n).tolist()


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(55), SMALL)


# -- what layer l is -----------------------------------------------------------

def test_the_list_is_the_one_place_that_says_what_a_layer_is():
    assert [SMALL.layer_kind(l)[0] for l in range(20)] == list(PERIOD * 2)
    assert all(SMALL.layer_kind(l)[1] == "dense" for l in range(20))
    assert SMALL.layers_of("full") == (5, 15)
    assert len(SMALL.layers_of("ssm")) == 18
    assert SMALL.kv_layers == 2
    # both KV heads of 16 lie side by side in one row of 32
    assert SMALL.kv_pack == 2 and SMALL.kv_row == ((1, 32), (1, 32))
    assert SMALL.lane_state == ("ssm",)
    shapes = kvstate.lane_shapes(SMALL, 3)
    # four heads of 32 side by side along 128 lanes, the state's 128 rows
    assert shapes["ssm_state"] == ((18, 3, 1, 128, 128), jnp.float32)
    assert shapes["ssm_conv"] == ((18, 3, 3, 4 * 32 + 2 * 128), jnp.float32)
    assert kvstate.pool_shapes(SMALL, 9, 16)["k"][0] == (2, 9, 16, 1, 32)


@pytest.mark.parametrize("group,kinds,depth", [
    (0, ["full"] * 6, 6),
    (1, ["mla"] * 6, 6),
    (3, ["kda", "kda", "mla"] * 2, 2)])
def test_the_rule_builds_what_it_built(group, kinds, depth):
    """``layer_group`` 0, 1 and 3 say what they said before there was a
    list, and keep no state-space state."""
    extra = dict(mla_latent=64, mla_nope=32, mla_rope=16, mla_v=32) \
        if group else {}
    if group > 1:
        extra.update(kda_conv=4, kda_gate_bound=-5.0)
    cfg = DecoderConfig(vocab_size=256, dim=128, n_layers=6, n_heads=4,
                        n_kv_heads=4, head_dim=32, hidden_dim=256,
                        layer_group=group, **extra)
    assert [cfg.layer_kind(l)[0] for l in range(6)] == kinds
    assert cfg.kv_layers == depth
    assert cfg.lane_state == (("kda",) if group > 1 else ())
    assert "ssm_state" not in kvstate.lane_shapes(cfg, 2)


# -- the forward pass against the reference ----------------------------------

@pytest.mark.parametrize("cfg", [ONE, SMALL], ids=["one-period", "two"])
def test_the_forward_pass_is_the_references(cfg):
    params = init_decoder(jax.random.PRNGKey(3), cfg)
    tokens = _tokens(70)
    got = np.asarray(decoder_forward(params, jnp.asarray([tokens]), cfg)[0])
    want = _ref_logits(params, tokens, _model(cfg))
    assert np.abs(got - want).max() < TOL * want.std()
    # a token's own row does not decide its logits (the tied head)
    own = want[np.arange(70), tokens]
    assert (want.argmax(-1) == np.asarray(tokens)).mean() < 0.2
    assert np.abs(own.mean() - want.mean()) < 2 * want.std()


def test_prefill_then_decode_through_the_cache_is_the_full_pass(params):
    """A prompt in two chunks through a dense scratch (the second padded),
    then decode steps through it: every position's logits are the
    reference's full forward pass over the whole sequence."""
    tokens = _tokens(45, seed=9)
    want = _ref_logits(params, tokens, _model())
    kv = kvstate.init_kv_cache(SMALL, 1, 64)
    got = []
    for first, real in ((0, 16), (16, 13)):
        row = tokens[first:first + real] + [0] * (16 - real)
        logits, kv = decoder_forward(
            params, jnp.asarray([row]), SMALL,
            positions=first + jnp.arange(16)[None, :], kv_cache=kv,
            cache_len=jnp.asarray([first + 16]), decode=False,
            n_valid=jnp.asarray([real]))
        got.append(np.asarray(logits[0, :real]))
    for at in range(29, 45):
        logits, kv = decoder_forward(
            params, jnp.asarray([[tokens[at]]]), SMALL,
            positions=jnp.asarray([[at]]), kv_cache=kv,
            cache_len=jnp.asarray([at + 1]), decode=True,
            n_valid=jnp.asarray([1]))
        got.append(np.asarray(logits[0]))
    assert np.abs(np.concatenate(got) - want).max() < TOL * want.std()


@pytest.mark.parametrize("change,pack", [
    (dict(), 2),                                   # 2 heads of 16: one row
    (dict(n_heads=8, n_kv_heads=8, head_dim=64, dim=512), 2),   # 128 / 64
    (dict(n_heads=16, n_kv_heads=16, head_dim=16, dim=256), 8),
    (dict(n_heads=3, n_kv_heads=3, head_dim=64, dim=192), 1),   # 3 % 2
    (dict(n_heads=2, n_kv_heads=2, head_dim=128, dim=256,
          attn_scale=0.0), 1),                     # whole rows as they are
    (dict(n_heads=2, n_kv_heads=2, head_dim=48, dim=96,
          attn_scale=0.0), 1),                     # 128 % 48
], ids=lambda v: "" if isinstance(v, dict) else str(v))
def test_heads_per_row_follow_from_the_heads_width(change, pack):
    """No option states the packing: ``kvstate.heads_per_row`` works it out
    for a listed pattern, and a uniform decoder keeps a head a row."""
    cfg = replace(SMALL, **change)
    assert cfg.kv_pack == pack
    assert cfg.kv_row == ((cfg.n_kv_heads // pack, cfg.head_dim * pack),) * 2
    assert kvstate.pool_shapes(cfg, 9, 16)["k"][0][3:] == cfg.kv_row[0]
    plain = DecoderConfig(vocab_size=256, dim=cfg.dim, n_layers=2,
                          n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.head_dim, hidden_dim=128)
    assert plain.kv_pack == 1
    assert plain.kv_row == ((cfg.n_kv_heads, cfg.head_dim),) * 2


def test_packed_rows_give_the_heads_own_attention():
    """``pack_heads`` / ``unpack_heads`` around attention over rows of two
    heads: every query's result is its own head's, at the heads' own
    softmax scale."""
    rng = np.random.default_rng(0)
    b, t, kh, group, d, pack = 1, 9, 4, 2, 16, 2
    q = jnp.asarray(rng.normal(size=(b, t, kh * group, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, t, kh, d)), jnp.float32)
            for _ in range(2))

    def attend(q, k, v):
        width = q.shape[-1]
        rep = q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(a, rep, axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * width ** -0.5
        causal = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    want = attend(q, k, v)
    qp, kp, vp = kvstate.pack_heads(q, k, v, pack)
    assert kp.shape == (b, t, kh // pack, pack * d) and qp.shape[-1] == 32
    got = kvstate.unpack_heads(attend(qp, kp, vp), kh, pack)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_an_idle_lane_keeps_its_state_through_a_decode_step(params):
    kv = kvstate.init_kv_cache(SMALL, 2, 32)
    kv = {n: a + 0.5 if n.startswith("ssm") else a for n, a in kv.items()}
    _, after = decoder_forward(
        params, jnp.asarray([[5], [6]]), SMALL,
        positions=jnp.asarray([[3], [3]]), kv_cache=kv,
        cache_len=jnp.asarray([4, 0]), decode=True,
        n_valid=jnp.asarray([1, 0]))
    for name in ("ssm_state", "ssm_conv"):
        assert (np.asarray(after[name][:, 1]) == 0.5).all()
        assert (np.asarray(after[name][:, 0]) != 0.5).any()


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_fails_the_tolerance_in_float32(params, control):
    """What the chip's comparison has to tell from the sound program moves
    the reference's own logits by more than the float32 program may differ
    from it (the state rounded to bfloat16 the least: 4 tolerances over 60
    tokens)."""
    tokens = _tokens(60, seed=11)
    sound = _ref_logits(params, tokens, _model())
    moved = _ref_logits(params, tokens, _model(control=[control]))
    assert np.abs(moved - sound).max() > 3 * TOL * sound.std()


def test_the_score_is_the_multiplier_and_not_the_root():
    """``attn_scale`` reaches the softmax: at ``head_dim ** -0.5`` (a factor
    of 1 on the queries) the program equals the reference's ``sqrt_scale``
    control, and not its sound form."""
    cfg = replace(ONE, attn_scale=0.25)
    params = init_decoder(jax.random.PRNGKey(4), cfg)
    tokens = _tokens(40)
    got = np.asarray(decoder_forward(params, jnp.asarray([tokens]), cfg)[0])
    root = _ref_logits(params, tokens, _model(cfg, control=["sqrt_scale"]))
    assert np.abs(got - root).max() < TOL * root.std()


# -- what is refused ------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(layer_pattern=PERIOD), "one kind of"),
    (dict(layer_pattern=("mla",) * 20), "one kind of"),
    (dict(layer_group=2, mla_latent=64, mla_nope=32, mla_rope=16, mla_v=32,
          n_kv_heads=4, tie_embeddings=False, rope=True, attn_scale=0.0,
          embed_mult=1.0, residual_mult=1.0, logit_div=1.0,
          kda_conv=4, kda_gate_bound=-5.0), "stated once"),
    (dict(loop_steps=2), "pass loop"),
    (dict(sandwich_norm=True), "pass loop, attn_window, sandwich_norm"),
    (dict(n_experts=4), "experts"),
    (dict(embed_scale=True), "another family"),
    (dict(act="gelu"), "another family"),
    (dict(ssm_state=0), "all needed"),
    (dict(ssm_conv=1), "at least 2 taps"),
    (dict(ssm_groups=3), "groups divide the heads"),
    (dict(attn_scale=0.3), "power of two"),
    (dict(attn_scale=1 / 48), "power of two"),
    (dict(residual_mult=0.0), "positive"),
    (dict(layer_pattern=("full",) * 20), "a uniform decoder"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_unbuilt_combinations_are_refused_with_their_reason(change, match):
    with pytest.raises(ValueError, match=match):
        replace(SMALL, **change)


@pytest.mark.parametrize("change,match", [
    (dict(ssm_heads=4), "without a layer_pattern"),
    (dict(layer_group=1, mla_latent=64, mla_nope=32, mla_rope=16, mla_v=32,
          rope=False), "latent attention has positions"),
    (dict(n_experts=4, residual_mult=0.5), "in their own code"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_new_descriptors_are_refused_where_nothing_reads_them(change,
                                                                  match):
    with pytest.raises(ValueError, match=match):
        DecoderConfig(vocab_size=256, dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=4, head_dim=32, hidden_dim=256, **change)


def test_a_uniform_decoder_takes_the_multipliers_and_no_rotary():
    """Off a pattern the descriptors are plain decoder descriptors: a
    uniform decoder without positions, at a power-of-two scale."""
    cfg = DecoderConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, head_dim=16, hidden_dim=128,
                        rope=False, attn_scale=1 / 16, residual_mult=0.5,
                        dtype=jnp.float32)
    params = init_decoder(jax.random.PRNGKey(1), cfg)
    toks = jnp.asarray([_tokens(12)])
    a = decoder_forward(params, toks, cfg)
    b = decoder_forward(params, toks, replace(cfg, residual_mult=1.0))
    assert np.isfinite(np.asarray(a)).all()
    assert np.abs(np.asarray(a - b)).max() > 1e-3
