"""A listed pattern of HALF-layers (ISSUE 59: every layer a Mamba-2 mixer,
attention without positions or a LatentMoE expert layer ALONE, one norm each)
held to the plain reference ``benchmark/reference/nemotronh.py`` at tiny sizes
on seeded weights: each of the three kinds of layer and the 11-layer stack on
logits, prefill then decode through the lanes' state and the one-plane cache
against the reference's full forward pass, the four shares of an expert layer
adding up to the uncut layer, the ungated kernels against their oracles, the
grouped norm against a loop over its groups, every builder's control FAILING
the tolerance in float32, and what the config refuses. Through the serving
path: ``test_nemotron_serving.py``, which takes this file's tiny
configuration."""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from tpu9.models import decoder_forward, init_decoder, kvstate, moe, ssm
from tpu9.models import transformer
from tpu9.models.transformer import DecoderConfig, moe_cfg
from tpu9.ops import grouped_ffn as grouped_ops
from tpu9.ops import held_ffn as held_ops

PATTERN = "MEMEMEM*EME"          # layers 0-10 of the published pattern
HALVES = {"M": ("ssm", "none"), "*": ("full", "none"), "E": ("none", "experts")}


def _lists(pattern):
    return dict(n_layers=len(pattern),
                layer_pattern=tuple(HALVES[c][0] for c in pattern),
                ffn_pattern=tuple(HALVES[c][1] for c in pattern))


# 8 mixer heads of 16 in 2 groups, state 32; 4 query heads over 2 KV heads of
# 16; 16 routed experts in a latent of 32 of which this chip holds 4 (ids
# 4-7: chip 1 of 4), 4 a token, shared expert 96
SMALL = DecoderConfig(
    vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, head_dim=16,
    hidden_dim=48, norm_eps=1e-5, max_seq_len=512, act="relu2", rope=False,
    ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_groups=2, ssm_conv=4,
    ssm_norm_groups=2, n_experts=4, moe_top_k=4, moe_hidden_dim=48,
    moe_routed=16, moe_held_first=4, moe_shared_dim=96, moe_score="sigmoid",
    moe_select_bias=True, moe_renormalise=True, moe_gate_scale=5.0,
    moe_gated=False, moe_latent_dim=32, dtype=jnp.float32, **_lists(PATTERN))
# what the float32 program may differ from the reference by, as a share of a
# logit's std
TOL = 2e-4
CONTROLS = ("int8_weights", "gated", "no_latent_scale", "whole_norm",
            "no_shared", "bf16_state", "no_decay")


def _model(cfg=SMALL, **kw):
    """``cfg`` in the published config's vocabulary, as the reference reads
    it."""
    return dict({
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "layer_norm_epsilon": cfg.norm_eps,
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
        "conv_kernel": cfg.ssm_conv, "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": True, "routed_scaling_factor": cfg.moe_gate_scale,
        "experts_held": [cfg.moe_held_first, cfg.n_experts]}, **kw)


REF = correctness.load_reference("nemotronh")


def _ref_logits(params, tokens, model):
    return np.asarray(REF.forward(params, jnp.asarray(tokens, jnp.int32),
                                  model))


def _tokens(n, seed=7):
    return np.random.default_rng(seed).integers(3, 250, n).tolist()


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(59), SMALL)


# -- what layer l is -----------------------------------------------------------

def test_the_lists_say_both_halves_of_a_layer(params):
    assert [SMALL.layer_kind(l) for l in range(11)] == [HALVES[c]
                                                        for c in PATTERN]
    assert SMALL.layers_of("ssm") == (0, 2, 4, 6, 9)
    assert SMALL.layers_of("full") == (7,)
    assert SMALL.layers_of("none") == (1, 3, 5, 8, 10)
    assert SMALL.kv_layers == 1 and SMALL.lane_state == ("ssm",)
    shapes = kvstate.lane_shapes(SMALL, 3)
    # a group's four heads of 16 do not fill 128 lanes: a head a row
    assert shapes["ssm_state"] == ((5, 3, 8, 32, 16), jnp.float32)
    assert shapes["ssm_conv"] == ((5, 3, 3, 8 * 16 + 2 * 2 * 32), jnp.float32)
    assert kvstate.pool_shapes(SMALL, 9, 16)["k"][0] == (1, 9, 16, 1, 32)
    # a half-layer keeps ONE norm and no weights for the half it lacks
    assert sorted(params["layers"][0]) == ["attn_norm", "ssm"]
    assert sorted(params["layers"][7]) == ["attn_norm", "wk", "wo", "wq",
                                           "wv"]
    assert sorted(params["layers"][1]) == ["mlp_norm", "moe"]
    # two matrices an expert, in the latent; no gate anywhere in the tree
    tree = params["layers"][1]["moe"]
    assert sorted(tree) == ["bias", "router", "shared", "w_down",
                            "w_latent_in", "w_latent_out", "w_up"]
    assert tree["w_up"].shape == (4, 32, 48)
    assert tree["w_down"].shape == (4, 48, 32)
    assert tree["router"].shape == (64, 16)
    assert sorted(tree["shared"]) == ["w_down", "w_up"]
    assert tree["shared"]["w_up"].shape == (64, 96)
    assert moe_cfg(SMALL).stacks == ("w_up", "w_down")
    assert moe_cfg(SMALL).expert_dim == 32


def test_the_published_widths_keep_a_head_a_row_and_a_lane_21_mb():
    full = replace(SMALL, dim=4096, n_heads=32, n_kv_heads=2, head_dim=128,
                   ssm_heads=128, ssm_head_dim=64, ssm_state=128,
                   ssm_groups=8, ssm_norm_groups=8, n_experts=128,
                   moe_routed=512, moe_held_first=0, moe_top_k=22,
                   moe_hidden_dim=2688, moe_latent_dim=1024,
                   moe_shared_dim=5376, dtype=jnp.bfloat16)
    assert full.kv_pack == 1 and full.kv_row == ((2, 128), (2, 128))
    state, conv = kvstate.lane_shapes(full, 64).values()
    # two heads of 64 side by side: 64 rows a lane of [128, 128] float32
    assert state == ((5, 64, 64, 128, 128), jnp.float32)
    assert conv == ((5, 64, 3, 8192 + 2 * 8 * 128), jnp.bfloat16)
    assert kvstate.lane_bytes(full, 1) == 5 * (128 * 64 * 128 * 4
                                               + 3 * 10240 * 2)
    assert kvstate.block_bytes(full, 1) == 2 * 2 * 128 * 2


# -- each kind of layer against the reference ---------------------------------

def test_the_mixer_is_the_references(params):
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 37, 64), jnp.float32)
    got, _ = ssm.ssm_block(params["layers"][0]["ssm"], u, SMALL, None, 0,
                           False, None)
    want = REF._mixer(params["layers"][0]["ssm"], u[0], _model())
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() \
        < 1e-5 * np.abs(np.asarray(want)).max()


def test_the_grouped_norm_is_a_loop_over_its_groups():
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(9, 128)) * rng.uniform(
        0.1, 10, size=(1, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    want = np.concatenate([
        np.asarray(transformer.rms_norm(y[:, g * 32:(g + 1) * 32],
                                        w[g * 32:(g + 1) * 32], 1e-5))
        for g in range(4)], axis=1)
    got = np.asarray(REF.group_norm(y, w, 4, 1e-5))
    assert np.abs(got - want).max() < 1e-5
    # and the program's, through the mixer: the control that norms over all
    # channels at once is another function
    assert np.abs(np.asarray(REF.group_norm(y, w, 1, 1e-5)) - want).max() > .1


def test_attention_alone_is_the_references(params):
    layer = params["layers"][7]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 29, 64), jnp.float32)
    got, _ = transformer._attn_block(
        layer, x, SMALL, jnp.arange(29)[None], None, None, None, 7, None,
        False)
    want = x[0] + REF._attention(
        layer, REF._rms_norm(x[0], layer["attn_norm"], 1e-5), _model())
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("form", ["held", "sorted"])
def test_the_expert_layer_is_the_references(params, form):
    tree = params["layers"][1]["moe"]
    n = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64), jnp.float32)
    fn = moe.moe_ffn_held if form == "held" else moe.moe_ffn_sorted
    got, picks = fn(tree, n, moe_cfg(SMALL))
    told = []
    want = REF._experts(tree, n.reshape(80, 64), _model(), told=told)
    assert np.abs(np.asarray(got).reshape(80, 64) - np.asarray(want)).max() \
        < 1e-5 * np.abs(np.asarray(want)).max()
    assert (np.sort(np.asarray(picks).reshape(80, 4), -1)
            == np.sort(np.asarray(told[0]["own"]), -1)).all()


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Chips 0-3 each hold 4 of the 16 experts: the routed parts of their
    results — each behind its own ``W_2`` — and the shared expert counted
    ONCE are the uncut reference's layer."""
    whole = replace(SMALL, n_experts=16, moe_held_first=0)
    tree = init_decoder(jax.random.PRNGKey(5), whole)["layers"][1]["moe"]
    n = jax.random.normal(jax.random.PRNGKey(6), (1, 50, 64), jnp.float32)
    want = np.asarray(REF._experts(
        tree, n[0], _model(whole, experts_held=[0, 16])))
    shared = np.asarray(moe.shared_ffn(tree["shared"], n[0], moe_cfg(whole)))
    total = shared.copy()
    for chip in range(4):
        held = slice(4 * chip, 4 * chip + 4)
        share = dict(tree, w_up=tree["w_up"][held],
                     w_down=tree["w_down"][held])
        cfg = replace(SMALL, moe_held_first=4 * chip)
        out, _ = moe.moe_ffn_held(share, n, moe_cfg(cfg))
        # the reference, given the same share, gives the same partial sum
        ref = np.asarray(REF._experts(share, n[0], _model(cfg)))
        assert np.abs(np.asarray(out[0]) - ref).max() < 1e-5
        total += np.asarray(out[0]) - shared
    assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(shared).max() > 0.1 and np.abs(want - shared).max() > 0.1


# -- the stack ------------------------------------------------------------------

@pytest.mark.parametrize("pattern", ["ME", "M*E", PATTERN],
                         ids=["mixer-experts", "one-of-each", "eleven"])
def test_the_forward_pass_is_the_references(pattern):
    cfg = replace(SMALL, **_lists(pattern))
    params = init_decoder(jax.random.PRNGKey(3), cfg)
    tokens = _tokens(70)
    got, picks = decoder_forward(params, jnp.asarray([tokens]), cfg,
                                 return_moe_picks=True)
    want = _ref_logits(params, tokens, _model(cfg))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL * want.std()
    # the picks' layer axis counts the expert layers alone
    assert picks.shape == (1, 70, pattern.count("E"), 4)
    # an untied head: a token's own row does not decide its logits
    assert (want.argmax(-1) == np.asarray(tokens)).mean() < 0.2


def test_prefill_then_decode_through_the_cache_is_the_full_pass(params):
    """A prompt in two chunks through a dense scratch (the second padded),
    then decode steps through it — the lanes' state, the convolution's tail
    and the ONE plane of keys and values: every position's logits are the
    reference's full forward pass over the whole sequence."""
    tokens = _tokens(45, seed=9)
    want = _ref_logits(params, tokens, _model())
    kv = kvstate.init_kv_cache(SMALL, 1, 64)
    assert kv["k"].shape[0] == 1 and kv["ssm_state"].shape[0] == 5
    got = []
    for first, real in ((0, 16), (16, 13)):
        row = tokens[first:first + real] + [0] * (16 - real)
        logits, kv, picks = decoder_forward(
            params, jnp.asarray([row]), SMALL,
            positions=first + jnp.arange(16)[None, :], kv_cache=kv,
            cache_len=jnp.asarray([first + 16]), decode=False,
            n_valid=jnp.asarray([real]), return_moe_picks=True)
        assert picks.shape == (1, 16, 5, 4)
        got.append(np.asarray(logits[0, :real]))
    for at in range(29, 45):
        logits, kv, picks = decoder_forward(
            params, jnp.asarray([[tokens[at]]]), SMALL,
            positions=jnp.asarray([[at]]), kv_cache=kv,
            cache_len=jnp.asarray([at + 1]), decode=True,
            n_valid=jnp.asarray([1]), return_moe_picks=True)
        assert picks.shape == (1, 1, 5, 4)
        got.append(np.asarray(logits[0]))
    assert np.abs(np.concatenate(got) - want).max() < TOL * want.std()


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_fails_the_tolerance_in_float32(params, control):
    """What the chip's comparison has to tell from the sound program moves
    the reference's own logits by more than the float32 program may differ
    from it."""
    tokens = _tokens(60, seed=11)
    sound = _ref_logits(params, tokens, _model())
    moved = _ref_logits(params, tokens, _model(control=[control]))
    assert np.abs(moved - sound).max() > 3 * TOL * sound.std()


def test_the_reference_takes_a_served_choice_only_inside_a_tie(params):
    """``route`` with a served choice: taken where it is the reference's own
    or a tie within ``routing_tie``; any other choice is left."""
    tree = params["layers"][1]["moe"]
    n = jax.random.normal(jax.random.PRNGKey(8), (6, 64), jnp.float32)
    told = []
    _, own = REF.route(tree, n, _model(), told=told)
    choice = np.asarray(told[0]["choice"])
    order = np.argsort(-choice, axis=-1)
    # the runner-up in place of the last chosen: a tie of their difference
    swapped = np.asarray(own).copy()
    swapped[:, -1] = order[:, 4]
    gap = choice[np.arange(6), order[:, 3]] - choice[np.arange(6), order[:, 4]]
    for tie, taken in ((gap.max() / 2 + 1e-6, True), (gap.min() / 4, False)):
        _, chosen = REF.route(tree, n, _model(routing_tie=float(tie)),
                              served=jnp.asarray(swapped))
        same = (np.sort(np.asarray(chosen), -1)
                == np.sort(swapped, -1)).all(-1)
        assert same.all() if taken else not same.any()


# -- the ungated kernels against their oracles --------------------------------

def test_the_ungated_held_kernel_is_its_oracle():
    rng = np.random.default_rng(0)
    n, e, d, h = 8, 6, 128, 256
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(e, d, h)) * d ** -0.5, jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(e, h, d)) * h ** -0.5, jnp.float32)
    local = jnp.asarray(rng.integers(-3, e, size=(n, 2)), jnp.int32)
    live = jnp.asarray([True] * 6 + [False] * 2)
    weight = (jax.nn.one_hot(local, e) * live[:, None, None]
              * jnp.asarray(rng.uniform(0.2, 1, (n, 2, 1)))).sum(1)
    ids, count = held_ops.touched_experts(local, live, e)
    got = np.asarray(jax.block_until_ready(held_ops.held_ffn_kernel(
        x, weight, ids, count, w_up, w_down, act="relu2", interpret=True)))
    want = np.asarray(held_ops.held_ffn_xla(x, weight, ids, count, w_up,
                                            w_down, act="relu2"))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    plain = sum(weight[:, j, None] * (
        jnp.square(jax.nn.relu(x @ w_up[j])) @ w_down[j]) for j in range(e))
    assert np.abs(want - np.asarray(plain)).max() < 1e-4 * np.abs(want).max()
    # two matrices of 1,024 x 2,688 bf16 an expert fit a step whole, and a
    # width no tile of 512 or 256 divides is taken whole by the grouped form
    assert held_ops._step_tile(1024, 2688, 2, 2) == 2688
    assert grouped_ops._hidden_tile(2688, 2 * 1024 * 2688 * 2) == 2688
    assert grouped_ops._hidden_tile(2688) == 128
    assert grouped_ops._hidden_tile(768, 10 ** 6) == 256


def test_the_ungated_grouped_kernel_is_its_oracle():
    rng = np.random.default_rng(1)
    e, d, h, tm = 5, 128, 384, grouped_ops.ROW_TILE
    tiles = jnp.asarray([1, 0, 2, 1, 0], jnp.int32)
    r = (int(tiles.sum()) + 1) * tm
    xs = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
    w_up = jnp.asarray(rng.normal(size=(e, d, h)) * d ** -0.5, jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(e, h, d)) * h ** -0.5, jnp.float32)
    used = int(tiles.sum()) * tm
    got = np.asarray(jax.block_until_ready(grouped_ops.grouped_ffn_kernel(
        xs, tiles, w_up, w_down, act="relu2", interpret=True)))[:used]
    want = np.asarray(grouped_ops.grouped_ffn_xla(
        xs, tiles, w_up, w_down, act="relu2"))[:used]
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    # through the sorted layer, the kernel in place of the oracle
    cfg = moe_cfg(SMALL)
    tree = init_decoder(jax.random.PRNGKey(59), SMALL)["layers"][1]["moe"]
    n = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64), jnp.float32)
    a, _ = moe.moe_ffn_sorted(tree, n, cfg, grouped_ffn=functools.partial(
        grouped_ops.grouped_ffn_kernel, interpret=True))
    a = np.asarray(jax.block_until_ready(a))
    b, _ = moe.moe_ffn_sorted(tree, n, cfg)
    assert np.abs(a - np.asarray(b)).max() < 1e-5


# -- what is refused ------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(ffn_pattern=SMALL.ffn_pattern[:5]), "for each of the 11 layers"),
    (dict(ffn_pattern=("dense",) + SMALL.ffn_pattern[1:]),
     "a dense feed-forward part is the rule's"),
    (dict(ffn_pattern=("experts",) + SMALL.ffn_pattern[1:]), "ONE half"),
    (dict(layer_pattern=("none",) + SMALL.layer_pattern[1:]), "ONE half"),
    (dict(ffn_pattern=()), "attn_window or experts"),
    (dict(ffn_pattern=(), n_experts=0, moe_routed=0, moe_shared_dim=0,
          moe_score="softmax", act="silu", moe_gated=True, moe_latent_dim=0),
     "\"none\" only beside an ffn_pattern"),
    (dict(n_experts=0), "there are experts"),
    (dict(moe_dense_layers=1), "no rule beside it"),
    (dict(moe_routed=0), "told which experts it holds"),
    (dict(moe_held_first=14), "inside the routed ones"),
    (dict(moe_gated=True), "ungated relu2 experts alone"),
    (dict(act="silu"), "another family"),
    (dict(moe_latent_dim=-1), "ungated relu2 experts alone"),
    (dict(ssm_norm_groups=3), "whole heads"),
    (dict(layer_group=2), "with a layer_group"),
    (dict(loop_steps=2), "pass loop"),
    (dict(residual_mult=0.5), "in their own code"),
    (dict(attn_scale=0.25), "in their own code"),
    (dict(moe_score="softmax"), "sigmoid scores only"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_unbuilt_combinations_are_refused_with_their_reason(change, match):
    with pytest.raises(ValueError, match=match):
        replace(SMALL, **change)


@pytest.mark.parametrize("change,match", [
    (dict(ffn_pattern=("experts", "none")), "without a layer_pattern"),
    (dict(moe_gated=False), "without an ffn_pattern"),
    (dict(moe_latent_dim=32), "without an ffn_pattern"),
    (dict(ssm_norm_groups=2), "without a layer_pattern"),
    (dict(act="relu2"), "without an ffn_pattern"),
    (dict(n_experts=4, moe_routed=8), "for a layer pattern only"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_new_descriptors_are_refused_where_nothing_reads_them(change,
                                                                  match):
    with pytest.raises(ValueError, match=match):
        DecoderConfig(vocab_size=256, dim=128, n_layers=2, n_heads=4,
                      n_kv_heads=4, head_dim=32, hidden_dim=256, **change)


def test_a_list_without_halves_keeps_refusing_experts_and_the_new_forms():
    granite = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, head_dim=16, hidden_dim=128,
                   layer_pattern=("ssm", "full"), ssm_heads=4,
                   ssm_head_dim=32, ssm_state=32, ssm_conv=4, rope=False)
    DecoderConfig(**granite)
    for change, match in ((dict(n_experts=4), "experts"),
                          (dict(moe_gated=False), "no layer would read"),
                          (dict(moe_latent_dim=8), "no layer would read"),
                          (dict(act="relu2"), "without an ffn_pattern")):
        with pytest.raises(ValueError, match=match):
            DecoderConfig(**granite, **change)
