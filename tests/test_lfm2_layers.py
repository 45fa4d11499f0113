"""A list of WHOLE layers around gated short convolutions (ISSUE 62: three
``"conv"`` layers to one of rotary attention under a norm a head on queries
and keys, two dense layers then sigmoid-routed experts that are all held, a
tied head) held to the plain reference ``benchmark/reference/lfm2.py`` at
tiny sizes on seeded weights: the forward pass, prefill then decode through
the cache, a prompt split over chunks (the tail carried), every builder's
control FAILING the tolerance in float32, what the list says of a layer,
what the state is, and what the config refuses. Through the serving path and
the prefix cache: ``test_lfm2_serving.py``, which takes this file's tiny
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

# the published order at stage 0's proportions: two leading mixers (the
# dense layers), then whole periods of attention and three mixers
PATTERN = ("conv", "conv") + ("full", "conv", "conv", "conv") * 2
SMALL = DecoderConfig(
    vocab_size=256, dim=64, n_layers=10, n_heads=4, n_kv_heads=2,
    head_dim=16, hidden_dim=160, norm_eps=1e-5, rope_theta=1e6,
    max_seq_len=512, tie_embeddings=True, layer_pattern=PATTERN,
    conv_taps=3, qk_norm=True, n_experts=8, moe_top_k=2, moe_hidden_dim=48,
    moe_routed=8, moe_dense_layers=2, moe_score="sigmoid",
    moe_select_bias=True, moe_renormalise=True, moe_gate_scale=1.0,
    dtype=jnp.float32)
# what the float32 program may differ from the reference by, as a share of a
# logit's std: both are float32, and what is left is the order of the sums
# (the program's experts by gathered rows, the reference's one at a time)
# and the gates' 1e-6 against max(., 1e-9)
TOL = 2e-4
CONTROLS = ("int8_weights", "no_conv_gate", "no_in_gate", "two_taps",
            "no_qk_norm", "bias_in_gates", "no_renormalise")


def _model(cfg=SMALL, **kw):
    """``cfg`` in the published config's vocabulary, as the reference reads
    it."""
    return dict({
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_renormalise,
        "routed_scaling_factor": cfg.moe_gate_scale}, **kw)


def _ref_logits(params, tokens, model):
    ref = correctness.load_reference("lfm2")
    return np.asarray(ref.forward(params, jnp.asarray(tokens, jnp.int32),
                                  model))


def _tokens(n, seed=7):
    return np.random.default_rng(seed).integers(3, 250, n).tolist()


@pytest.fixture(scope="module")
def params():
    """Seeded, with the norms a head moved off 1 and the choice's bias made
    large enough to flip picks at these few experts: a weight that the
    program left out would otherwise go unseen."""
    p = init_decoder(jax.random.PRNGKey(62), SMALL)
    for i, layer in enumerate(p["layers"]):
        key = jax.random.fold_in(jax.random.PRNGKey(7), i)
        if "q_norm" in layer:
            for j, name in enumerate(("q_norm", "k_norm")):
                layer[name] = 1.0 + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, j), layer[name].shape)
        if "moe" in layer:
            layer["moe"]["bias"] = 0.1 * jax.random.normal(
                key, layer["moe"]["bias"].shape)
    return p


# -- what layer l is, and what it keeps ----------------------------------------

def test_the_list_is_the_one_place_that_says_what_a_layer_is():
    assert [SMALL.layer_kind(l)[0] for l in range(10)] == list(PATTERN)
    assert [SMALL.layer_kind(l)[1] for l in range(10)] \
        == ["dense"] * 2 + ["experts"] * 8
    assert SMALL.layers_of("full") == (2, 6)
    assert SMALL.kv_layers == 2 and SMALL.lane_state == ("conv",)
    # both KV heads of 16 lie side by side in one row of 32
    assert SMALL.kv_pack == 2 and SMALL.kv_row == ((1, 32), (1, 32))
    # a lane kind of ONE array: the tail is the state
    assert kvstate.LANE_KINDS["conv"] == ("conv_tail",)
    assert kvstate.lane_shapes(SMALL, 3) \
        == {"conv_tail": ((8, 3, 2, 64), jnp.float32)}
    # and a plane a BLOCK, in the pool and in a dense cache cut into pages
    assert kvstate.block_tail_shapes(SMALL, 9) \
        == {kvstate.BLOCK_TAIL: ((8, 9, 2, 64), jnp.float32)}
    dense = kvstate.dense_shapes(SMALL, 1, 64, block=16)
    assert dense[kvstate.BLOCK_TAIL][0] == (8, 1, 4, 2, 64)
    assert kvstate.BLOCK_TAIL not in kvstate.dense_shapes(SMALL, 1, 64)
    assert kvstate.block_tail_bytes(SMALL, 9) == 8 * 9 * 2 * 64 * 4
    assert kvstate.pool_shapes(SMALL, 9, 16)["k"][0] == (2, 9, 16, 1, 32)


def test_the_tree_holds_what_each_layer_is(params):
    for l, layer in enumerate(params["layers"]):
        mixer, ffn = SMALL.layer_kind(l)
        assert ("conv" in layer) == (mixer == "conv")
        assert ("q_norm" in layer) == ("wq" in layer) == (mixer == "full")
        assert ("moe" in layer) == (ffn == "experts")
        assert ("w_gate" in layer) == (ffn == "dense")
        assert {"attn_norm", "mlp_norm"} <= set(layer)
    conv = params["layers"][0]["conv"]
    assert conv["w_in"].shape == (64, 192) and conv["conv"].shape == (3, 64)
    moe = params["layers"][2]["moe"]
    assert moe["w_up"].shape == (8, 64, 48) and moe["bias"].shape == (8,)
    assert "shared" not in moe and "lm_head" not in params


@pytest.mark.parametrize("other", [
    DecoderConfig(vocab_size=256, dim=64, n_layers=4, n_heads=4,
                  n_kv_heads=2, head_dim=16, hidden_dim=128,
                  layer_pattern=("ssm", "full") * 2, ssm_heads=4,
                  ssm_head_dim=32, ssm_state=128, ssm_conv=4, rope=False),
    DecoderConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, head_dim=16, hidden_dim=128)])
def test_other_decoders_keep_no_state_a_block(other):
    """Only a list with ``"conv"`` layers has the plane: every other
    decoder's pool, scratch and programs are what they were."""
    assert kvstate.block_tail_shapes(other, 9) == {}
    assert kvstate.block_tail_bytes(other, 9) == 0
    assert kvstate.BLOCK_TAIL not in kvstate.dense_shapes(other, 1, 64,
                                                          block=16)


# -- the program against the reference -----------------------------------------

def _close(got, want):
    return float(np.abs(got - want).max() / want.std())


def test_forward_matches_the_reference(params):
    tokens = _tokens(70)
    want = _ref_logits(params, tokens, _model())
    got = np.asarray(decoder_forward(params, jnp.asarray([tokens]), SMALL)[0])
    assert _close(got, want) < TOL


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_fails_the_comparison(params, control):
    """Each control leaves out one thing of the model, and the program's
    logits then differ from the reference's by many times ``TOL``: a program
    that lacked that thing would be seen."""
    tokens = _tokens(70)
    got = np.asarray(decoder_forward(params, jnp.asarray([tokens]), SMALL)[0])
    wrong = _ref_logits(params, tokens, _model(control=[control]))
    assert _close(got, wrong) > 10 * TOL, control


def _through_cache(params, tokens, splits, block=0):
    """The logits of ``tokens`` fed in pieces at ``splits`` through one dense
    cache, then a token at a time: ``[T, V]``."""
    n = len(tokens)
    kv = kvstate.init_kv_cache(SMALL, 1, 128, block=block)
    out, at = [], 0
    for end in list(splits) + list(range(splits[-1] + 1, n + 1)):
        piece = jnp.asarray([tokens[at:end]])
        positions = at + jnp.arange(end - at)[None]
        logits, kv, _ = decoder_forward(
            params, piece, SMALL, positions=positions, kv_cache=kv,
            cache_len=jnp.asarray([end]), decode=end - at == 1 and at > 0,
            n_valid=jnp.asarray([end - at]), return_moe_picks=True)
        out.append(np.asarray(logits[0]))
        at = end
    return np.concatenate(out), kv


@pytest.mark.parametrize("splits", [(48,), (16, 32, 48), (16, 48)])
def test_prefill_in_chunks_then_decode_matches_the_reference(params, splits):
    """The tail is carried chunk to chunk and then step to step: a prompt in
    one piece, in three, and in unequal two, each followed by twelve decode
    steps, gives the reference's logits of the whole sequence."""
    tokens = _tokens(60, seed=11)
    want = _ref_logits(params, tokens, _model())
    got, _ = _through_cache(params, tokens, splits)
    assert _close(got, want) < TOL


def test_a_chunk_leaves_the_tail_of_every_page_it_fills(params):
    """The tail a BLOCK is the tail a LANE as of that block's last row: a
    prompt fed in pages of 16 leaves, for page ``j``, what feeding its first
    ``16 (j + 1)`` tokens alone leaves in the lane."""
    tokens = _tokens(48, seed=13)
    _, kv = _through_cache(params, tokens, (32, 48), block=16)
    pages = np.asarray(kv[kvstate.BLOCK_TAIL])            # [P, 1, 8, 2, D]
    assert pages.shape == (8, 1, 8, 2, 64)
    for j in range(3):
        _, upto = _through_cache(params, tokens[:16 * (j + 1)],
                                 (16 * (j + 1),))
        np.testing.assert_allclose(pages[:, 0, j],
                                   np.asarray(upto["conv_tail"])[:, 0],
                                   atol=2e-5)
    assert not pages[:, 0, 3:].any()


def test_a_padded_chunk_and_an_idle_lane_leave_the_tail(params):
    tokens = _tokens(16, seed=5)
    kv = kvstate.init_kv_cache(SMALL, 1, 64)
    _, kv, _ = decoder_forward(
        params, jnp.asarray([tokens]), SMALL, kv_cache=kv,
        cache_len=jnp.asarray([16]), n_valid=jnp.asarray([10]),
        return_moe_picks=True)
    _, upto = _through_cache(params, tokens[:10], (10,))
    np.testing.assert_allclose(np.asarray(kv["conv_tail"]),
                               np.asarray(upto["conv_tail"]), atol=1e-6)
    _, idle, _ = decoder_forward(
        params, jnp.asarray([[9]]), SMALL, positions=jnp.asarray([[10]]),
        kv_cache=kv, cache_len=jnp.asarray([0]), decode=True,
        n_valid=jnp.asarray([0]), return_moe_picks=True)
    np.testing.assert_array_equal(np.asarray(idle["conv_tail"]),
                                  np.asarray(kv["conv_tail"]))


# -- what the config refuses ---------------------------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(layer_pattern=("conv", "ssm") + PATTERN[2:], ssm_heads=4,
          ssm_head_dim=32, ssm_state=128, ssm_conv=4), "second kind"),
    (dict(ffn_pattern=("none",) * 10, act="relu2"), "half-layers"),
    (dict(layer_pattern=("conv",) * 10), "no plane"),
    (dict(conv_taps=1), "at least 2 taps"),
    (dict(conv_taps=0), "at least 2 taps"),
    (dict(rope=False), "positions"),
    (dict(residual_mult=0.5), "multiplier"),
    (dict(loop_steps=2), "pass loop"),
    (dict(attn_window=64, attn_chunk=16), "experts or a pass loop"),
    (dict(moe_routed=16), "hold every expert"),
    (dict(moe_shared_dim=32), "hold every expert"),
    (dict(moe_latent_dim=16, moe_gated=False, act="relu2"),
     "without an ffn_pattern"),
    (dict(moe_routed=0), "told which experts"),
    (dict(moe_dense_layers=10), "leading run"),
    (dict(n_experts=0, moe_routed=0, moe_score="softmax",
          moe_select_bias=False), "no layer would read it"),
    (dict(layer_pattern=("full",) * 10), "without a \"conv\" layer"),
    (dict(layer_pattern=(), n_experts=0, moe_routed=0, moe_dense_layers=0,
          moe_score="softmax", moe_select_bias=False),
     "without a \"conv\" layer"),
])
def test_the_config_refuses_what_is_not_built(kw, needle):
    with pytest.raises(ValueError, match=needle):
        replace(SMALL, **kw)


def test_a_list_of_mixers_and_attention_alone_builds():
    """No experts at all is a list this family could state too: whole layers
    closed by the dense SwiGLU."""
    plain = replace(SMALL, n_experts=0, moe_routed=0, moe_dense_layers=0,
                    moe_score="softmax", moe_select_bias=False)
    assert {plain.layer_kind(l)[1] for l in range(10)} == {"dense"}
