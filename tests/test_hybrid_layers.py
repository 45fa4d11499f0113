"""A layer PATTERN (ISSUE 48): delta-rule linear attention with a decay a
channel (KDA) over a state a LANE, latent attention (MLA) over a cache of one
row a token, and an expert layer that is told which experts it holds — held
to the plain reference ``benchmark/reference/ling.py`` layer kind by layer
kind and whole; the forms of the recurrence against each other; the chip's
share against the uncut layer; and every descriptor combination that is
refused. The serving programs and the engine: ``test_hybrid_serving.py``."""

import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling as reference
from tpu9.models import decoder_forward, init_decoder, init_kv_cache
from tpu9.models import hybrid, kvstate
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.moe import (SORTED_MIN_TOKENS, _top_k_gates, moe_ffn_held,
                             moe_ffn_sorted)
from tpu9.models.transformer import DecoderConfig, moe_cfg
from tpu9.ops import delta_rule
from tpu9.ops.latent_attention import (WAVE_PAGES, expanded_attention,
                                       pack_rotated, paged_latent_attention,
                                       unpack_rotated)
from tpu9.ops.rotary import rope_rows

# the rehearsal's tiny widths: two periods of 3 in 6 layers (KDA, KDA, MLA),
# layer 0 dense, 16 experts routed in 4 groups of which 2 are kept, top-4
SMALL = DecoderConfig(
    vocab_size=256, dim=128, n_layers=6, n_heads=4, n_kv_heads=4,
    head_dim=32, hidden_dim=256, norm_eps=1e-6, rope_theta=6e6,
    max_seq_len=512, layer_group=3, mla_latent=64, mla_nope=32, mla_rope=16,
    mla_v=32, kda_conv=4, kda_gate_bound=-5.0, n_experts=16, moe_top_k=4,
    moe_dense_layers=1, moe_hidden_dim=64, moe_routed=16, moe_held_first=0,
    moe_shared_dim=64, moe_score="sigmoid", moe_select_bias=True,
    moe_groups=4, moe_top_groups=2, moe_renormalise=True, moe_gate_scale=2.5,
    dtype=jnp.float32)
PLAIN = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
BS = 16
# float32 on both sides: the order of the sums alone
TOL = 5e-4


def _model(cfg=SMALL, **more):
    return dict({
        "num_attention_heads": cfg.n_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.norm_eps, "kda_lower_bound": cfg.kda_gate_bound,
        "qk_nope_head_dim": cfg.mla_nope, "qk_rope_head_dim": cfg.mla_rope,
        "v_head_dim": cfg.mla_v, "kv_lora_rank": cfg.mla_latent,
        "rope_theta": cfg.rope_theta, "num_experts_per_tok": cfg.moe_top_k,
        "n_group": cfg.moe_groups, "topk_group": cfg.moe_top_groups,
        "norm_topk_prob": cfg.moe_renormalise,
        "routed_scaling_factor": cfg.moe_gate_scale,
        "experts_held": [cfg.moe_held_first, cfg.n_experts]}, **more)


@pytest.fixture(scope="module")
def params():
    return init_decoder(jax.random.PRNGKey(48), SMALL)


def _ref_logits(params, tokens, cfg=SMALL, **more):
    return np.asarray(jax.jit(
        lambda p, x: reference.forward(p, x, _model(cfg, **more)))(
            params, jnp.asarray(tokens, jnp.int32)))


def _margin(row, token):
    return float(row.max() - row[token])


def _normed(seed, t, dim=SMALL.dim):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, dim), jnp.float32)


# ---------------------------------------------------------------------------
# what layer l is
# ---------------------------------------------------------------------------

def test_layer_kind_is_the_one_place_that_knows_the_pattern():
    assert [SMALL.layer_kind(l) for l in range(6)] == [
        ("kda", "dense"), ("kda", "experts"), ("mla", "experts"),
        ("kda", "experts"), ("kda", "experts"), ("mla", "experts")]
    assert SMALL.layers_of("kda") == (0, 1, 3, 4)
    assert SMALL.layers_of("mla") == (2, 5) and SMALL.kv_layers == 2
    assert SMALL.kv_row == ((1, 64), (1, 16))
    # the published pattern: 5 KDA layers to 1 MLA layer, one dense layer
    six = replace(SMALL, layer_group=6)
    assert [six.layer_kind(l)[0] for l in range(6)] == ["kda"] * 5 + ["mla"]
    assert six.kv_layers == 1
    # a plain decoder: every layer the plain attention, the cache as it was
    assert PLAIN.layer_kind(1) == ("full", "dense")
    assert PLAIN.kv_layers == PLAIN.n_layers
    assert PLAIN.kv_row == ((PLAIN.n_kv_heads, PLAIN.head_dim),) * 2
    assert kvstate.lane_shapes(PLAIN, 4) == {}
    mixtral = replace(PLAIN, n_experts=8)
    assert mixtral.layer_kind(0) == ("full", "experts")


def test_the_trees_follow_the_pattern(params):
    for l, layer in enumerate(params["layers"]):
        attention, ffn = SMALL.layer_kind(l)
        assert (attention in layer) and ("wq" not in layer)
        assert ("moe" in layer) == (ffn == "experts")
        assert ("w_up" in layer) == (ffn == "dense")
    moe = params["layers"][1]["moe"]
    assert moe["router"].shape == (128, 16) and moe["bias"].shape == (16,)
    assert moe["w_gate"].shape == (16, 128, 64)
    assert moe["shared"]["w_down"].shape == (64, 128)
    cache = init_kv_cache(SMALL, 3, 64)
    assert cache["k"].shape == (2, 3, 64, 1, 64)
    assert cache["v"].shape == (2, 3, 64, 1, 16)
    assert cache["kda_state"].shape == (4, 3, 4, 32, 32)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (4, 3, 3, 3 * 128)
    assert kvstate.lane_bytes(SMALL, 3) == 4 * 3 * (
        4 * 32 * 32 * 4 + 3 * 384 * 4)


# ---------------------------------------------------------------------------
# each kind of layer alone, and the whole model, against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [40, 64, 100, 192])
def test_a_kda_layer_equals_the_reference(params, t):
    """One block, a length that is no whole block (the per-token scan) and
    three blocks of the chunkwise form."""
    p, n = params["layers"][0]["kda"], _normed(t, t)
    got, _ = hybrid.kda_block(p, n[None], SMALL, None, 0, False, None)
    want = reference._kda(p, n, _model())
    assert np.abs(np.asarray(got[0] - want)).max() < TOL


@pytest.mark.parametrize("t", [1, 40, 130])
def test_an_mla_layer_equals_the_reference(params, t):
    p, n = params["layers"][2]["mla"], _normed(t + 7, t)
    positions = jnp.arange(t)[None]
    sin, cos = rope_rows(positions, SMALL.mla_rope, SMALL.rope_theta)
    got, _ = hybrid.mla_block(p, n[None], SMALL, positions, sin, cos, None,
                              0, None, False)
    want = reference._mla(p, n, _model())
    assert np.abs(np.asarray(got[0] - want)).max() < TOL


@pytest.mark.parametrize("form,t", [("held", 24), ("held", SORTED_MIN_TOKENS),
                                    ("held_kernel", 24),
                                    ("held_kernel", SORTED_MIN_TOKENS),
                                    ("sorted", 24), ("sorted", 300)])
@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)])
def test_an_expert_layer_equals_the_reference(monkeypatch, params, form, t,
                                              held):
    """The dropless forms — the held experts' einsums, the ``held_ffn``
    kernel (interpreted) that reads the touched ones, the sorted layout —
    on the uncut layer and two chips' shares of it: the held experts' terms
    with gates normalised over all chosen."""
    import tpu9.ops.held_ffn as held_ops
    if form == "held_kernel":
        # done before the layer's next eager line is queued behind it: the
        # interpreter's callbacks run jax computations of their own
        monkeypatch.setattr(held_ops, "held_ffn", lambda *a, **kw:
                            jax.block_until_ready(held_ops.held_ffn_kernel(
                                *a, interpret=True, **kw)))
    first, count = held
    cfg = replace(SMALL, n_experts=count, moe_held_first=first)
    moe = dict(params["layers"][1]["moe"])
    for name in ("w_gate", "w_up", "w_down"):
        moe[name] = moe[name][first:first + count]
    h = _normed(t + first, t)
    got, picks = (moe_ffn_sorted if form == "sorted" else moe_ffn_held)(
        moe, h[None], moe_cfg(cfg))
    want = reference._experts(moe, h, _model(cfg))
    assert np.abs(np.asarray(got[0] - want)).max() < TOL
    # the layer also says which experts every token chose: global ids, the
    # reference's own choice (held here or not)
    _, chosen = reference.route(moe, h, _model(cfg))
    assert picks.shape == (1, t, 4)
    assert (np.sort(np.asarray(picks[0]), -1)
            == np.sort(np.asarray(chosen), -1)).all()


def test_rows_no_tile_holds_never_reach_the_result(params):
    """The grouped kernel writes the tiles that hold rows and nothing else:
    on the chip the rest of its output is whatever the memory held. A pick
    that fell on an expert held elsewhere gathers such a row (its index is
    clamped onto the layout's last), and must select it away — a gate of 0
    times NaN is NaN. Found on the chip: the second set of weights a
    process served read the first's memory."""
    from tpu9.ops import grouped_ffn as ops
    cfg = replace(SMALL, n_experts=4, moe_held_first=4)
    moe = dict(params["layers"][1]["moe"])
    for name in ("w_gate", "w_up", "w_down"):
        moe[name] = moe[name][4:8]
    h = _normed(77, 300)

    def poisoned(xs, tiles, *weights, act):
        ys = ops.grouped_ffn_xla(xs, tiles, *weights, act=act)
        written = jnp.arange(ys.shape[0]) < tiles.sum() * ops.ROW_TILE
        return jnp.where(written[:, None], ys, jnp.nan)

    got, _ = moe_ffn_sorted(moe, h[None], moe_cfg(cfg),
                            grouped_ffn=poisoned)
    want = reference._experts(moe, h, _model(cfg))
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got[0] - want)).max() < TOL


@pytest.mark.parametrize("t", [40, 100, 320])
def test_forward_without_a_cache_equals_the_reference(params, t):
    tokens = np.random.default_rng(t).integers(3, 256, t)
    got = decoder_forward(params, jnp.asarray(tokens)[None], SMALL)[0]
    assert np.abs(np.asarray(got) - _ref_logits(params, tokens)).max() < TOL


@pytest.mark.parametrize("control", ["no_decay", "no_rope_score",
                                     "no_shared", "bias_in_gates",
                                     "int8_weights"])
def test_the_references_controls_move_the_logits(params, control):
    """What the tolerance is set against: each control is another model."""
    tokens = np.random.default_rng(5).integers(3, 256, 80)
    sound = _ref_logits(params, tokens)
    moved = _ref_logits(params, tokens, control=(control,))
    assert np.abs(moved - sound).max() > 100 * TOL


def test_the_reference_takes_a_served_choice_only_at_a_tie(params):
    """``reference.route`` with the system's choice beside it: a served set
    of experts is taken where the reference's own rules give it once each
    served expert's score is raised by ``routing_tie`` and every other
    lowered by it, so a swap of two experts is taken while their scores lie
    within twice the tie and never beyond; the gates are the reference's own
    scores of the experts it runs; a row of -1 keeps the reference's choice."""
    moe, h = params["layers"][1]["moe"], _normed(11, 64)
    told = []
    reference.route(moe, h, _model(), None, told)
    choice, own = np.asarray(told[0]["choice"]), np.asarray(told[0]["own"])
    # each token's next-best expert inside the groups it kept, in the place
    # of its last pick: the gap between the two scores is what a tie closes
    groups = own // 4
    served, gap = own.copy(), np.zeros(64)
    for t in range(64):
        rest = [e for e in np.argsort(-choice[t]) if e not in own[t]
                and e // 4 in groups[t]]
        served[t, -1] = rest[0]
        gap[t] = choice[t, own[t, -1]] - choice[t, rest[0]]
    assert (gap > 0).all()
    served[5] = -1                                 # nothing kept for token 5
    tie = float(np.median(gap)) / 2
    said = []
    gates, ran = reference.route(moe, h, _model(routing_tie=tie),
                                 jnp.asarray(served), said)
    taken = np.asarray(said[0]["taken"])
    near = gap < 2 * tie
    near[5] = False
    assert (taken == near).all() and 20 < taken.sum() < 44
    assert (np.asarray(ran) == np.where(taken[:, None], served, own)).all()
    # the gates: the reference's own scores of what it runs, renormalised
    scores = choice - np.asarray(moe["bias"])
    want = np.take_along_axis(scores, np.asarray(ran), 1)
    want = want / want.sum(-1, keepdims=True) * SMALL.moe_gate_scale
    assert np.abs(np.asarray(gates) - want).max() < 1e-6
    # a choice from a group the reference dropped, far from any tie: refused
    far = own.copy()
    far[:, 0] = np.argmin(choice, -1)
    said = []
    reference.route(moe, h, _model(routing_tie=tie), jnp.asarray(far), said)
    assert not np.asarray(said[0]["taken"]).any()


def test_a_served_routing_reaches_the_logits_only_where_it_is_taken(
        params, monkeypatch):
    """``forward`` takes the provider's records as constants of its trace
    and finds on the device the one its tokens continue: a served routing
    that is the reference's own changes nothing; one that is far from a tie
    is refused at every position and changes nothing either; a record of
    another sequence is no record; with no provider, or no ``routing_tie``,
    nobody is asked."""
    from benchmark.reference import served_routing
    tokens = np.random.default_rng(6).integers(3, 256, 48)
    told = []
    sound = np.asarray(reference.forward(params, jnp.asarray(tokens),
                                         _model(), told))
    own = np.stack([np.asarray(t["own"]) for t in told], 1)    # [T, 5, k]
    asked = []

    def provider(*records):
        def give():
            asked.append(len(records))
            return list(records)
        return give

    other = (tokens[:40] + 1, (own[:40] + 5) % 16)
    run = jax.jit(lambda x, tie: reference.forward(
        params, x, _model(routing_tie=tie)), static_argnums=1)
    monkeypatch.setattr(served_routing, "provider",
                        provider(other, (tokens[:40], own[:40])))
    same = run(jnp.asarray(tokens), 1e-4)
    # (jitted against eager: rounding alone)
    assert asked == [2] and np.abs(np.asarray(same) - sound).max() < 1e-4
    far = (tokens[:40], (own[:40] + 5) % 16)
    monkeypatch.setattr(served_routing, "provider", provider(far))
    refused = reference.forward(params, jnp.asarray(tokens),
                                _model(routing_tie=1e-4))
    assert np.abs(np.asarray(refused) - sound).max() < 1e-6
    # taken everywhere (a tie wider than any score): other experts run —
    told = []
    forced = reference.forward(params, jnp.asarray(tokens),
                               _model(routing_tie=2.0), told)
    assert np.abs(np.asarray(forced) - sound).max() > 100 * TOL
    # (where the served experts lie in two groups: the rules give no other
    # set), and never past the record's 40 positions
    taken = np.asarray(told[0]["taken"])
    assert taken[:40].sum() > 10 and not taken[40:].any()
    # — unless the record is another sequence's, or longer than the tokens
    monkeypatch.setattr(served_routing, "provider", provider(
        other, (np.concatenate([tokens, tokens]), np.tile(far[1], (3, 1, 1)))))
    nobody = reference.forward(params, jnp.asarray(tokens),
                               _model(routing_tie=2.0))
    assert np.abs(np.asarray(nobody) - sound).max() < 1e-6
    reference.forward(params, jnp.asarray(tokens), _model())
    assert len(asked) == 4


# ---------------------------------------------------------------------------
# the recurrence: its three forms, padding, idle lanes, the convolution
# ---------------------------------------------------------------------------

def _rule_inputs(seed, b, t, h=4, d=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    log_alpha = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    state = jax.random.normal(ks[5], (b, h, d, d))
    return state, q, k, v, log_alpha, beta


@pytest.mark.parametrize("t,block", [(64, 64), (192, 64), (48, 16), (20, 64)])
def test_the_chunkwise_form_equals_the_recurrence(t, block):
    state, *xs = _rule_inputs(t, 2, t)
    s1, o1 = delta_rule.scan(state, *xs)
    s2, o2 = delta_rule.chunked(state, *xs, block=block)
    assert np.abs(np.asarray(o1 - o2)).max() < 1e-4
    assert np.abs(np.asarray(s1 - s2)).max() < 1e-4


def test_the_strongest_decay_does_not_overflow_the_chunkwise_form():
    state, q, k, v, _, beta = _rule_inputs(3, 1, 128)
    log_alpha = jnp.full(q.shape, -5.0)         # e^{-320} inside a block
    s1, o1 = delta_rule.scan(state, q, k, v, log_alpha, beta)
    s2, o2 = delta_rule.chunked(state, q, k, v, log_alpha, beta)
    assert np.isfinite(np.asarray(o2)).all()
    assert np.abs(np.asarray(o1 - o2)).max() < 1e-4
    assert np.abs(np.asarray(s1 - s2)).max() < 1e-4


@pytest.mark.parametrize("form", ["scan", "chunked"])
def test_a_padded_tail_does_not_advance_the_state(form):
    """Lane 0 has 37 real tokens of 64, lane 1 none: the state after the
    call is the state after the real tokens alone, and an untouched lane's
    comes back bit for bit."""
    state, *xs = _rule_inputs(9, 2, 64)
    valid = jnp.arange(64)[None, :] < jnp.asarray([37, 0])[:, None]
    got, out = getattr(delta_rule, form)(state, *xs, valid)
    want, want_out = delta_rule.scan(state[:1], *(x[:1, :37] for x in xs))
    assert np.abs(np.asarray(got[0] - want[0])).max() < 1e-4
    assert np.abs(np.asarray(out[0, :37] - want_out[0])).max() < 1e-4
    assert (np.asarray(got[1]) == np.asarray(state[1])).all()


def test_a_decode_step_is_one_step_of_the_recurrence_and_skips_idle_lanes():
    state, *xs = _rule_inputs(11, 3, 1)
    live = jnp.asarray([True, False, True])
    got, out = delta_rule.step(state, *(x[:, 0] for x in xs), live=live)
    want, want_out = delta_rule.scan(state, *xs)
    for lane in (0, 2):
        assert np.abs(np.asarray(got[lane] - want[lane])).max() < 1e-5
        assert np.abs(np.asarray(out[lane] - want_out[lane, 0])).max() < 1e-5
    assert (np.asarray(got[1]) == np.asarray(state[1])).all()


def test_the_step_kernel_equals_the_step_in_place():
    """The Pallas step (interpreted) at whole (8, 128) tiles: plane 1 of two
    advanced, plane 0 untouched, the idle lane's state bit for bit."""
    h, d = 8, 128
    state, *xs = _rule_inputs(13, 3, 1, h, d)
    planes = jnp.stack([state + 1.0, state])
    live = jnp.asarray([True, False, True])
    step = [x[:, 0] for x in xs]
    want, want_out = delta_rule.step(state, *step, live=live)
    got, out = delta_rule.step_pallas(planes, 1, *step, live=live,
                                      interpret=True)
    assert np.abs(np.asarray(got[1] - want)).max() < 1e-4
    assert np.abs(np.asarray(out - want_out))[[0, 2]].max() < 1e-4
    assert (np.asarray(got[0]) == np.asarray(planes[0])).all()
    assert (np.asarray(got[1, 1]) == np.asarray(state[1])).all()
    # off the TPU, and at heads that are no whole tiles, the XLA step runs
    assert delta_rule.step_kernel_declined(32, 128) == "no TPU backend"


def test_the_convolution_carries_its_tail():
    """A sequence in pieces equals the sequence whole; the tail after a
    padded piece is the last real inputs; none real: the tail as it was."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 12))
    taps = jax.random.normal(jax.random.PRNGKey(3), (4, 12))
    zero = jnp.zeros((1, 3, 12))
    whole, tail = delta_rule.causal_conv(x, taps, zero, jnp.asarray([40]))
    y1, t1 = delta_rule.causal_conv(x[:, :16], taps, zero, jnp.asarray([16]))
    padded = jnp.concatenate([x[:, 16:40], jnp.ones((1, 8, 12))], 1)
    y2, t2 = delta_rule.causal_conv(padded, taps, t1, jnp.asarray([24]))
    assert np.abs(np.asarray(whole[:, :16] - y1)).max() < 1e-6
    assert np.abs(np.asarray(whole[:, 16:] - y2[:, :24])).max() < 1e-6
    assert (np.asarray(t2) == np.asarray(x[:, 37:40])).all()
    assert (np.asarray(tail) == np.asarray(t2)).all()
    _, t3 = delta_rule.causal_conv(padded, taps, t2, jnp.asarray([0]))
    assert (np.asarray(t3) == np.asarray(t2)).all()
    # a piece shorter than the tail keeps the older inputs in front
    _, t4 = delta_rule.causal_conv(x[:, :1], taps, t2, jnp.asarray([1]))
    assert (np.asarray(t4[:, :2]) == np.asarray(t2[:, 1:])).all()
    assert (np.asarray(t4[:, 2]) == np.asarray(x[:, 0])).all()


# ---------------------------------------------------------------------------
# latent attention: absorbed over the pages equals expanded
# ---------------------------------------------------------------------------

def test_absorbed_attention_over_paged_latents_equals_expanded():
    """Two lanes of 37 and 5 rows and an idle one, their pages scattered in
    the pool: ``W_uk`` folded into the query and ``W_uv`` applied after
    equals attention over keys and values expanded from every row."""
    h, dn, dr, dv, dc, bs = 4, 32, 16, 32, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    lengths = jnp.asarray([37, 5, 0])
    table = jnp.asarray([[3, 5, 1], [2, 0, 0], [0, 0, 0]], jnp.int32)
    c_pool = jax.random.normal(ks[0], (2, 6, bs, 1, dc))
    rotated = jax.random.normal(ks[1], (2, 6, bs, 1, dr))
    r_pool = pack_rotated(rotated, 2)           # as the pool holds them
    assert r_pool.shape == (2, 6, bs // 2, 1, 2 * dr)
    w_ukv = jax.random.normal(ks[2], (dc, h, dn + dv)) * dc ** -0.5
    q_nope = jax.random.normal(ks[3], (3, h, dn))
    q_rope = jax.random.normal(ks[4], (3, h, dr))
    scale = (dn + dr) ** -0.5
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_ukv[..., :dn])
    o_lat = paged_latent_attention(q_lat, q_rope, c_pool, r_pool, table,
                                   lengths, 1, scale)
    got = jnp.einsum("bhc,chd->bhd", o_lat, w_ukv[..., dn:])
    for lane, n in ((0, 37), (1, 5)):
        rows = c_pool[1, table[lane]].reshape(-1, dc)[:n]
        rot = rotated[1, table[lane]].reshape(-1, dr)[:n]
        kv = jnp.einsum("sc,chd->shd", rows, w_ukv)
        want = expanded_attention(
            q_nope[lane][None], q_rope[lane][None], kv[..., :dn], rot,
            kv[..., dn:], jnp.asarray([n - 1]), scale)[0]
        assert np.abs(np.asarray(got[lane] - want)).max() < 1e-4
    assert (np.asarray(got[2]) == 0).all()          # nothing to attend


def test_a_pools_rotated_keys_lie_two_tokens_a_row():
    """Token ``j`` of a page in the first lanes of row ``j``, token ``j +
    BS / 2`` in the rest; unpacking gives the rows back in token order."""
    bs, dr = 8, 4
    rows = jnp.arange(3 * bs * dr, dtype=jnp.float32).reshape(3, bs, 1, dr)
    packed = np.asarray(pack_rotated(rows, 1))
    assert packed.shape == (3, bs // 2, 1, 2 * dr)
    for j in range(bs):
        half = j // (bs // 2)
        assert (packed[:, j % (bs // 2), 0, half * dr:(half + 1) * dr]
                == np.asarray(rows[:, j, 0])).all()
    assert (np.asarray(unpack_rotated(packed, 1)) == np.asarray(rows)).all()


BS_K, WAVE_K = 16, WAVE_PAGES   # of the kernel's cases below
# name -> (heads, rotary width, table columns, the lanes' lengths)
KERNEL_CASES = {
    # 0, 1, a page, a page and a row, whole waves and the table's width
    "lengths": (8, 64, 9, [37, 1, 0, BS_K * 9, BS_K, BS_K + 1, 4 * BS_K,
                           8 * BS_K]),
    # a page's edge and a wave's, from both sides
    "edges": (8, 64, WAVE_K + 1, [BS_K - 1, BS_K, BS_K + 1,
                                  WAVE_K * BS_K - 1, WAVE_K * BS_K,
                                  WAVE_K * BS_K + 1]),
    # an idle lane between two live ones, first and last, and two in a row
    "idle-lanes": (8, 64, 5, [0, 70, 0, 0, 33, 0]),
    # every lane fills its table; the last wave holds one page
    "full-tables": (8, 64, WAVE_K + 1, [(WAVE_K + 1) * BS_K] * 3),
    # Kimi's 64 heads, its 449 columns cut to what the CPU holds (57: the
    # last wave one page), a rotary width that is not the latent's quarter
    "64-heads": (64, 32, 57, [57 * BS_K, 0, 449, 29 * BS_K + 3]),
    # a table narrower than a wave
    "narrow-table": (8, 64, 3, [3 * BS_K, 20, 0]),
}


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_the_latent_kernel_equals_the_xla_form(name):
    """The Pallas walk (interpreted) over a lane's own pages against the
    XLA form over the whole table. Every block no lane holds a row in — the
    trash block among them — holds NaN in BOTH planes for the kernel, and
    so does the tail of each lane's last page: a row past a lane's length
    never reaches the sums (the XLA form would carry it into every lane)."""
    from tpu9.ops.latent_attention import (paged_latent_attention_kernel,
                                           paged_latent_attention_xla)
    h, dr, mb, lengths = KERNEL_CASES[name]
    dc, bs, lanes = 128, BS_K, len(lengths)
    blocks = lanes * mb + 1
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    rng = np.random.default_rng(0)
    table = rng.permutation(blocks - 1).reshape(lanes, mb) + 1
    held = np.arange(mb)[None, :] < (np.asarray(lengths)[:, None] + bs - 1) \
        // bs
    table = jnp.asarray(np.where(held, table, 0), jnp.int32)
    lengths = jnp.asarray(lengths)
    c_pool = jax.random.normal(ks[0], (2, blocks, bs, 1, dc))
    rotated = jax.random.normal(ks[1], (2, blocks, bs, 1, dr))
    # what a lane has written, and NaN wherever it has not
    at = np.full((blocks, bs), False)
    for lane in range(lanes):
        for pos in range(int(lengths[lane])):
            at[int(table[lane, pos // bs]), pos % bs] = True
    written = jnp.asarray(at)[None, :, :, None, None]
    c_pool, rotated = (x.astype(jnp.bfloat16) for x in (c_pool, rotated))
    q_lat = jax.random.normal(ks[2], (lanes, h, dc)).astype(jnp.bfloat16)
    q_rope = jax.random.normal(ks[3], (lanes, h, dr)).astype(jnp.bfloat16)
    want = paged_latent_attention_xla(
        q_lat, q_rope, jnp.where(written, c_pool, 0),
        pack_rotated(jnp.where(written, rotated, 0), 2), table, lengths, 1,
        0.07)
    got = np.asarray(paged_latent_attention_kernel(
        q_lat, q_rope, jnp.where(written, c_pool, jnp.nan),
        pack_rotated(jnp.where(written, rotated, jnp.nan), 2), table,
        lengths, 1, 0.07, interpret=True))
    assert np.isfinite(got).all()
    # bfloat16 probabilities on both sides, summed in another order
    assert np.abs(got - np.asarray(want)).max() < 2e-2
    assert (got[np.asarray(lengths) == 0] == 0).all()
    assert np.abs(got[np.asarray(lengths) > 0]).max() > 0.1


def test_the_latent_kernel_declines_what_is_not_whole_tiles(monkeypatch):
    from tpu9 import utils
    from tpu9.ops.latent_attention import kernel_declined
    assert kernel_declined(32, 512, 64, 128, jnp.bfloat16) \
        == "no TPU backend"
    monkeypatch.setattr(utils, "on_tpu", lambda: True)
    assert kernel_declined(32, 512, 64, 128, jnp.bfloat16) == ""
    assert kernel_declined(64, 512, 64, 128, jnp.bfloat16) == ""
    assert "float32" in kernel_declined(32, 512, 64, 128, jnp.float32)
    # half a page of 16-row tiles; two rotated keys a 128-lane row
    assert "not whole tiles" in kernel_declined(32, 512, 64, 16,
                                                jnp.bfloat16)
    assert "not whole tiles" in kernel_declined(32, 512, 32, 128,
                                                jnp.bfloat16)


def test_expanded_attention_in_query_blocks_equals_one_block(monkeypatch):
    from tpu9.ops import latent_attention
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    t, h = 64, 2
    args = (jax.random.normal(ks[0], (t, h, 8)),
            jax.random.normal(ks[1], (t, h, 4)),
            jax.random.normal(ks[2], (t, h, 8)),
            jax.random.normal(ks[3], (t, 4)),
            jax.random.normal(ks[4], (t, h, 8)), jnp.arange(t), 0.3)
    whole = expanded_attention(*args)
    monkeypatch.setattr(latent_attention, "QUERY_BLOCK", 16)
    blocks = expanded_attention(*args)
    assert np.abs(np.asarray(whole - blocks)).max() < 1e-5


# ---------------------------------------------------------------------------
# routing: the bias changes the choice and never a gate
# ---------------------------------------------------------------------------

def test_the_bias_changes_the_choice_and_never_a_gate(params):
    moe = params["layers"][1]["moe"]
    mcfg = moe_cfg(SMALL)
    h = _normed(21, 64)
    scores, gates, chosen = _top_k_gates(moe, h, mcfg)
    pushed = dict(moe, bias=moe["bias"].at[7].add(10.0).at[2].add(-10.0))
    _, gates2, chosen2 = _top_k_gates(pushed, h, mcfg)
    chosen, chosen2 = np.asarray(chosen), np.asarray(chosen2)
    assert (chosen2 == 7).any(axis=1).all() and not (chosen2 == 2).any()
    assert (chosen != chosen2).any()
    # every gate is the chosen experts' OWN sigmoid scores, renormalised
    # over the chosen and scaled: the bias is nowhere in it
    for g, idx in ((gates, chosen), (gates2, chosen2)):
        own = np.take_along_axis(np.asarray(scores), idx, axis=1)
        want = 2.5 * own / own.sum(-1, keepdims=True)
        assert np.abs(np.asarray(g) - want).max() < 1e-6
    # group-limited: the chosen lie in at most 2 of the 4 groups of 4
    assert all(len(set((row // 4).tolist())) <= 2 for row in chosen)
    # and the reference chooses the same experts
    _, ref_chosen = reference.route(moe, h, _model())
    assert (np.sort(np.asarray(ref_chosen), 1) == np.sort(chosen, 1)).all()


def test_softmax_gates_are_as_they_were():
    """Mixtral's gate form, bit for bit: softmax over all, top-k,
    renormalised to a convex combination."""
    from tpu9.models.moe import MoeConfig, init_moe_layer
    mcfg = MoeConfig(dim=32, hidden_dim=64, n_experts=8, top_k=2,
                     dtype=jnp.float32)
    moe = init_moe_layer(jax.random.PRNGKey(0), mcfg)
    assert set(moe) == {"router", "w_gate", "w_up", "w_down"}
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 32))
    probs, gates, idx = _top_k_gates(moe, x, mcfg)
    want = jax.nn.softmax(x @ moe["router"], axis=-1)
    top, top_idx = jax.lax.top_k(want, 2)
    assert (np.asarray(probs) == np.asarray(want)).all()
    assert (np.asarray(idx) == np.asarray(top_idx)).all()
    assert (np.asarray(gates) == np.asarray(
        top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9))).all()


# ---------------------------------------------------------------------------
# a chip's share (model-configs guide, section 4)
# ---------------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """The routed parts of the four chips' shares, with the shared expert
    counted once, are the uncut reference's whole expert layer — in the
    reference and in the program alike."""
    moe = params["layers"][1]["moe"]
    h = _normed(33, 48)
    whole = reference._experts(moe, h, _model())
    shared = reference._swiglu(h, moe["shared"], _model())
    parts_ref = parts_got = 0.0
    for chip in range(4):
        cfg = replace(SMALL, n_experts=4, moe_held_first=4 * chip)
        part = dict(moe, **{n: moe[n][4 * chip:4 * chip + 4]
                            for n in ("w_gate", "w_up", "w_down")})
        parts_ref = parts_ref + reference._experts(
            part, h, _model(cfg)) - shared
        got, _ = moe_ffn_held(part, h[None], moe_cfg(cfg))
        parts_got = parts_got + got[0] - shared
    assert np.abs(np.asarray(parts_ref + shared - whole)).max() < TOL
    assert np.abs(np.asarray(parts_got + shared - whole)).max() < TOL


def test_a_slice_of_the_vocabulary_is_the_uncut_heads_rows(params):
    """Rows 64..127 of the embedding and the head: the logits over the slice
    are the uncut head's logits at those ids, for tokens drawn from it."""
    lo, hi = 64, 128
    sliced = dict(params, embed=params["embed"][lo:hi],
                  lm_head=params["lm_head"][:, lo:hi])
    cfg = replace(SMALL, vocab_size=hi - lo)
    tokens = np.random.default_rng(4).integers(lo, hi, 50)
    whole = _ref_logits(params, tokens)
    assert np.abs(_ref_logits(sliced, tokens - lo) - whole[:, lo:hi]).max() \
        < 1e-5
    got = decoder_forward(sliced, jnp.asarray(tokens - lo)[None], cfg)[0]
    assert np.abs(np.asarray(got) - whole[:, lo:hi]).max() < TOL


# ---------------------------------------------------------------------------
# what the config refuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,needle", [
    (dict(loop_steps=2), "pass loop"),
    (dict(attn_window=64, attn_chunk=4), "attn_window"),
    (dict(sandwich_norm=True), "sandwich_norm"),
    (dict(n_kv_heads=2), "n_kv_heads"),
    (dict(mla_latent=0), "latent-attention width"),
    (dict(mla_rope=15), "latent-attention width"),
    (dict(kda_conv=1), "convolution"),
    (dict(kda_gate_bound=0.5), "negative lower bound"),
    (dict(layer_group=1), "no KDA layer to read them"),
    (dict(tie_embeddings=True), "another family"),
    (dict(moe_routed=0), "told which"),
    (dict(moe_held_first=8, n_experts=16), "inside the routed"),
    (dict(moe_score="tanh"), "softmax or sigmoid"),
    (dict(moe_groups=3), "groups divide"),
    (dict(moe_top_groups=5), "groups divide"),
    (dict(moe_score="softmax"), "sigmoid scores only"),
])
def test_the_config_refuses_what_is_not_built(kw, needle):
    with pytest.raises(ValueError, match=needle):
        replace(SMALL, **kw)


@pytest.mark.parametrize("kw", [
    dict(mla_latent=64), dict(kda_conv=4), dict(moe_dense_layers=1),
    dict(moe_routed=16, n_experts=8), dict(moe_shared_dim=64, n_experts=8),
    dict(moe_score="sigmoid", n_experts=8)])
def test_the_descriptors_build_for_a_pattern_only(kw):
    with pytest.raises(ValueError, match="layer pattern only"):
        replace(PLAIN, **kw)


# ---------------------------------------------------------------------------
# the configurations the benchmark had build the model config they built
# ---------------------------------------------------------------------------

with open(os.path.join(os.path.dirname(__file__), "data",
                       "decoder_configs_at_257b200.json")) as _f:
    BEFORE = json.load(_f)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_an_accepted_configuration_builds_the_model_config_it_built(name):
    """Every configuration the benchmark had before PR 48: its family
    builds the same ``DecoderConfig`` — every field the parent commit had,
    equal; every field added since, at its default: off. (That their
    programs also LOWER as on the parent is a builder's step,
    ``benchmark/tools/lowered_configs.py`` against a parent checkout: a
    test that pinned those digests would refuse every later ``perf_opt``.)"""
    import dataclasses
    import importlib.util

    from benchmark import manifest
    spec = importlib.util.spec_from_file_location(
        "lowered_configs", os.path.join(manifest.HERE, "tools",
                                        "lowered_configs.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    got, want = tool.decoder_fields(name), BEFORE[name]
    for field, value in want.items():
        assert got[field] == value, field
    defaults = {f.name: repr(f.default)
                for f in dataclasses.fields(DecoderConfig)}
    for field in set(got) - set(want):
        assert got[field] == defaults[field], field
