"""A decode step's expert layer reads the held experts its LIVE rows picked
(ISSUE 49): the ``held_ffn`` kernel, interpreted, against the einsums over
every held expert (``held_ffn_xla``) and against the plain reference's
experts (``benchmark/reference/ling.py``, read only); the touched list the
kernel's index maps read; and the rows that are not live. What the TPU's
compiler says of the kernel at the published widths:
``test_chip_compile.py``."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import ling as reference
from tests.test_hybrid_layers import SMALL, TOL, _model, _normed
from tpu9.models import init_decoder
from tpu9.models.moe import SORTED_MIN_TOKENS, moe_ffn_held
from tpu9.models.transformer import moe_cfg
from tpu9.ops import held_ffn as ops

# memory no step wrote reads NaN, as on the chip it reads whatever it held
POISONED = pltpu.InterpretParams(uninitialized_memory="nan")


def kernel(*args, **kwargs):
    """The kernel, interpreted, and DONE before it returns: its callbacks run
    jax computations of their own, and an eager one queued behind the
    running kernel (the layer's next line) would wait for it for ever."""
    return jax.block_until_ready(
        ops.held_ffn_kernel(*args, interpret=POISONED, **kwargs))


@pytest.fixture(scope="module")
def moe():
    return init_decoder(jax.random.PRNGKey(48), SMALL)["layers"][1]["moe"]


def _share(moe, first, count):
    return dict(moe, **{name: moe[name][first:first + count]
                        for name in ("w_gate", "w_up", "w_down")})


def _call(seed, n, e, touched, d=128, h=64, k=4):
    """A call whose rows pick among exactly ``touched`` of ``e`` experts:
    (x, weight, ids, count, w_gate, w_up, w_down)."""
    rng = np.random.default_rng(seed)
    pool = rng.permutation(e)[:touched]
    local = np.full((n, k), -1)
    if touched:
        local = rng.choice(pool, (n, k))
        local[:touched, 0] = pool[:n]
    weight = np.zeros((n, e), np.float32)
    for row, picks in enumerate(local):
        weight[row, picks[picks >= 0]] = rng.random()
    ids, count = ops.touched_experts(jnp.asarray(local), jnp.ones(n, bool), e)
    assert int(count[0]) == touched

    def normal(shape):
        return jnp.asarray(rng.normal(size=shape) * shape[-2] ** -0.5,
                           jnp.float32)
    return (normal((n, d)), jnp.asarray(weight), ids, count,
            normal((e, d, h)), normal((e, d, h)), normal((e, h, d)))


@pytest.mark.parametrize("n", [24, SORTED_MIN_TOKENS])
@pytest.mark.parametrize("touched", [0, 1, 7, 16])
def test_the_kernel_equals_the_einsums_over_every_expert(n, touched):
    """None touched (zeros, not the buffer's contents), one, about half, all;
    a row count that is no multiple of the sublane tile and the widest call
    the held form takes."""
    args = _call(touched, n, 16, touched)
    got = np.asarray(kernel(*args))
    want = np.asarray(ops.held_ffn_xla(*args))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOL
    assert (np.abs(want).max() > 1e-3) == (touched > 0)
    if not touched:
        assert not got.any()


@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_a_tiled_hidden_width_sums_to_the_whole(monkeypatch, act):
    """Where an expert's three matrices are too large for a step, ``h`` is
    cut: the same sum over (expert, tile) steps."""
    args = _call(3, 40, 8, 5, h=768)
    monkeypatch.setattr(ops, "STEP_BYTES", 2 * 3 * 128 * 256 * 4)
    assert ops._step_tile(128, 768, 4) == 256
    # the function under the jit: traced here, under this test's limit
    got = jax.block_until_ready(ops.held_ffn_kernel.__wrapped__(
        *args, act=act, interpret=POISONED))
    want = ops.held_ffn_xla(*args, act=act)
    assert np.abs(np.asarray(got - want)).max() < TOL


@pytest.mark.parametrize("d,h,itemsize,tile", [
    (2560, 768, 2, 768),        # Ling: 23.6 MB twice over, whole
    (2560, 768, 4, 768),
    (4096, 14336, 2, 512),      # Mixtral's widths: grouped_ffn's tile
    (128, 64, 4, 64)])
def test_a_step_holds_an_experts_matrices_whole_where_they_fit(d, h,
                                                               itemsize,
                                                               tile):
    assert ops._step_tile(d, h, itemsize) == tile
    assert 2 * 3 * d * tile * itemsize <= ops.STEP_BYTES


@pytest.mark.parametrize("t", [24, SORTED_MIN_TOKENS])
@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)])
def test_live_rows_through_the_kernel_equal_the_reference(monkeypatch, moe,
                                                          t, held):
    """The uncut layer and two chips' shares of it with a third of the rows
    idle, the kernel interpreted: a live row gets the held experts' terms
    with gates normalised over all chosen, and every row, idle or live,
    says which experts it chose."""
    first, count = held
    cfg = replace(SMALL, n_experts=count, moe_held_first=first)
    share = _share(moe, first, count)
    h = _normed(t + first, t)
    live = np.arange(t) % 3 > 0
    monkeypatch.setattr(ops, "held_ffn", kernel)
    got, picks = moe_ffn_held(share, h[None], moe_cfg(cfg),
                              jnp.asarray(live)[None])
    want = reference._experts(share, h, _model(cfg))
    assert np.abs(np.asarray(got[0] - want))[live].max() < TOL
    _, chosen = reference.route(share, h, _model(cfg))
    assert (np.sort(np.asarray(picks[0]), -1)
            == np.sort(np.asarray(chosen), -1)).all()


def _note_routed_count(picks, mask, first, e):
    """``engine._note_routed``'s count of one (step, layer): the held
    experts at least one live lane's pick reached."""
    local = np.asarray(picks)[np.asarray(mask)] - first
    held = (local >= 0) & (local < e)
    return int((np.bincount(local[held], minlength=e) > 0).sum())


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)])
@pytest.mark.parametrize("live_share", [0.0, 0.2, 0.7, 1.0])
def test_the_touched_list(moe, held, live_share):
    """Ascending, padded with its last id, as long as the held experts; its
    count is the engine's ``moe_held_touched`` of the same picks and mask."""
    first, e = held
    cfg = replace(SMALL, n_experts=e, moe_held_first=first)
    _, picks = moe_ffn_held(_share(moe, first, e), _normed(9, 32)[:, None],
                            moe_cfg(cfg))
    picks = np.asarray(picks[:, 0])
    mask = np.random.default_rng(first).random(32) < live_share
    ids, count = ops.touched_experts(jnp.asarray(picks - first),
                                     jnp.asarray(mask), e)
    ids, n = np.asarray(ids), int(count[0])
    assert ids.shape == (e,) and ids.dtype == np.int32 and count.shape == (1,)
    assert n == _note_routed_count(picks, mask, first, e)
    want = np.unique([p for p in (picks[mask] - first).ravel()
                      if 0 <= p < e])
    assert list(ids[:n]) == list(want)
    assert (ids[n:] == (ids[n - 1] if n else 0)).all()


def test_idle_rows_put_no_expert_on_the_list(monkeypatch, moe):
    """A decode step's idle lanes: whatever their padding picks, the list is
    the live rows' alone, the live rows' result is what it was, an idle
    row's experts add nothing, and every row still says what it chose."""
    cfg = moe_cfg(SMALL)
    h = _normed(5, 24)[:, None]                     # [B, 1, dim]: a step
    live = jnp.arange(24) < 6
    seen = {}

    def spy(x, weight, ids, count, *stacks, act):
        seen["ids"], seen["count"] = np.asarray(ids), int(count[0])
        seen["weight"] = np.asarray(weight)
        return kernel(x, weight, ids, count, *stacks, act=act)
    monkeypatch.setattr(ops, "held_ffn", kernel)
    everyone, picks_all = moe_ffn_held(moe, h, cfg)
    monkeypatch.setattr(ops, "held_ffn", spy)
    got, picks = moe_ffn_held(moe, h, cfg, live[:, None])
    by_live = set(np.asarray(picks)[:6].ravel())
    by_idle = set(np.asarray(picks)[6:].ravel()) - by_live
    assert by_idle, "the idle rows must reach experts no live row picked"
    assert set(seen["ids"]) == by_live and seen["count"] == len(by_live)
    assert not seen["weight"][6:].any()
    assert (np.asarray(picks) == np.asarray(picks_all)).all()
    assert np.abs(np.asarray(got[:6] - everyone[:6])).max() < TOL
    # an idle row keeps the shared expert's term and loses the routed ones
    from tpu9.models.moe import shared_ffn
    shared = shared_ffn(moe["shared"], h[:, 0], cfg)
    assert np.abs(np.asarray(got[6:, 0] - shared[6:])).max() < TOL
    assert np.abs(np.asarray(everyone[6:, 0] - shared[6:])).max() > 0.01


def test_the_dispatcher_takes_the_kernel_on_a_tpu_alone(monkeypatch):
    args = _call(1, 8, 4, 2)
    taken = []
    monkeypatch.setattr(ops, "held_ffn_kernel",
                        lambda *a, **kw: taken.append("kernel"))
    monkeypatch.setattr(ops, "held_ffn_xla",
                        lambda *a, **kw: taken.append("xla"))
    ops.held_ffn(*args)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    ops.held_ffn(*args)
    assert taken == ["xla", "kernel"]


# -- a plain expert decoder: every expert held (ISSUE 50) ------------------------

@pytest.fixture(scope="module")
def plain():
    """mixtral-tiny in bf16 with experts of three 128-column tiles, dropless
    as the benchmark's Mixtral states it (capacity factor = E / k), and nine
    tokens a lane for ten lanes."""
    from tpu9.models.mixtral import MIXTRAL_PRESETS
    tiny = MIXTRAL_PRESETS["mixtral-tiny"]
    cfg = replace(tiny, dtype=jnp.bfloat16, hidden_dim=384,
                  moe_capacity_factor=tiny.n_experts / tiny.moe_top_k)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (10, 9), 0,
                                cfg.vocab_size)
    return cfg, init_decoder(jax.random.PRNGKey(50), cfg), tokens


def _tiled_kernel(seen):
    """``held_ffn`` as the chip runs it at Mixtral's widths — ``hidden`` cut
    into tiles — interpreted; ``seen`` gets every call's list."""
    def call(x, weight, ids, count, *stacks, act):
        seen.append((np.asarray(ids), int(count[0])))
        return jax.block_until_ready(ops.held_ffn_kernel.__wrapped__(
            x, weight, ids, count, *stacks, act=act, interpret=POISONED))
    return call


def _step(monkeypatch, plain, live, tokens=None):
    """A decode step of the plain decoder over a dense cache that holds
    eight tokens a lane: (logits [B, V], picks, the kernel's lists)."""
    from tpu9.models import moe
    from tpu9.models import decoder_forward, init_kv_cache
    cfg, params, given = plain
    tokens = given if tokens is None else tokens
    b = tokens.shape[0]
    seen = []
    # the form of a served expert layer, at a test's widths
    monkeypatch.setattr(moe, "HELD_MIN_STACK_BYTES", 0)
    # three hidden tiles of 128 an expert, as a wide expert is cut
    monkeypatch.setattr(ops, "STEP_BYTES", 2 * 3 * cfg.dim * 128 * 2)
    assert ops._step_tile(cfg.dim, cfg.hidden_dim, 2) == 128 < cfg.hidden_dim
    monkeypatch.setattr(ops, "held_ffn", _tiled_kernel(seen))
    _, cache = decoder_forward(params, tokens[:, :8], cfg,
                               kv_cache=init_kv_cache(cfg, b, 16))
    assert not seen                     # a call with no mask: the one-hot form
    out = decoder_forward(
        params, tokens[:, 8:9], cfg, positions=jnp.full((b, 1), 8),
        kv_cache=cache, cache_len=jnp.full((b,), 9), decode=True,
        n_valid=None if live is None else jnp.asarray(live, jnp.int32),
        return_moe_picks=True)
    logits = np.asarray(out[0][:, 0])
    return (logits, np.asarray(out[2]) if len(out) > 2 else None, seen)


@pytest.mark.parametrize("live_share", [0.0, 0.2, 1.0])
def test_a_plain_decoders_step_reads_by_the_list(monkeypatch, plain,
                                                 live_share):
    """The step of a decoder that holds every expert, through the kernel
    with ``hidden`` tiled: a live lane's logits are the one-hot form's
    (``moe_ffn``, dropless) and the cache-less forward's; each layer's list
    is the experts the live lanes picked, as the engine counts them; every
    lane, idle or live, says what it chose."""
    from tpu9.models.transformer import decoder_forward
    cfg, params, tokens = plain
    live = np.arange(10) < round(live_share * 10)
    got, picks, seen = _step(monkeypatch, plain, live)
    assert picks.shape == (10, 1, cfg.n_layers, cfg.moe_top_k)
    assert len(seen) == cfg.n_layers
    for layer, (ids, count) in enumerate(seen):
        assert count == _note_routed_count(picks[:, 0, layer], live, 0,
                                           cfg.n_experts)
        assert set(ids[:count]) == set(picks[live, 0, layer].ravel())
    one_hot, none, unseen = _step(monkeypatch, plain, None)
    assert none is None and not unseen
    whole = np.asarray(decoder_forward(params, tokens, cfg)[:, -1])
    if live.any():
        # bf16: the held form rounds the experts' hidden rows once, the
        # one-hot form each expert's output and the gates too
        spread = whole.std()
        assert np.abs(got - one_hot)[live].max() < 0.05 * spread
        assert np.abs(got - whole)[live].max() < 0.05 * spread
        assert np.abs(one_hot - whole)[live].max() < 0.05 * spread


def test_idle_lanes_of_a_plain_decoder_leave_the_live_lanes_alone(
        monkeypatch, plain):
    """Whatever token an idle lane is parked on, the lists hold the live
    lanes' picks alone and the live lanes' logits do not move by a bit."""
    cfg, _, tokens = plain
    live = np.arange(10) < 3
    parked = tokens.at[3:, 8].set((tokens[3:, 8] + 101) % cfg.vocab_size)
    first, picks, seen = _step(monkeypatch, plain, live)
    second, other, again = _step(monkeypatch, plain, live, parked)
    assert (picks[3:] != other[3:]).any(), "the idle lanes must pick anew"
    assert (picks[:3] == other[:3]).all()
    for (ids, count), (ids2, count2) in zip(seen, again):
        assert count == count2 and (ids == ids2).all()
    assert (first[:3] == second[:3]).all()
    # with every lane live the idle lanes' picks are on the list
    _, _, everyone = _step(monkeypatch, plain, np.ones(10, bool))
    assert sum(c for _, c in everyone) > sum(c for _, c in seen)


@pytest.mark.parametrize("itemsize", [2, 4])
def test_a_wide_experts_tile_fits_the_kernels_vmem_twice(itemsize):
    """Mixtral's 4096 x 14336 (352 MB an expert in bf16): ``hidden`` is cut
    into whole 128-lane registers that divide it, three blocks of a step
    double-buffered well inside the kernel's VMEM limit — on the chip the
    512-column tile reads one call alone at the einsums' speed (PERF.md §6,
    PR 50), so a wide expert keeps ``grouped_ffn``'s tile; Ling's expert
    stays whole."""
    from tpu9.ops.grouped_ffn import _VMEM_LIMIT
    tile = ops._step_tile(4096, 14336, itemsize)
    assert tile < 14336 and 14336 % tile == 0 and tile % 128 == 0
    assert 2 * 2 * 3 * 4096 * tile * itemsize <= _VMEM_LIMIT
    assert ops._step_tile(2560, 768, itemsize) == 768
