"""The Mamba-2 recurrence (``tpu9/ops/ssd.py``, ISSUE 55) in its four forms
— ``step``, ``scan``, ``chunked`` and the Pallas step, interpreted — against
each other and against the plain reference's recurrence
(``benchmark/reference/granitehybrid.py``), in float32: across block
boundaries, with padded tails, with idle lanes untouched, in place at a
plane, live lanes only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from tpu9.ops import ssd
from tpu9.ops.delta_rule import causal_conv

H, P, N = 4, 8, 128


def _inputs(seed, b, t, h=H, p=P, n=N, g=1):
    """``(state, x, dt, a_head, bm, cm)``: a decay between 0.2 and 0.999 a
    token, as Mamba-2's initialisation gives."""
    r = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.exp(jax.random.uniform(r[1], (b, t, h), jnp.float32,
                                    np.log(0.001), np.log(0.1)))
    a_head = -jax.random.uniform(r[2], (h,), jnp.float32, 1.0, 16.0)
    return (jax.random.normal(r[5], (b, h, p, n)),
            jax.random.normal(r[0], (b, t, h, p)), dt, a_head,
            jax.random.normal(r[3], (b, t, g, n)),
            jax.random.normal(r[4], (b, t, g, n)))


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


def test_the_scan_is_the_references_recurrence():
    """From zero state, one lane: the oracle computes what the plain
    reference's token-at-a-time recurrence does."""
    ref = correctness.load_reference("granitehybrid")
    _, x, dt, a_head, bm, cm = _inputs(1, 1, 50)
    _, got = ssd.scan(jnp.zeros((1, H, P, N)), x, dt, a_head, bm, cm)
    _close(got[0], ref.recurrence(x[0], dt[0], a_head, bm[0], cm[0]))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("t,block", [(64, 16), (48, 48), (16, 64)])
def test_chunked_is_the_scan_across_block_boundaries(t, block, groups):
    state, *xs = _inputs(2, 2, t, g=groups)
    want_state, want = ssd.scan(state, *xs)
    got_state, got = ssd.chunked(state, *xs, block=block)
    _close(got, want)
    _close(got_state, want_state)


@pytest.mark.parametrize("form", ["scan", "chunked"])
def test_a_padded_tail_and_an_idle_lane_leave_the_state(form):
    """Lane 0 all real, lane 1 real to 21 of 32 (its state is the state
    after 21 tokens), lane 2 none real: its state bit for bit."""
    state, *xs = _inputs(3, 3, 32)
    n_valid = jnp.asarray([32, 21, 0])
    valid = jnp.arange(32)[None, :] < n_valid[:, None]
    fn = ssd.scan if form == "scan" else \
        (lambda *a: ssd.chunked(*a, block=8))
    got_state, got = fn(state, *xs, valid)
    whole, want = ssd.scan(state, *xs)
    short, _ = ssd.scan(state[1:2], *(a[1:2, :21] if a.ndim > 1 else a
                                      for a in xs))
    _close(got_state[0], whole[0])
    _close(got_state[1], short[0])
    _close(got[1, :21], want[1, :21])
    assert (np.asarray(got_state[2]) == np.asarray(state[2])).all()


def test_chunked_refuses_a_ragged_last_block():
    state, *xs = _inputs(4, 1, 40)
    with pytest.raises(ValueError, match="whole blocks"):
        ssd.chunked(state, *xs, block=16)


def test_a_decode_step_is_one_step_of_the_recurrence_and_skips_idle_lanes():
    state, *xs = _inputs(5, 3, 1)
    x, dt, a_head, bm, cm = xs
    live = jnp.asarray([True, False, True])
    got, out = ssd.step(state, x[:, 0], dt[:, 0], a_head, bm[:, 0], cm[:, 0],
                        live=live)
    want, want_out = ssd.scan(state, *xs)
    for lane in (0, 2):
        _close(got[lane], want[lane])
        _close(out[lane], want_out[lane, 0])
    assert (np.asarray(got[1]) == np.asarray(state[1])).all()


def test_the_stored_state_packs_heads_to_whole_rows():
    """``[B, H, P, N]`` is stored ``[B, H / pack, N, pack P]``: two heads of
    64 side by side along 128 lanes, where they divide a group's heads."""
    assert ssd.head_pack(64, 64) == 2 and ssd.head_pack(64, 64, 64) == 1
    assert ssd.head_pack(8, 128) == 1 and ssd.head_pack(4, 8) == 1
    assert ssd.state_shape(64, 64, 128) == (32, 128, 128)
    assert ssd.state_shape(4, 8, 128) == (4, 128, 8)
    state = _inputs(9, 2, 1, h=4, p=64)[0]
    stored = ssd.pack_state(state, 2)
    assert stored.shape == (2, 2, 128, 128)
    # head 1's number (p, n) lies at row n, lane 64 + p of the first pair
    assert float(stored[1, 0, 5, 64 + 3]) == float(state[1, 1, 3, 5])
    assert (np.asarray(ssd.unpack_state(stored, 64))
            == np.asarray(state)).all()


def _live_case(case):
    """A case of the step kernel's test as a tuple of bools: itself, or by
    name one at the edges of the kernel's groups — ``ssd.GROUP_LANES`` slots,
    three lanes more than that, the live ones scattered from lane 1 to the
    array's last (never a prefix)."""
    if not isinstance(case, str):
        return case
    group = ssd.GROUP_LANES
    b = group + 3
    n = {"last-only": 1, "group-1": group - 1, "group": group,
         "group+1": group + 1, "all": b}[case]
    if n == b:
        return (True,) * b
    lanes = {int(round(float(x))) for x in np.linspace(b - 1, 1, n)}
    assert len(lanes) == n and b - 1 in lanes and 0 not in lanes
    return tuple(i in lanes for i in range(b))


@pytest.mark.parametrize("live", [(True, False, True, True),
                                  (False, False, True, False),
                                  (False, False, False, False),
                                  "last-only", "group-1", "group", "group+1",
                                  "all"],
                         ids=["three", "one", "none", "last-only", "group-1",
                              "group", "group+1", "all"])
@pytest.mark.parametrize("groups,p", [(1, 8), (2, 8), (1, 64), (2, 64)])
def test_the_step_kernel_equals_the_step_in_place_on_live_lanes(live, groups,
                                                                p):
    """The Pallas step (interpreted) over the STORED state (a head a row at
    P = 8, two heads a row at P = 64): plane 1 of three advanced for the
    live lanes, every idle lane's state and both other planes bit for bit,
    an idle lane's output zero — with no lane live (nothing is copied), one,
    and as many as a group of the kernel has slots, one fewer, one more (a
    second group of one) and every lane (:func:`_live_case`), the live list
    scattered."""
    live = jnp.asarray(_live_case(live))
    state, x, dt, a_head, bm, cm = _inputs(6, live.shape[0], 1, p=p,
                                           g=groups)
    pack = ssd.head_pack(H, p, groups)
    assert pack == (2 if p == 64 else 1)
    planes = jnp.stack([ssd.pack_state(s, pack)
                        for s in (state + 1.0, state, state - 1.0)])
    args = (x[:, 0], dt[:, 0], a_head, bm[:, 0], cm[:, 0])
    want, want_out = ssd.step(state, *args, live=live)
    step = jax.jit(lambda planes, *a: ssd.step_pallas(planes, 1, *a,
                                                      interpret=True))
    got, out = jax.block_until_ready(step(planes, *args, live))
    got, out = np.asarray(got), np.asarray(out)
    idle = ~np.asarray(live)
    state = ssd.pack_state(state, pack)
    _close(got[1], ssd.pack_state(want, pack))
    if (~idle).any():
        _close(out[~idle], np.asarray(want_out)[~idle])
    assert (got[1][idle] == np.asarray(state)[idle]).all()
    assert not out[idle].any()
    assert (got[0] == np.asarray(planes[0])).all()
    assert (got[2] == np.asarray(planes[2])).all()


def test_the_live_lanes_come_first_in_order():
    lanes, n = ssd.live_lanes(jnp.asarray([False, True, False, True, False]))
    assert np.asarray(lanes).tolist() == [1, 3, 0, 2, 4] and int(n[0]) == 2
    lanes, n = ssd.live_lanes(jnp.zeros((3,), bool))
    assert np.asarray(lanes).tolist() == [0, 1, 2] and int(n[0]) == 0


def test_the_kernel_declines_off_the_chip_and_at_ragged_tiles(monkeypatch):
    assert ssd.step_kernel_declined(64, 64, 128) == "no TPU backend"
    import tpu9.utils
    monkeypatch.setattr(tpu9.utils, "on_tpu", lambda: True)
    assert ssd.step_kernel_declined(64, 64, 128) == ""
    assert ssd.step_kernel_declined(8, 128, 64) == ""
    assert "tiles" in ssd.step_kernel_declined(4, 16, 32)
    assert "tiles" in ssd.step_kernel_declined(64, 64, 128, groups=64)


def test_the_convolution_adds_its_bias_and_carries_its_tail():
    """The short convolution shared with the delta rule, with the
    state-space mixer's bias: a sequence in pieces equals the sequence
    whole, and without a bias it is what it was."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 12))
    taps = jax.random.normal(jax.random.PRNGKey(3), (4, 12))
    bias = jax.random.normal(jax.random.PRNGKey(4), (12,))
    zero = jnp.zeros((1, 3, 12))
    whole, _ = causal_conv(x, taps, zero, jnp.asarray([40]), bias)
    plain, _ = causal_conv(x, taps, zero, jnp.asarray([40]))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(plain + bias),
                               atol=1e-6)
    y1, t1 = causal_conv(x[:, :16], taps, zero, jnp.asarray([16]), bias)
    y2, _ = causal_conv(x[:, 16:], taps, t1, jnp.asarray([24]), bias)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(whole),
        atol=1e-6)
