"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060): a
selective state-space layer whose decay is ONE scalar a head and token, so
that a head's state is a ``[P, N]`` matrix (``P`` the head's width, ``N`` the
state's), the same size at any sequence length.

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t

``x_t`` ``[H, P]``, ``dt_t`` ``[H]`` (after its softplus), ``A_h < 0`` a head,
``B_t`` and ``C_t`` ``[G, N]`` shared by the ``H / G`` heads of a group. The
skip ``D_h x_t`` is the caller's: it touches no state.

Four forms of the one recurrence, float32 throughout (the state is carried
for thousands of tokens and the decay ``exp(dt A)`` lies just under 1: a
rounding of the state at every token compounds over everything it
remembers):

- :func:`step`: one token a lane — a decode step, ``jax.numpy``.
- :func:`step_pallas`: the same as a kernel that updates the lanes' states
  IN PLACE at one plane of the whole array (aliased to its output: a copy of
  the array does not fit beside it), which is kept in the STORED form
  ``[planes, lanes, H / pack, N, pack P]`` (:func:`state_shape`,
  :func:`pack_state`: the ``jax.numpy`` forms take ``[B, H, P, N]``), and
  moves the LIVE lanes only: the array stays in HBM and ONE invocation walks
  a prefetched list of the live lanes, a group at a time — the group's
  blocks copied in, each stepped as it lands, all copied back
  (:func:`_step_kernel`). An idle lane costs nothing, no live lane nothing
  at all.
- :func:`scan`: a token at a time under ``lax.scan`` — the oracle.
- :func:`chunked`: the chunkwise-parallel (SSD) form — a prefill. Inside a
  block of ``BLOCK`` tokens, with ``a_t = dt_t A_h``, ``g_t = sum_{i<=t} a_i``
  and ``Z`` the state entering the block,

      Y = (C B^T o L) (dt o X) + e^{g} o (C Z^T),   L[t, i] = e^{g_t - g_i}  (i <= t)
      S_end = e^{g_C} Z + sum_i e^{g_C - g_i} dt_i x_i (x) B_i

  The segment sums ``g_t - g_i`` are differences of ONE cumulative sum and
  are masked to ``i <= t`` BEFORE the exponential, so only non-positive
  numbers are exponentiated; the products inside a block are matrix
  products, the state goes from block to block under a ``lax.scan``.

A token that is padding (``valid`` false) has ``dt = 0``: decay 1 and no
input, so it leaves the state as it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# tokens of a block of the chunkwise form: the published ``mamba_chunk_size``
BLOCK = 256
STEP_KERNEL = "ssm_state_step"
# lanes of a vector register: the width a stored row fills
LANES = 128


def _to_heads(m, heads: int, axis: int):
    """``B`` or ``C`` with its group axis repeated to the heads'."""
    groups = m.shape[axis]
    return m if groups == heads else jnp.repeat(m, heads // groups, axis=axis)


def step(state, x, dt, a_head, bm, cm, live=None):
    """One token a lane. ``state`` [B, H, P, N]; ``x`` [B, H, P]; ``dt``
    [B, H]; ``a_head`` [H]; ``bm, cm`` [B, G, N]; ``live`` [B] bool or None.
    Returns ``(state, y [B, H, P])``; a lane that is not live keeps its
    state."""
    h = x.shape[1]
    decay = jnp.exp(dt * a_head)                               # [B, H]
    new = decay[..., None, None] * state \
        + (dt[..., None] * x)[..., None] * _to_heads(bm, h, 1)[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new, _to_heads(cm, h, 1),
                   precision=HIGHEST)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return new, y


def head_pack(heads: int, head_dim: int, groups: int = 1) -> int:
    """Heads that lie side by side in one row of the STORED state: as many
    as fill ``LANES`` lanes, where they divide a group's heads; else 1."""
    pack = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 \
        else 1
    return pack if (heads // groups) % pack == 0 else 1


def state_shape(heads: int, head_dim: int, state_dim: int,
                groups: int = 1) -> tuple:
    """``[H / pack, N, pack P]``: a lane's state as it is STORED — the
    state's width ``N`` down the rows, ``pack`` heads' ``P`` numbers side by
    side along the lanes (:func:`head_pack`). Laid so, a head's decay and its
    input are ROWS that broadcast down the sublanes and the read-out ``S C``
    sums down them: register adds. As ``[H, P, N]`` the read-out is a sum
    along the lanes of every register, and the step kernel ran at 31 % of
    the bandwidth its bytes need (PR 55's first traced run)."""
    pack = head_pack(heads, head_dim, groups)
    return heads // pack, state_dim, pack * head_dim


def pack_state(state, pack: int):
    """``[B, H, P, N]`` as it is stored, ``[B, H / pack, N, pack P]``."""
    b, h, p, n = state.shape
    return state.reshape(b, h // pack, pack, p, n).transpose(
        0, 1, 4, 2, 3).reshape(b, h // pack, n, pack * p)


def unpack_state(stored, head_dim: int):
    """The stored ``[B, H / pack, N, pack P]`` as ``[B, H, P, N]``."""
    b, hp, n, width = stored.shape
    pack = width // head_dim
    return stored.reshape(b, hp, n, pack, head_dim).transpose(
        0, 1, 3, 4, 2).reshape(b, hp * pack, head_dim, n)


def step_kernel_declined(heads: int, head_dim: int, state_dim: int,
                         groups: int = 1) -> str:
    """Why a decode step takes :func:`step` and not the Pallas kernel ('' =
    the kernel runs)."""
    from ..utils import on_tpu
    if not on_tpu():
        return "no TPU backend"
    _, rows, width = state_shape(heads, head_dim, state_dim, groups)
    if width % LANES or rows % 8:
        return (f"a stored row of {width} numbers, {rows} rows a head: not "
                "whole (8, 128) tiles")
    return ""


# lanes a group of the step kernel: it reads a group's blocks (a lane's whole
# stored state, 2 MB at the published widths), steps them and writes them
# back, ONE direction of copies in flight at a time. On a v5e the blocks of 26
# scattered lanes are read at 715 GB/s and written at 631, and an in-place
# copy that keeps reads and write-backs in flight TOGETHER — however many —
# moves 630 in all; a group read, then written, 665–670 (PR 56, the kernel
# alone: groups of 4 / 8 / 13 / 26 lanes 165.1 / 164.1 / 163.1 / 163.1 us a
# call). Eight: 16 MB of VMEM
GROUP_LANES = 8


def _column(ref, vec, n: int, width: int):
    """Vector ``vec`` of ``ref`` — ``[vectors x ceil(n / LANES), LANES]``, a
    vector's numbers in whole rows of ``LANES`` — as ``[n, width]``: its
    numbers down the sublanes, each broadcast along the lanes, a square
    tile's transpose at a time."""
    chunks = -(-n // LANES)
    tiles = [jnp.broadcast_to(ref[pl.ds(vec * chunks + k, 1), :],
                              (LANES, LANES)).T for k in range(chunks)]
    col = (tiles[0] if chunks == 1 else jnp.concatenate(tiles, 0))[:n]
    reps = -(-width // LANES)
    if reps > 1:
        col = jnp.concatenate([col] * reps, axis=1)
    return col[:, :width]


def _step_kernel(lanes_ref, n_ref, s_hbm, a_ref, x_ref, b_ref, c_ref,
                 s_out, y_ref, buf, sem_in, sem_out, *, plane: int,
                 groups: int):
    """ONE invocation steps the live lanes ``lanes_ref[:n_ref[0]]``, a group
    of ``GROUP_LANES`` at a time. ``s_hbm`` and ``s_out`` are the whole
    states array where it lies (one buffer: the output is aliased to the
    input); ``buf`` [GROUP_LANES, H / pack, N, W] the slots a lane's state
    (:func:`state_shape`) is copied into, stepped in and copied back from.
    ``a_ref`` [B, H / pack, W] the decays and ``x_ref`` the inputs ``dt x``,
    a head's along its lanes: ROWS that broadcast down the sublanes of a
    ``[N, W]`` state; ``b_ref``, ``c_ref`` a lane's and group's ``N`` numbers
    in rows that :func:`_column` lays down the sublanes; ``y_ref`` [B, H /
    pack, W], rows (the sum over ``N`` runs down the sublanes).

    A group: its reads are all started, and each lane is stepped as its read
    lands, under the reads behind it; when the last has landed the stepped
    lanes' write-backs start, under the last lane's arithmetic; the next
    group's reads start when the write-backs are done — reads and
    write-backs are never in flight together (``GROUP_LANES`` says why). No
    live lane: no group, nothing is copied, the array stays as it is."""
    n = n_ref[0]
    group, rows, n_state, width = buf.shape

    def read(j, slot):
        return pltpu.make_async_copy(s_hbm.at[plane, lanes_ref[j]],
                                     buf.at[slot], sem_in.at[slot])

    def write(j, slot):
        return pltpu.make_async_copy(buf.at[slot],
                                     s_out.at[plane, lanes_ref[j]],
                                     sem_out.at[slot])

    def step_lane(j, slot):
        lane = lanes_ref[j]
        for g in range(groups):
            vec = lane * groups + g
            bb = _column(b_ref, vec, n_state, width)
            cb = _column(c_ref, vec, n_state, width)

            def one_row(r, _):
                row = pl.ds(r, 1)
                new = buf[slot, r] * a_ref[lane, row, :] \
                    + bb * x_ref[lane, row, :]
                buf[slot, r] = new
                y_ref[lane, row, :] = jnp.sum(new * cb, axis=0,
                                              keepdims=True)

            jax.lax.fori_loop(g * rows // groups, (g + 1) * rows // groups,
                              one_row, None)

    def one_group(g, _):
        first = g * group
        count = jnp.minimum(n - first, group)

        def each(stop, act):
            jax.lax.fori_loop(0, stop, lambda k, _: act(first + k, k), None)

        each(count, lambda j, slot: read(j, slot).start())

        def one_lane(k, _):
            read(first + k, k).wait()

            @pl.when(k == count - 1)
            def _():
                each(k, lambda j, slot: write(j, slot).start())

            step_lane(first + k, k)

        jax.lax.fori_loop(0, count, one_lane, None)
        write(first + count - 1, count - 1).start()
        each(count, lambda j, slot: write(j, slot).wait())

    jax.lax.fori_loop(0, pl.cdiv(n, group), one_group, None)


def live_lanes(live):
    """``(lanes [B], n [1])``: the live lanes first, in order, and how many
    they are — the kernel's prefetched list and its trip count (the idle
    lanes follow; it never reads them)."""
    n = jnp.sum(live.astype(jnp.int32))
    lanes = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return lanes, jnp.reshape(n, (1,))


def step_pallas(states, plane: int, x, dt, a_head, bm, cm, live=None,
                interpret: bool = False):
    """:func:`step` on plane ``plane`` of ``states`` [planes, B, H / pack, N,
    pack P] (the STORED form, :func:`state_shape`), in place: the kernel
    reads a LIVE lane's state once and writes it once — what the recurrence
    has to move — and an idle lane's not at all; the other planes are not
    touched (the array stays in HBM, aliased to the output, and the kernel
    copies the live lanes' blocks itself: :func:`_step_kernel`). ``x`` [B, H,
    P], ``dt`` [B, H], ``bm, cm`` [B, G, N]. Returns ``(states, y [B, H,
    P])``, an idle lane's ``y`` zero."""
    _, b, rows, n, width = states.shape
    h, p = x.shape[1:]
    g = bm.shape[1]
    if live is None:
        live = jnp.ones((b,), bool)
    lanes, count = live_lanes(live)
    dt = dt.astype(F32)
    decay = jnp.repeat(jnp.exp(dt * a_head), p, axis=1).reshape(
        b, rows, width)
    xdt = (dt[..., None] * x).astype(F32).reshape(b, rows, width)

    def vectors(m):     # [B, G, N] in whole rows of ``LANES`` numbers
        return jnp.pad(m.astype(F32).reshape(b * g, n),
                       ((0, 0), (0, -n % LANES))).reshape(-1, LANES)

    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    block = rows * n * width * 4
    # (a group's slots in 48 MB of VMEM at most: a lane's block is 2 MB at
    # the published widths)
    group = max(1, min(GROUP_LANES, b, (48 << 20) // block))
    states, y = pl.pallas_call(
        functools.partial(_step_kernel, plane=plane, groups=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[where_it_lies, whole, whole, whole, whole],
            out_specs=[where_it_lies, whole],
            scratch_shapes=[pltpu.VMEM((group, rows, n, width), F32),
                            pltpu.SemaphoreType.DMA((group,)),
                            pltpu.SemaphoreType.DMA((group,))]),
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct((b, rows, width), F32)],
        # operand 2 (after the two prefetched scalars) is the states array
        input_output_aliases={2: 0},
        # VMEM: the slots and 8 MB, no more — what the call reserves XLA
        # cannot prefetch the layers' weights into. And NO cost estimate: the
        # scheduler reads one as time under which to hide those prefetches,
        # and this call leaves the HBM none — told the bytes it moves, XLA
        # piled the next projections' weights on it and left the short
        # operations around it with nothing in flight (PR 56: a decode step
        # of granite-4.0-h-micro 16.55 ms told, 15.45 untold; 15.84 with 64
        # MB reserved)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=group * block + (8 << 20)),
        name=STEP_KERNEL,
        interpret=interpret,
    )(lanes, count, states, decay, xdt, vectors(bm), vectors(cm))
    y = jnp.where(live[:, None, None], y.reshape(b, h, p), 0.0)
    return states, y


def _mask_padding(dt, valid):
    return dt if valid is None else jnp.where(valid[..., None], dt, 0.0)


def scan(state, x, dt, a_head, bm, cm, valid=None):
    """A token at a time: ``x`` [B, T, H, P], ``dt`` [B, T, H], ``bm, cm``
    [B, T, G, N], ``valid`` [B, T] bool or None. Returns ``(state, y [B, T,
    H, P])``."""
    dt = _mask_padding(dt, valid)

    def body(s, xs):
        xt, dtt, bt, ct = xs
        return step(s, xt, dtt, a_head, bt, ct)

    state, y = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, bm, cm)))
    return state, jnp.moveaxis(y, 0, 1)


def chunked(state, x, dt, a_head, bm, cm, valid=None, block: int = BLOCK):
    """The chunkwise-parallel (SSD) form of :func:`scan` (same arguments,
    same result up to rounding): blocks of ``block`` tokens, a ``lax.scan``
    over the blocks that carries the state. ``T`` must be a multiple of the
    block; a shorter call is one block."""
    b, t, h, p = x.shape
    c = min(block, t)
    if t % c:
        raise ValueError(f"{t} tokens are not whole blocks of {c}")
    dt = _mask_padding(dt, valid)
    nb = t // c

    def blocks(a):          # [B, T, H | G, ...] -> [NB, B, H | G, C, ...]
        a = a.reshape((b, nb, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    lower = jnp.tril(jnp.ones((c, c), bool))

    def one_block(z, xs):
        xb, dtb, bb, cb = xs   # [B,H,C,P], [B,H,C,1], [B,G,C,N] x 2
        dtb = dtb[..., 0]
        g = jnp.cumsum(dtb * a_head[None, :, None], axis=2)     # [B, H, C]
        # e^{g_t - g_i} where i <= t, 0 elsewhere (masked BEFORE the
        # exponential: above the diagonal the difference is positive)
        seg = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                                -jnp.inf))                      # [B, H, C, C]
        scores = jnp.einsum("bgtn,bgin->bgti", cb, bb, precision=HIGHEST)
        inputs = dtb[..., None] * xb                            # [B, H, C, P]
        y = jnp.einsum("bhti,bhip->bhtp", _to_heads(scores, h, 1) * seg,
                       inputs, precision=HIGHEST) \
            + jnp.exp(g)[..., None] * jnp.einsum(
                "bhtn,bhpn->bhtp", _to_heads(cb, h, 1), z, precision=HIGHEST)
        to_end = jnp.exp(g[..., -1:] - g)                       # [B, H, C]
        z = jnp.exp(g[..., -1])[..., None, None] * z + jnp.einsum(
            "bhip,bhin->bhpn", to_end[..., None] * inputs,
            _to_heads(bb, h, 1), precision=HIGHEST)
        return z, y

    state, y = jax.lax.scan(
        one_block, state,
        (blocks(x), blocks(dt[..., None]), blocks(bm), blocks(cm)))
    # [NB, B, H, C, P] -> [B, T, H, P]
    y = jnp.moveaxis(jnp.moveaxis(y, 2, 3), 0, 1).reshape(b, t, h, p)
    return state, y

