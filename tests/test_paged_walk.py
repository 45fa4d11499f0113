"""The page walk inside the paged decode kernel (ISSUE 40): the kernel copies
``ceil(len / BS)`` pages a sequence out of the pool itself, a wave of several
at a time, and never touches what lies past them. Interpreted on the CPU at
the three benchmark cells' per-chip shapes, against the XLA oracle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import tpu9.ops.attention as attention_ops
from tpu9.ops import paged_attention as pa
from tpu9.ops.quant import quantize_kv

BS, D, LAYERS, LAYER = 128, 128, 2, 1
# cell: KV heads a chip, query heads a KV head, table columns
SHAPES = {"mixtral": (8, 4, 33), "tp4-long": (2, 4, 129), "ouro": (16, 1, 9)}
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _wave(kh, columns, dtype):
    return pa._pages_per_wave(BS * kh * D * jnp.dtype(dtype).itemsize,
                              columns)


def _lengths(wave, columns):
    """An empty slot, one token, around a page's edge, exactly one wave, one
    wave and a page, the whole table."""
    return [0, 1, BS - 1, BS, BS + 1, wave * BS, (wave + 1) * BS,
            columns * BS]


def _case(kh, group, columns, lens, dtype, seed=0, shared=0):
    """(q, k_pool, v_pool, table, lens): physical pages shuffled, the first
    ``shared`` columns of every row the same pages; NaN in the trash block,
    in every block no sequence owns, in the pool's last block and in the
    whole of the other layer; table entries past a row's pages far out of
    range."""
    rng = np.random.default_rng(seed)
    pages = [-(-n // BS) for n in lens]
    n_blocks = 1 + shared + sum(max(p - shared, 0) for p in pages) + 3
    order = list(rng.permutation(np.arange(1, n_blocks - 1)))
    prefix = [order.pop() for _ in range(shared)]
    table = np.full((len(lens), columns), 2 ** 30, np.int32)
    owned = set(prefix)
    for b, p in enumerate(pages):
        row = (prefix + [order.pop() for _ in range(max(p - shared, 0))])[:p]
        table[b, :p] = row
        owned.update(row)
    shape = (LAYERS, n_blocks, BS, kh, D)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    unowned = [i for i in range(n_blocks) if i not in owned]
    for pool in (k, v):
        pool[LAYER, unowned] = np.nan
        pool[1 - LAYER] = np.nan
    q = rng.standard_normal((len(lens), 1, kh * group, D)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(table),
            jnp.asarray(lens, jnp.int32))


def _oracle(q, k, v, table, lens, *scales):
    """The XLA oracle on a table whose unread entries point at block 0 and a
    pool whose NaN are zeros: what the walk must give without reading
    either."""
    pages = (np.asarray(lens) + BS - 1) // BS
    valid = np.arange(table.shape[1])[None, :] < pages[:, None]
    table = jnp.where(valid, table, 0)
    k, v = (jnp.nan_to_num(x.astype(jnp.float32)).astype(x.dtype)
            for x in (k, v))
    return pa.xla_paged_decode_attention(q, k, v, table, lens, *scales,
                                         layer=LAYER)


def _close(got, want, dtype, lens):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=TOL[dtype])
    assert not got[np.asarray(lens) == 0].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cell", list(SHAPES))
def test_walk_matches_the_oracle_at_every_length(cell, dtype):
    kh, group, columns = SHAPES[cell]
    wave = _wave(kh, columns, dtype)
    assert wave < columns and (wave == 1 or columns % wave)
    lens = _lengths(wave, columns)
    q, k, v, table, lens = _case(kh, group, columns, lens, dtype)
    got = pa.paged_decode_attention(q, k, v, table, lens, layer=LAYER,
                                    interpret=True)
    _close(got, _oracle(q, k, v, table, lens), dtype, lens)


@pytest.mark.parametrize("cell", list(SHAPES))
def test_two_sequences_share_their_prefix_pages(cell):
    kh, group, columns = SHAPES[cell]
    wave = _wave(kh, columns, jnp.float32)
    lens = [(wave + 1) * BS + 7, 0, wave * BS + BS // 2, (wave + 2) * BS]
    q, k, v, table, lens = _case(kh, group, columns, lens, jnp.float32,
                                 seed=1, shared=wave)
    assert (np.asarray(table)[[0, 2, 3], :wave] == np.asarray(
        table)[0, :wave]).all()
    got = pa.paged_decode_attention(q, k, v, table, lens, layer=LAYER,
                                    interpret=True)
    _close(got, _oracle(q, k, v, table, lens), jnp.float32, lens)


@pytest.mark.parametrize("lens", [
    [0, 0, 0, 0], [0, 0, 5, 0, 300, 0], [300, 0, 0, 0], [1] * 5,
], ids=["all_empty", "empties_between", "empties_after", "one_token_each"])
def test_empty_slots_cost_no_page_and_break_no_chain(lens):
    """The next sequence's first wave is started by the sequence before it:
    a slot without tokens must hand that on, wherever it stands."""
    kh, group, columns = SHAPES["mixtral"]
    q, k, v, table, lens = _case(kh, group, columns, lens, jnp.float32,
                                 seed=2)
    got = pa.paged_decode_attention(q, k, v, table, lens, layer=LAYER,
                                    interpret=True)
    _close(got, _oracle(q, k, v, table, lens), jnp.float32, lens)


@pytest.mark.parametrize("cell", list(SHAPES))
def test_int8_pool_through_the_same_cases(cell):
    kh, group, columns = SHAPES[cell]
    wave = _wave(kh, columns, jnp.int8)
    lens = _lengths(wave, columns)
    q, k, v, table, lens = _case(kh, group, columns, lens, jnp.float32,
                                 seed=3)
    nan = np.isnan(np.asarray(k[..., 0]))
    kq, ks = quantize_kv(jnp.nan_to_num(k))
    vq, vs = quantize_kv(jnp.nan_to_num(v))
    # an int8 payload has no NaN: the scales carry it
    ks, vs = (jnp.where(nan, jnp.nan, s) for s in (ks, vs))
    got = pa.paged_decode_attention_quant(q, kq, vq, ks, vs, table, lens,
                                          layer=LAYER, interpret=True)
    want = _oracle(q, kq, vq, table, lens, jnp.nan_to_num(ks),
                   jnp.nan_to_num(vs))
    _close(got, want, jnp.float32, lens)


@pytest.mark.parametrize("cell,pages", [("tp4-long", 32), ("mixtral", 8),
                                        ("ouro", 4)])
def test_pages_a_wave_follow_from_the_page_bytes(cell, pages):
    kh, _, columns = SHAPES[cell]
    assert _wave(kh, columns, jnp.bfloat16) == pages
    # a power of two, never wider than the table, never none
    assert pa._pages_per_wave(BS * kh * D * 2, 3) == 2
    assert pa._pages_per_wave(64 << 20, columns) == 1


@pytest.mark.parametrize("kh,d,dtype,cut", [
    (8, 128, jnp.bfloat16, True), (2, 128, jnp.bfloat16, True),
    (16, 128, jnp.bfloat16, True), (1, 128, jnp.bfloat16, False),
    (6, 128, jnp.bfloat16, False), (3, 128, jnp.float32, True),
    (8, 64, jnp.bfloat16, False), (8, 64, jnp.float32, False),
])
def test_pools_the_walk_cannot_cut_take_the_grid(kh, d, dtype, cut):
    """What Mosaic refuses to slice out of HBM (``test_chip_compile`` asks
    the compiler) goes the older way, to the same result."""
    pool = jax.ShapeDtypeStruct((LAYERS, 9, BS, kh, d), dtype)
    assert pa._pages_can_be_cut(pool) is cut
    if cut:
        return
    rng = np.random.default_rng(4)
    k, v = (jnp.asarray(rng.standard_normal((LAYERS, 9, 16, kh, d)), dtype)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((2, 1, 2 * kh, d)), dtype)
    table = (jnp.arange(8, dtype=jnp.int32) + 1).reshape(2, 4)
    lens = jnp.asarray([64, 19], jnp.int32)
    got = pa.paged_decode_attention(q, k, v, table, lens, layer=LAYER,
                                    interpret=True)
    want = pa.xla_paged_decode_attention(q, k, v, table, lens, layer=LAYER)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.multichip
def test_walk_under_shard_map_at_two_kv_heads_a_chip():
    """``mistral-tp4-long``'s kernel as the dispatcher wraps it: eight KV
    heads over four (virtual) chips, each walking its own quarter of the
    stacked pool."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                ("dp", "fsdp", "sp", "tp"))
    lens = [0, 3 * BS + 5, 17 * BS, 1]
    q, k, v, table, lens = _case(8, 4, 129, lens, jnp.float32, seed=5)
    heads = NamedSharding(mesh, attention_ops._HEADS4)
    pool = NamedSharding(mesh, attention_ops._POOL5)
    sharded = attention_ops._per_chip_heads(
        functools.partial(pa.paged_decode_attention, interpret=True), mesh,
        (attention_ops._HEADS4, attention_ops._POOL5, attention_ops._POOL5,
         P(), P(), P()))
    got = jax.jit(sharded)(jax.device_put(q, heads),
                           jax.device_put(k, pool), jax.device_put(v, pool),
                           table, lens, jnp.int32(LAYER))
    _close(got, _oracle(q, k, v, table, lens), jnp.float32, lens)
    assert got.sharding.spec == P(None, None, "tp", None)
