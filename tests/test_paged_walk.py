"""The page walk inside the paged decode kernel (ISSUE 40): the kernel copies
``ceil(len / BS)`` pages a sequence out of the pool itself, a wave of several
at a time, and never touches what lies past them; its arithmetic goes a block
of KV heads an update (ISSUE 47). Interpreted on the CPU at the benchmark
cells' per-chip shapes, against the XLA oracle."""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import tpu9.ops.attention as attention_ops
from tpu9.ops.attention import paged_kernel_form
from tpu9.ops import paged_attention as pa
from tpu9.ops.quant import quantize_kv

BS, D, LAYERS, LAYER = 128, 128, 2, 1
# cell: KV heads a chip, query heads a KV head, table columns
SHAPES = {"mixtral": (8, 4, 33), "tp4-long": (2, 4, 129), "ouro": (16, 1, 9),
          "evabyte": (32, 1, 7)}
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
    k, v = (np.full((LAYERS, n_blocks, BS, kh, D), np.nan, np.float32)
            for _ in range(2))
    own = sorted(owned)
    for pool in (k, v):
        pool[LAYER, own] = rng.standard_normal((len(own), BS, kh, D),
                                               np.float32)
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
                                        ("ouro", 4), ("evabyte", 2)])
def test_pages_a_wave_follow_from_the_page_bytes(cell, pages):
    kh, _, columns = SHAPES[cell]
    assert _wave(kh, columns, jnp.bfloat16) == pages
    # a power of two, never wider than the table, never none
    assert pa._pages_per_wave(BS * kh * D * 2, 3) == 2
    assert pa._pages_per_wave(64 << 20, columns) == 1


@pytest.mark.parametrize("kh,group,heads", [
    (32, 1, 8), (16, 1, 8), (8, 4, 2), (2, 4, 2),     # the cells
    (8, 1, 8), (4, 1, 4), (2, 1, 2), (1, 1, 1),       # fewer heads than 8
    (8, 2, 4), (8, 8, 2), (4, 16, 2),                 # a tile or more a pair
    (12, 1, 12), (3, 2, 3), (8, 3, 8), (4, 7, 4),     # what does not divide
])
def test_heads_an_update_follow_from_the_heads_and_their_query_rows(
        kh, group, heads):
    """As many KV heads as fill eight rows, at least a pair; all of them
    where that does not divide the heads or is no whole tile of rows (a
    float32 pool of any head count; in bfloat16 a group that is no power of
    two: 28 query heads on 4 KV heads, Qwen2-7B's)."""
    assert pa._heads_per_update(kh, group) == heads
    assert kh % heads == 0
    assert heads == kh or (heads * group) % 8 == 0


@pytest.mark.parametrize("kh,group,dtype", [
    (16, 1, jnp.float32), (16, 1, jnp.bfloat16), (8, 4, jnp.float32),
    (8, 4, jnp.bfloat16), (8, 2, jnp.bfloat16), (12, 1, jnp.float32),
    (4, 7, jnp.bfloat16),
], ids=lambda x: x if isinstance(x, int) else jnp.dtype(x).name)
def test_a_block_of_heads_equals_a_head_at_a_time(kh, group, dtype):
    """The walk's update of a block of heads against the grid's, which is
    ``_head_update`` a head, on the same pool: two blocks of eight and four
    pairs in a loop, two blocks of four, and heads the block does not divide
    into whole tiles (twelve at a row a head, four at seven: one block of
    all)."""
    assert pa._pages_can_be_cut(
        jax.ShapeDtypeStruct((LAYERS, 1, BS, kh, D), dtype))
    columns = 5
    lens = _lengths(_wave(kh, columns, dtype), columns)
    q, k, v, table, lens = _case(kh, group, columns, lens, dtype, seed=6)
    safe = jnp.where(table < k.shape[1], table, 0)
    k0, v0 = (jnp.nan_to_num(x.astype(jnp.float32)).astype(dtype)
              for x in (k, v))
    got = pa._page_walk(q, k, v, table, lens, LAYER, True)
    want = pa._page_grid(q, (k0, v0), safe, lens, LAYER, True)
    _close(got, want, dtype, lens)


@pytest.mark.parametrize("cell,said", [
    ("evabyte", "pallas walk, 8 heads x 1 row an update, 2 pages a wave"),
    ("ouro", "pallas walk, 8 heads x 1 row an update, 4 pages a wave"),
    ("mixtral", "pallas walk, 2 heads x 4 rows an update, 8 pages a wave"),
    ("tp4-long", "pallas walk, 2 heads x 4 rows an update, 32 pages a wave"),
])
def test_the_form_that_runs_is_named_for_the_health_page(cell, said):
    kh, group, columns = SHAPES[cell]
    pool = jax.ShapeDtypeStruct((LAYERS, 9, BS, kh, D), jnp.bfloat16)
    assert pa.paged_decode_form(pool, kh * group, columns) == said
    for other in (jax.ShapeDtypeStruct(pool.shape, jnp.int8),
                  jax.ShapeDtypeStruct((LAYERS, 9, BS, kh, 64),
                                       jnp.bfloat16)):
        assert pa.paged_decode_form(other, kh * group, columns) == \
            "pallas grid, 1 head an update, 1 page a step"
    # an engine's whole pool on a mesh: the words are about a chip's heads
    chips = SimpleNamespace(shape={"tp": 4})
    whole = jax.ShapeDtypeStruct((LAYERS, 9, BS, 4 * kh, D), jnp.bfloat16)
    assert paged_kernel_form(whole, 4 * kh * group, columns, chips) == said
    assert paged_kernel_form(pool, kh * group, columns) == said


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
