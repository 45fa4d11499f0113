"""``tpu9.models.kvstate``: the one owner of the KV state's format (ISSUE 51).

Over the five shapes the configurations have, at tiny widths — per-head rows
in the model's type, the int8 pool, a looped decoder's depth, window-summary
entries, latent rows with state a lane — the pool's bytes are its shapes',
the scratch and the pool agree on every row, a mesh shards the KV-head axis,
and ``write`` then ``attend`` equals the XLA oracles of ``ops/`` over a
cache assembled by hand. One AST case holds that nothing else under
``tpu9/models/`` reads a key of the cache dict.
"""

from __future__ import annotations

import ast
import pathlib
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu9.models import kvstate
from tpu9.models.llama import LLAMA_PRESETS
from tpu9.models.ouro import OURO_PRESETS
from tpu9.models.transformer import DecoderConfig
from tpu9.ops.attention import (xla_chunk_prefill_attention,
                                xla_decode_attention)
from tpu9.ops.latent_attention import paged_latent_attention_xla
from tpu9.ops.quant import dequantize_kv, quantize_kv
from tpu9.serving.engine import EngineConfig
from tpu9.serving.feasibility import kv_cache_bytes, lane_state_bytes
from tpu9.serving.graphs import abstract_state
from tpu9.serving.kvpool import KvPool
from tpu9.serving.paged_kv import kv_block_bytes, scratch_len
from tpu9.serving.shard import make_policy
from tpu9.serving.shard.policy import SingleDevicePolicy

PLAIN = replace(LLAMA_PRESETS["llama-tiny"], dtype=jnp.float32)
BS, S, LANES, BLOCKS = 16, 128, 2, 8
# name -> (config, int8 pool): the five shapes a configuration's cache has
CASES = {
    "plain": (PLAIN, False),
    "int8-pool": (replace(PLAIN, dtype=jnp.bfloat16), True),
    "looped": (replace(OURO_PRESETS["ouro-tiny"], dtype=jnp.float32), False),
    "window": (DecoderConfig(
        vocab_size=320, dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
        head_dim=32, hidden_dim=256, max_seq_len=512, norm_offset=1.0,
        attn_window=64, attn_chunk=4, dtype=jnp.float32), False),
    "latent": (DecoderConfig(
        vocab_size=256, dim=128, n_layers=6, n_heads=4, n_kv_heads=4,
        head_dim=32, hidden_dim=256, max_seq_len=512, layer_group=3,
        mla_latent=64, mla_nope=32, mla_rope=16, mla_v=32, kda_conv=4,
        kda_gate_bound=-5.0, dtype=jnp.float32), False),
    # state a lane beside PER-HEAD rows: a listed pattern (ISSUE 55), a pool
    # of two planes for seven layers
    "listed": (DecoderConfig(
        vocab_size=256, dim=64, n_layers=7, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=128, max_seq_len=512, tie_embeddings=True,
        layer_pattern=("ssm", "ssm", "full", "ssm", "ssm", "full", "ssm"),
        ssm_heads=4, ssm_head_dim=16, ssm_state=32, ssm_conv=4, rope=False,
        dtype=jnp.float32), False),
    # a list of HALF-layers (ISSUE 59): five mixers' state a lane (heads in
    # two groups), ONE plane of per-head rows for eleven layers, and expert
    # layers that keep nothing
    "halves": (DecoderConfig(
        vocab_size=256, dim=64, n_layers=11, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=48, max_seq_len=512, act="relu2", rope=False,
        layer_pattern=("ssm", "none") * 3 + ("ssm", "full", "none", "ssm",
                                             "none"),
        ffn_pattern=("none", "experts") * 3 + ("none", "none", "experts",
                                               "none", "experts"),
        ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_groups=2, ssm_conv=4,
        ssm_norm_groups=2, n_experts=4, moe_top_k=4, moe_hidden_dim=48,
        moe_routed=16, moe_shared_dim=96, moe_score="sigmoid",
        moe_select_bias=True, moe_gate_scale=5.0, moe_gated=False,
        moe_latent_dim=32, dtype=jnp.float32), False),
}
# the kind of layer whose state a case keeps by lane
LANE_KIND = {"latent": "kda", "listed": "ssm", "halves": "ssm"}
PER_HEAD = [name for name in CASES if name != "latent"]
case = pytest.mark.parametrize("name", list(CASES))


def _ecfg(quantized: bool, pool_blocks: int = 0) -> EngineConfig:
    return EngineConfig(max_batch=LANES, max_seq_len=S, kv_block_size=BS,
                        kv_pool_blocks=pool_blocks, prefill_chunk=BS,
                        kv_quant="int8" if quantized else "")


# ---------------------------------------------------------------------------
# the format: bytes against shapes, scratch against pool, the sharded axis
# ---------------------------------------------------------------------------

@case
def test_pool_bytes_are_blocks_times_block_bytes(name):
    """New cover: at the parent ``kv_block_bytes`` was an arithmetic of its
    own beside ``KvPool.array_shapes`` and nothing held the two together."""
    cfg, quantized = CASES[name]
    for pool_blocks in (0, 5):
        pool = KvPool(cfg, _ecfg(quantized, pool_blocks), quantized,
                      SingleDevicePolicy())
        arrays = pool.init_arrays()
        block = kvstate.block_bytes(cfg, BS, quantized)
        assert sum(arrays[n].nbytes for n in pool.wire_names()) \
            == pool.n_blocks * block
        lanes = kvstate.lane_shapes(cfg, LANES)
        assert sum(arrays[n].nbytes for n in lanes) \
            == kvstate.lane_bytes(cfg, LANES) == lane_state_bytes(cfg, LANES)
        assert set(arrays) == {*pool.wire_names(), kvstate.TABLE, *lanes}
        # the public names price with the same sum
        assert kv_block_bytes(cfg, BS, quantized) == block
        assert kv_cache_bytes(cfg, LANES, S, quantized) == LANES \
            * kvstate.block_bytes(cfg, cfg.kv_entries_peak(S), quantized)
    assert bool(kvstate.lane_bytes(cfg)) == (name in LANE_KIND)
    assert cfg.lane_state == ((LANE_KIND[name],) if name in LANE_KIND else ())
    if quantized:
        # equal-HBM sizing: the int8 pool spends what the plain one would
        int8, plain = (KvPool(cfg, _ecfg(q), q, SingleDevicePolicy())
                       for q in (True, False))
        assert (int8.n_blocks - 1) * block <= (plain.n_blocks - 1) \
            * kvstate.block_bytes(cfg, BS) < int8.n_blocks * block


@case
def test_scratch_and_pool_agree_on_every_row(name):
    cfg, quantized = CASES[name]
    pool = kvstate.pool_shapes(cfg, BLOCKS, BS, quantized)
    dense = kvstate.dense_shapes(cfg, 1, S)
    assert [row for row, _ in kvstate.paged_planes(cfg).values()] \
        == list(cfg.kv_row)
    for plane in ("k", "v"):
        assert dense[plane][0][:3] == (cfg.kv_layers, 1, S)
        if name == "latent" and plane == "v":
            # a pool's rotated keys: two tokens a row, the same numbers
            assert pool[plane][0] == (cfg.kv_layers, BLOCKS, BS // 2, 1,
                                      2 * cfg.mla_rope)
            assert dense[plane][0][3:] == (1, cfg.mla_rope)
        else:
            assert pool[plane][0][:3] == (cfg.kv_layers, BLOCKS, BS)
            assert pool[plane][0][3:] == dense[plane][0][3:]
        assert dense[plane][1] == cfg.dtype       # the scratch is never int8
        assert pool[plane][1] == (jnp.int8 if quantized else cfg.dtype)
    if quantized:
        assert pool["k_scale"] == (pool["k"][0][:-1], jnp.float32)
    assert set(dense) - {"k", "v"} == set(kvstate.lane_shapes(cfg, 1))
    cache = kvstate.init_kv_cache(cfg, 1, S)
    assert {n: (a.shape, a.dtype) for n, a in cache.items()} == dense
    # what the engine and graphcheck lower against is the same format
    ecfg = _ecfg(quantized)
    state = abstract_state(cfg, ecfg, SingleDevicePolicy(), quantized)
    rows = scratch_len(cfg, S, BS)
    assert {n: (a.shape, a.dtype) for n, a in state["scratch"].items()} \
        == kvstate.dense_shapes(cfg, 1, rows)
    assert set(state["pool"]) == set(kvstate.paged_planes(cfg, quantized))
    assert kvstate.dense_len(cache) == S
    assert kvstate.dense_len(dict(cache, table=None)) == 0


@case
def test_a_mesh_shards_the_kv_head_axis(name):
    cfg, quantized = CASES[name]
    policy = make_policy("2x1", devices=jax.devices()[:2])
    heads = cfg.kv_row[0][0]
    shapes = {**kvstate.pool_shapes(cfg, BLOCKS, BS, quantized),
              **kvstate.dense_shapes(cfg, 1, S)}
    for plane, (shape, _) in shapes.items():
        spec = tuple(policy.kv_spec(plane, len(shape)))
        if plane in kvstate.lane_shapes(cfg, 1):
            assert not any(spec)
            continue
        assert shape[kvstate.HEAD_AXIS] == heads
        assert [i for i, axis in enumerate(spec) if axis] \
            == [kvstate.HEAD_AXIS]
    assert not any(tuple(policy.kv_spec(kvstate.TABLE, 2)))


# ---------------------------------------------------------------------------
# write, then attend, against the oracles of ops/ over a cache built by hand
# ---------------------------------------------------------------------------

TABLE = np.asarray([[3, 1, 4, 0, 0, 0, 0], [2, 5, 6, 0, 0, 0, 0]], np.int32)


def _rows(seed, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _stored(x, quantized):
    """What the cache gives back for a row written as ``x``."""
    return dequantize_kv(*quantize_kv(x), x.dtype) if quantized else x


def _by_hand(rows, entries, width):
    """``[B, width, ...]`` holding ``rows`` [B, T, ...] at ``entries``."""
    out = np.zeros(rows.shape[:1] + (width,) + rows.shape[2:], np.float32)
    for b in range(rows.shape[0]):
        out[b, np.asarray(entries[b])] = np.asarray(rows[b], np.float32)
    return jnp.asarray(out).astype(rows.dtype)


def _close(got, want, quantized):
    tol = 2e-2 if quantized else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("form", ["decode", "verify"])
@pytest.mark.parametrize("name", PER_HEAD)
def test_paged_write_then_attend_is_the_oracle(name, form):
    cfg, quantized = CASES[name]
    layer, t = cfg.kv_layers - 1, 20      # the last plane: a pass's depth
    kh, d = cfg.kv_row[0]
    pool = {n: jnp.zeros(shape, dt) for n, (shape, dt) in kvstate.pool_shapes(
        cfg, BLOCKS, BS, quantized).items()}
    kv = dict(pool, table=jnp.asarray(TABLE))
    assert kvstate.is_paged(kv)
    # a window of 20 tokens a lane, past the first attention window where
    # there is one (entries then differ from positions), then one decode row
    first = jnp.asarray([[cfg.attn_window + 3], [cfg.attn_window + 7]])
    positions = first + jnp.arange(t + 1)[None, :]
    entries = cfg.kv_entry(positions)
    k = _rows(1, (LANES, t + 1, kh, d), cfg.dtype)
    v = _rows(2, (LANES, t + 1, kh, d), cfg.dtype)
    q = _rows(3, (LANES, t + 1, cfg.n_heads, d), cfg.dtype)
    kv = kvstate.write(kv, layer, k[:, :t], v[:, :t], entries[:, :t], False)
    width = TABLE.shape[1] * BS
    if form == "verify":
        got = kvstate.attend(kv, layer, q[:, :t], None, None, entries[:, :t],
                             None, False)
        want = xla_chunk_prefill_attention(
            q[:, :t],
            _by_hand(_stored(k[:, :t], quantized), entries[:, :t], width),
            _by_hand(_stored(v[:, :t], quantized), entries[:, :t], width),
            entries[:, :t])
    else:
        kv = kvstate.write(kv, layer, k[:, t:], v[:, t:], entries[:, t:],
                           True)
        cache_len = cfg.kv_entries(positions[:, t] + 1)
        got = kvstate.attend(kv, layer, q[:, t:], None, None, entries[:, t:],
                             cache_len, True)
        want = xla_decode_attention(
            q[:, t:], _by_hand(_stored(k, quantized), entries, width),
            _by_hand(_stored(v, quantized), entries, width), cache_len)
    _close(got, want, quantized)
    # no other plane, and no block the table does not name, was touched
    assert not np.asarray(kv["k"][:layer]).any()
    assert not np.asarray(kv["v"][layer, 7]).any()
    assert (kv["k"].dtype == jnp.int8) == quantized


@pytest.mark.parametrize("form", ["decode", "chunk", "prompt"])
@pytest.mark.parametrize("name", PER_HEAD)
def test_dense_write_then_attend_is_the_oracle(name, form):
    cfg, _ = CASES[name]
    layer, t, rows = cfg.kv_layers - 1, 16, 64
    kh, d = cfg.kv_row[0]
    b = LANES if form == "decode" else 1
    kv = kvstate.init_kv_cache(cfg, b, rows)
    assert not kvstate.is_paged(kv)
    k = _rows(4, (b, 2 * t, kh, d), cfg.dtype)
    v = _rows(5, (b, 2 * t, kh, d), cfg.dtype)
    q = _rows(6, (b, 2 * t, cfg.n_heads, d), cfg.dtype)
    entries = jnp.broadcast_to(jnp.arange(2 * t), (b, 2 * t))
    hand = [_by_hand(x, entries, rows) for x in (k, v)]
    if form == "prompt":
        kv = kvstate.write(kv, layer, k, v, entries, False)
        got = kvstate.attend(kv, layer, q, k, v, entries, None, False)
        want = xla_chunk_prefill_attention(q, *hand, entries)
    elif form == "chunk":
        for at in (0, t):          # two chunks, the second at its offset
            kv = kvstate.write(kv, layer, k[:, at:at + t], v[:, at:at + t],
                               entries[:, at:at + t], False)
        got = kvstate.attend(kv, layer, q[:, t:], k[:, t:], v[:, t:],
                             entries[:, t:], jnp.asarray([2 * t]), False)
        want = xla_chunk_prefill_attention(q[:, t:], *hand, entries[:, t:])
    else:
        kv = kvstate.write(kv, layer, k[:, :-1], v[:, :-1], entries[:, :-1],
                           False)
        kv = kvstate.write(kv, layer, k[:, -1:], v[:, -1:], entries[:, -1:],
                           True)
        cache_len = jnp.full((b,), 2 * t)
        got = kvstate.attend(kv, layer, q[:, -1:], k[:, -1:], v[:, -1:],
                             entries[:, -1:], cache_len, True)
        want = xla_decode_attention(q[:, -1:], *hand, cache_len)
    _close(got, want, False)
    np.testing.assert_array_equal(np.asarray(kv["k"][layer]),
                                  np.asarray(hand[0]))
    assert not np.asarray(kv["v"][:layer]).any()


@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_latent_rows(form):
    cfg, _ = CASES["latent"]
    plane, steps = cfg.kv_layers - 1, 9
    (_, dc), (_, dr) = cfg.kv_row
    c = _rows(7, (LANES, steps, dc), cfg.dtype)
    r = _rows(8, (LANES, steps, dr), cfg.dtype)
    if form == "decode":
        kv = dict({n: jnp.zeros(shape, dt) for n, (shape, dt)
                   in kvstate.pool_shapes(cfg, BLOCKS, BS).items()},
                  table=jnp.asarray(TABLE))
        first = jnp.asarray([[12], [30]])    # lane 0 crosses into block 2
        for i in range(steps):
            kv = kvstate.write(kv, plane, c[:, i:i + 1], r[:, i:i + 1],
                               first + i, True)
        hand = [np.zeros(kv[n].shape, np.float32) for n in ("k", "v")]
        assert hand[1].shape[2:] == (BS // 2, 1, 2 * dr)
        for b in range(LANES):
            for i in range(steps):
                at = int(first[b, 0]) + i
                block, row = TABLE[b, at // BS], at % BS
                hand[0][plane, block, row, 0] = c[b, i]
                # a rotated key: token j and token j + BS / 2 share row j
                half = row // (BS // 2)
                hand[1][plane, block, row % (BS // 2), 0,
                        half * dr:(half + 1) * dr] = r[b, i]
        hand = [jnp.asarray(h) for h in hand]
        np.testing.assert_array_equal(np.asarray(kv["k"]), hand[0])
        np.testing.assert_array_equal(np.asarray(kv["v"]), hand[1])
        # read back in token order, block by block
        got = kvstate.read_blocks(kv, "v", jnp.asarray(TABLE[0]))
        assert got.shape == (cfg.kv_layers, TABLE.shape[1], BS, 1, dr)
        at = int(first[0, 0])
        np.testing.assert_array_equal(
            np.asarray(got[plane]).reshape(-1, dr)[at:at + steps],
            np.asarray(r[0]))
        q_lat = _rows(9, (LANES, cfg.n_heads, dc), cfg.dtype)
        q_rope = _rows(10, (LANES, cfg.n_heads, dr), cfg.dtype)
        lengths = first[:, 0] + steps
        _close(kvstate.latent_attend(kv, plane, q_lat, q_rope, lengths, 0.1),
               paged_latent_attention_xla(q_lat, q_rope, *hand,
                                          jnp.asarray(TABLE), lengths, plane,
                                          0.1), False)
    elif form == "chunk":
        kv = kvstate.init_kv_cache(cfg, 1, 64)
        positions = 16 + jnp.arange(steps)[None, :]
        kv = kvstate.write(kv, plane, c[:1], r[:1], positions, False)
        latents, rotated = kvstate.latent_rows(kv, plane)
        np.testing.assert_array_equal(
            np.asarray(latents), np.asarray(_by_hand(c[:1], positions, 64)[0]))
        np.testing.assert_array_equal(
            np.asarray(rotated), np.asarray(_by_hand(r[:1], positions, 64)[0]))
        assert not np.asarray(kv["k"][:plane]).any()


@pytest.mark.parametrize("name", list(LANE_KIND))
def test_lane_state_is_read_and_written_at_its_plane(name):
    """State a lane, beside latent rows (KDA) and beside per-head planes (a
    listed pattern's state-space layers): a plane written and read back,
    the others untouched, a step in place handing the whole array back —
    and the dense scratch carrying exactly one lane of it."""
    cfg, _ = CASES[name]
    kind = LANE_KIND[name]
    state_name, conv_name = kvstate.LANE_KINDS[kind]
    kv = kvstate.init_kv_cache(cfg, LANES, 16)
    shapes = kvstate.lane_shapes(cfg, LANES)
    assert list(shapes) == [state_name, conv_name]
    assert shapes[state_name][0][:2] == (len(cfg.layers_of(kind)), LANES)
    assert shapes[state_name][1] == jnp.float32
    last = shapes[state_name][0][0] - 1
    state = _rows(11, shapes[state_name][0][1:], jnp.float32)
    tail = _rows(12, shapes[conv_name][0][1:], cfg.dtype)
    kv = kvstate.lane_write(kv, last, tail, state=state, kind=kind)
    for got, want in zip(kvstate.lane_read(kv, last, kind), (state, tail)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(kvstate.lane_read(kv, 0, kind)[0]).any()
    # a step in place hands the whole array back
    whole = kvstate.lane_states(kv, kind) + 1.0
    kv = kvstate.lane_write(kv, 0, tail, states=whole, kind=kind)
    np.testing.assert_array_equal(np.asarray(kvstate.lane_states(kv, kind)),
                                  np.asarray(whole))
    np.testing.assert_array_equal(
        np.asarray(kvstate.lane_read(kv, 0, kind)[1]), np.asarray(tail))
    # the scratch of a chunked prefill: one lane, the same planes
    one = kvstate.dense_shapes(cfg, 1, 32)
    for n, (shape, dt) in shapes.items():
        assert one[n] == (shape[:1] + (1,) + shape[2:], dt)
    assert kvstate.lane_bytes(cfg, LANES) == LANES * kvstate.lane_bytes(cfg)


# ---------------------------------------------------------------------------
# the one owner
# ---------------------------------------------------------------------------

def test_nothing_else_under_models_reads_a_key_of_the_cache_dict():
    """No module of ``tpu9/models/`` but ``kvstate`` subscripts the cache
    dict by a literal key, asks whether it has one, or rebuilds it with one;
    ``hybrid`` imports no private name of ``transformer``."""
    caches = ("kv_cache", "kv", "cache", "scratch", "pool")
    found = []
    for path in pathlib.Path(kvstate.__file__).parent.glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            held = node.value if isinstance(node, ast.Subscript) \
                else node.comparators[0] if isinstance(node, ast.Compare) \
                else node.args[0] if isinstance(node, ast.Call) \
                and getattr(node.func, "id", "") == "dict" and node.args \
                and node.keywords else None
            key = node.slice if isinstance(node, ast.Subscript) \
                else node.left if isinstance(node, ast.Compare) else None
            literal = key is None or (isinstance(key, ast.Constant)
                                      and isinstance(key.value, str))
            if path.name != "kvstate.py" and literal \
                    and getattr(held, "id", "") in caches:
                found.append(f"{path.name}:{node.lineno}")
            if isinstance(node, ast.ImportFrom) and path.name == "hybrid.py" \
                    and node.module == "transformer":
                found += [f"hybrid imports {a.name}" for a in node.names
                          if a.name.startswith("_")]
    assert not found, found
