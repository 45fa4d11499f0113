"""One call of the paged decode kernel alone, on the chip, at the shapes the
benchmark's cells serve (ISSUE 47): microseconds a call and the GB/s of the
keys and values the call NEEDS (resident entries x KV heads x head x 2 x 2
bytes), best of ``--repeats`` timings of ``--calls`` calls chained in one
program (a call's output is the next call's query, so no host dispatch is in
the time).

    chiprun -- python3 scripts/paged_kernel_bench.py [--repo DIR] [--shapes ...]

``--repo`` takes ``tpu9`` from another checkout (the parent's ``git archive``)
so both sides are measured by the same code on the same chip. Also prints the
kernel's largest error against a float64 oracle on a float32 query, which is
what tells a float32 product from a bfloat16 one, and a digest of the output's
bytes at the served lengths and at the lengths around a page's and a wave's
edge (``tests/test_paged_walk.py``'s): two sides that print the same digests
give the same result bit for bit. No number of this script is a benchmark
metric; ``PERF.md`` §6 quotes them as "one call alone".
"""

import argparse
import hashlib
import json
import os
import sys
import time

import numpy as np

BS = D = 128
# the benchmark's cells as the kernel sees them (ISSUE 40): slots, KV heads,
# query heads a KV head, table columns, pool blocks, planes of the pool,
# chips. The one table: ``tests/test_chip_compile.py`` compiles the walk for
# a described v5e at these.
CELL_SHAPES = {"mistral-tp4-long": (8, 8, 4, 129, 897, 32, 4),
               "ouro-qa": (16, 16, 1, 9, 31, 192, 1),
               "mixtral": (32, 8, 4, 33, 513, 4, 1),
               "evabyte-files": (16, 32, 1, 24, 216, 16, 1),
               # (eight 64-wide KV heads packed two a 128-lane row: four
               # rows, each read by eight query heads; three planes)
               "lfm2-sessions": (32, 4, 8, 261, 4481, 3, 1)}
# resident entries of a cell's live lanes in a decode step (``mixtral``:
# the batch cell's); the other slots are empty
LIVE = {"mistral-tp4-long": np.linspace(8300, 12000, 8),
        "ouro-qa": (200, 450, 700),
        "mixtral": np.linspace(128, 1280, 32),
        "evabyte-files": (1300, 1600, 3072),
        "lfm2-sessions": np.linspace(8500, 30000, 24)}


def case(name, seed=0, edges=False):
    """(q, k_pool, v_pool, table, lens, layer, needed bytes) of ONE chip's
    share of a cell: the live lanes first, the rest empty; every lane's
    pages distinct and shuffled.
    ``edges``: eight lanes of 0, 1, a page less one, a page, a page and one,
    two pages, nine pages and the whole table instead."""
    import jax
    import jax.numpy as jnp
    slots, kh, group, columns, blocks, planes, chips = CELL_SHAPES[name]
    kh //= chips
    live = [int(n) for n in LIVE[name]]
    if edges:
        live = (0, 1, BS - 1, BS, BS + 1, 2 * BS, 9 * BS, columns * BS)
    lens = np.zeros(slots, np.int32)
    lens[:len(live)] = live
    rng = np.random.default_rng(seed)
    order = list(rng.permutation(np.arange(1, blocks)))
    table = np.zeros((slots, columns), np.int32)
    for b, n in enumerate(lens):
        pages = -(-int(n) // BS)
        assert pages <= columns and len(order) >= pages, name
        table[b, :pages] = [order.pop() for _ in range(pages)]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (planes, blocks, BS, kh, D)
    make = jax.jit(lambda key: jax.random.normal(key, shape, jnp.bfloat16))
    q = jax.random.normal(kq, (slots, 1, kh * group, D), jnp.bfloat16)
    need = int(lens.sum()) * kh * D * 2 * 2
    return (q, make(kk), make(kv), jnp.asarray(table), jnp.asarray(lens),
            planes // 2, need)


def microseconds(kernel, args, calls, repeats):
    """Best of ``repeats``: ``calls`` calls in one program, each call's
    output the next call's query."""
    import jax
    q, k, v, table, lens, layer = args

    @jax.jit
    def chain(q, k, v, table, lens):
        return jax.lax.fori_loop(
            0, calls, lambda _, q: kernel(q, k, v, table, lens, layer), q)

    jax.block_until_ready(chain(q, k, v, table, lens))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q, k, v, table, lens))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def float64_oracle(q, k, v, table, lens, layer):
    q, k, v = (np.asarray(x, np.float32).astype(np.float64)
               for x in (q, k[layer], v[layer]))
    table, lens = np.asarray(table), np.asarray(lens)
    group = q.shape[2] // k.shape[2]
    out = np.zeros(q.shape)
    for b, n in enumerate(lens):
        if not n:
            continue
        pages = table[b, :-(-int(n) // BS)]
        kk = k[pages].reshape(-1, *k.shape[2:])[:n]
        vv = v[pages].reshape(-1, *v.shape[2:])[:n]
        for h in range(q.shape[2]):
            s = kk[:, h // group] @ q[b, 0, h] * D ** -0.5
            p = np.exp(s - s.max())
            out[b, 0, h] = p @ vv[:, h // group] / p.sum()
    return out


def largest_error(kernel, args):
    """On a float32 query (bf16 values) the kernel's output is float32: a
    float32 product reads ~1e-6 against float64, a bf16 one ~1e-3."""
    import jax.numpy as jnp
    q, k, v, table, lens, layer = args
    got = np.asarray(kernel(q.astype(jnp.float32), k, v, table, lens, layer))
    return float(np.abs(got - float64_oracle(q, k, v, table, lens,
                                             layer)).max())


def digest(kernel, args):
    return hashlib.sha256(np.asarray(kernel(*args)).tobytes()).hexdigest()[:16]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=None)
    ap.add_argument("--shapes", default=",".join(CELL_SHAPES))
    ap.add_argument("--wave-bytes", type=int, default=0,
                    help="sweep: this many bytes of K a wave instead of "
                         "the module's WAVE_BYTES")
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--label", default="tree")
    a = ap.parse_args()
    root = os.path.abspath(a.repo or os.path.join(os.path.dirname(__file__),
                                                  ".."))
    sys.path.insert(0, root)
    import jax
    from tpu9.ops import paged_attention as pa
    if not os.path.abspath(pa.__file__).startswith(root):
        sys.exit(f"tpu9 came from {pa.__file__}, not from {root}")
    device = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    if a.wave_bytes:
        pa.WAVE_BYTES = a.wave_bytes    # read when the kernel is traced
    kernel = pa.paged_decode_attention
    with open("chiprun_out/paged_kernel_bench.jsonl", "a") as out:
        for name in a.shapes.split(","):
            *args, need = case(name)
            us = microseconds(kernel, args, a.calls, a.repeats)
            line = {"label": a.label, "shape": name,
                    "wave_bytes": pa.WAVE_BYTES,
                    "device": device.device_kind, "us_a_call": us,
                    "needed_bytes": need, "gb_s": need / us / 1e3,
                    "largest_error": largest_error(kernel, args),
                    "digest": digest(kernel, args)}
            del args        # two of EvaByte's pools do not share the chip
            line["digest_edges"] = digest(
                kernel, case(name, seed=1, edges=True)[:-1])
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
