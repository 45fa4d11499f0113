"""One decode step's latent attention alone, on the chip, at the two cells'
shapes: ``kimi`` (16 lanes x 64 heads, a 449-column table over 6 x 7,184
blocks, the lanes at 16–51 k rows: ``kimi-docs``) and ``ling`` (128 lanes x
32 heads, 33 columns over 1 x 4,097 blocks, the lanes at 1–4 k rows:
``ling-reason``). Microseconds a call of ``tpu9.ops.latent_attention.
paged_latent_attention`` — the kernel and whatever the checkout runs in
front of it — and the GB/s of the rows it attends (rows x (512 + 64) x 2
bytes), best of ``--repeats`` timings of ``--calls`` calls chained in one
program (a call's output, rounded, is the next call's query: no host
dispatch in the time); and the kernel's largest difference from the
``jax.numpy`` form on the ``ling`` shape.

    chiprun -- python3 scripts/latent_kernel_bench.py [--repo DIR --label L]

``--wave-pages N`` reads the kernel with another ``WAVE_PAGES`` (pages a
softmax update), one process a size. ``--repo`` takes ``tpu9`` from another checkout (a parent's ``git archive``):
the pool's rotated keys are laid as THAT checkout's ``kvstate.pool_shapes``
says, so a parent that keeps them a token a row and a change that keeps two
are measured by the same code on the same chip. It measures a TPU and exits
where there is none (the interpreted kernel: ``tests/test_hybrid_layers.py``).
No number of this script is a benchmark metric; ``PERF.md`` quotes them as
"one call alone".
"""

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

# lanes, heads, table columns, planes, blocks, (shortest, longest) lane
SHAPES = {"kimi": (16, 64, 449, 6, 7184, (16000, 51000)),
          "ling": (128, 32, 33, 1, 4097, (1000, 4100))}
LATENT, ROPE, BLOCK = 512, 64, 128


def case(kvstate, shape, seed: int = 0):
    """``(q_lat, q_rope, latents, rotated keys, table, lengths)``: the lanes'
    lengths spread evenly over the shape's range, their pages scattered."""
    import jax
    import jax.numpy as jnp
    lanes, heads, columns, planes, blocks, (low, high) = shape
    rng = np.random.default_rng(seed)
    lengths = np.linspace(low, high, lanes).astype(np.int32)
    rng.shuffle(lengths)
    pages = -(-lengths // BLOCK)
    assert pages.sum() < blocks and pages.max() <= columns
    table = np.zeros((lanes, columns), np.int32)
    order = rng.permutation(blocks - 1) + 1
    at = 0
    for lane, n in enumerate(pages):
        table[lane, :n] = order[at:at + n]
        at += n
    cfg = SimpleNamespace(kv_layers=planes, layer_group=1, dtype=jnp.bfloat16,
                          kv_row=((1, LATENT), (1, ROPE)))
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    # 512 blocks of random rows, repeated: 5.6 GB of latents drawn at once
    # would need as much again for the generator's bits
    pools = [jnp.tile(jnp.take(jax.random.normal(key, (1, 512) + shape[2:],
                                                 dt),
                               jnp.arange(blocks) % 512, axis=1),
                      (planes, 1, 1, 1, 1))
             for key, (shape, dt) in zip(
                 keys, kvstate.pool_shapes(cfg, blocks, BLOCK).values())]
    q_lat = jax.random.normal(keys[2], (lanes, heads, LATENT), jnp.bfloat16)
    q_rope = jax.random.normal(keys[3], (lanes, heads, ROPE), jnp.bfloat16)
    return (q_lat, q_rope, *pools, jnp.asarray(table), jnp.asarray(lengths))


def microseconds(form, args, planes, calls, repeats):
    """Best of ``repeats``: ``calls`` calls in one program, plane after
    plane, each call's output rounded into the next call's query."""
    import jax
    import jax.numpy as jnp
    q_lat, *rest = args

    @jax.jit
    def chain(q_lat, *rest):
        for i in range(calls):
            out = form(q_lat, *rest, i % planes, 192 ** -0.5)
            q_lat = (out * jax.lax.rsqrt(jnp.mean(out * out) + 1e-6)
                     ).astype(q_lat.dtype)
        return q_lat
    jax.block_until_ready(chain(q_lat, *rest))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q_lat, *rest))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--label", default="change")
    ap.add_argument("--shapes", default="kimi,ling")
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--wave-pages", type=int, default=0,
                    help="read the kernel with another WAVE_PAGES")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("latent_kernel_bench measures a TPU; there is none here")
    from tpu9.models import kvstate
    from tpu9.ops import latent_attention as ops
    if args.wave_pages:
        ops.WAVE_PAGES = args.wave_pages
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        operands = case(kvstate, shape)
        rows = int(np.asarray(operands[-1]).sum())
        us = microseconds(ops.paged_latent_attention, operands, shape[3],
                          args.calls, args.repeats)
        line = {"label": args.label, "shape": name, "rows": rows,
                "wave_pages": ops.WAVE_PAGES,
                "us_a_call": round(us, 1),
                "gb_s_of_rows": round(rows * (LATENT + ROPE) * 2 / us / 1e3,
                                      1)}
        if name == "ling":
            got = ops.paged_latent_attention(*operands, 0, 192 ** -0.5)
            want = ops.paged_latent_attention_xla(*operands, 0, 192 ** -0.5)
            line["largest_difference"] = float(np.abs(
                np.asarray(got) - np.asarray(want)).max())
            line["largest_output"] = float(np.abs(np.asarray(want)).max())
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
