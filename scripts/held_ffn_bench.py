"""One decode step's expert layer alone, on the chip: by default at
Ling-3.0-flash's widths (ISSUE 49), 128 rows against 128 held experts of
2560 x 768, of which the rows' picks touch a share; ``--sizes 32,8,4096,14336
--shares 0.625,0.75,0.875,1`` is Mixtral's step at 5 to 8 of 8 touched
(ISSUE 50). Microseconds a call and the GB/s of the TOUCHED experts' weights
(touched x 3 x d x hidden x 2 bytes), for the kernel
(``tpu9.ops.held_ffn.held_ffn_kernel``; ``hidden_tile`` says how it cut
``hidden``), for the einsum form (``held_ffn_xla``, which reads every held
expert whatever is touched) and, once a size, for the one-hot form of a
layer that holds every expert (``moe_ffn`` at ``capacity_factor = E / k``, a
seeded router: its time does not depend on the picks), best of ``--repeats``
timings of ``--calls`` calls chained in one program (a call's output,
rounded, is the next call's rows, so no host dispatch is in the time); and
the largest difference of the kernel's and the einsums' outputs.

    chiprun -- python3 scripts/held_ffn_bench.py [--repo DIR] [--shares ...]

``--repo`` takes ``tpu9`` from another checkout (a parent's ``git archive``)
so both sides are measured by the same code on the same chip; PR 48's
``moe_ffn_held`` is ``held_ffn_xla`` here, arithmetic and all. It measures a
TPU and exits where there is none (the interpreted kernel:
``tests/test_held_ffn.py``). No number of this script is a benchmark metric;
``PERF.md`` §6 quotes them as "one call alone".
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# rows, held experts, model width, expert width: Ling-3.0-flash, a chip's share
SIZES = (128, 128, 2560, 768)
# picks of a row that fall on this chip's share (8 x 128 / 512)
K = 2


def case(ops, sizes, k: int, share: float, seed: int = 0):
    """(x, weight, ids, count, w_gate, w_up, w_down, touched): every row
    picks ``k`` of a ``share`` of the experts, drawn at random, and every one
    of those is picked, so that exactly ``round(share x E)`` are touched."""
    import jax
    import jax.numpy as jnp
    n, e, d, h = sizes
    rng = np.random.default_rng(seed)
    touched = max(1, round(share * e))
    pool = rng.permutation(e)[:touched]
    local = np.stack([rng.choice(pool, min(k, touched), replace=False)
                      for _ in range(n)])
    local[:touched, 0] = pool[:n]
    weight = np.zeros((n, e), np.float32)
    np.put_along_axis(weight, local,
                      rng.random(local.shape).astype(np.float32), axis=1)
    ids, count = ops.touched_experts(jnp.asarray(local), jnp.ones(n, bool), e)
    assert int(count[0]) == touched
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def normal(key, shape, fan):
        return jax.random.normal(key, shape, jnp.bfloat16) * fan ** -0.5
    return (normal(keys[0], (n, d), 1.0), jnp.asarray(weight), ids, count,
            normal(keys[1], (e, d, h), d), normal(keys[2], (e, d, h), d),
            normal(keys[3], (e, h, d), h), touched)


def microseconds(form, args, calls, repeats):
    """Best of ``repeats``: ``calls`` calls in one program, each call's
    output (scaled back to the rows' size) the next call's rows."""
    import jax
    import jax.numpy as jnp
    x, *rest = args

    @jax.jit
    def chain(x, *rest):
        def one(_, x):
            y = form(x, *rest)
            return (y * jax.lax.rsqrt(jnp.mean(y * y) + 1e-6)).astype(x.dtype)
        return jax.lax.fori_loop(0, calls, one, x)

    jax.block_until_ready(chain(x, *rest))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(x, *rest))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def one_hot_layer(sizes, k: int, seed: int = 0):
    """``moe_ffn`` over a layer that holds every expert, dropless, and the
    rest of its arguments: (form, (x [1, N, d], params))."""
    import jax
    import jax.numpy as jnp
    from tpu9.models.moe import MoeConfig, init_moe_layer, moe_ffn
    n, e, d, h = sizes
    cfg = MoeConfig(dim=d, hidden_dim=h, n_experts=e, top_k=k,
                    capacity_factor=e / k)
    params = init_moe_layer(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, n, d),
                          jnp.bfloat16)
    return (lambda x, params: moe_ffn(params, x, cfg, ep_sharded=False)[0],
            (x, params))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=None)
    ap.add_argument("--sizes", default=",".join(map(str, SIZES)),
                    help="rows,experts,d,hidden")
    ap.add_argument("--top-k", type=int, default=K)
    ap.add_argument("--shares", default="0.25,0.55,0.77,1.0")
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--label", default="tree")
    a = ap.parse_args()
    sizes = tuple(int(s) for s in a.sizes.split(","))
    root = os.path.abspath(a.repo or os.path.join(os.path.dirname(__file__),
                                                  ".."))
    sys.path.insert(0, root)
    import jax
    from tpu9.ops import held_ffn as ops
    if not os.path.abspath(ops.__file__).startswith(root):
        sys.exit(f"tpu9 came from {ops.__file__}, not from {root}")
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"no TPU to measure: jax runs on {device.platform}")
    forms = {"kernel": ops.held_ffn_kernel, "einsums": ops.held_ffn_xla}
    common = {"label": a.label, "device": device.device_kind,
              "sizes": list(sizes), "top_k": a.top_k,
              "hidden_tile": ops._step_tile(sizes[2], sizes[3], 2)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/held_ffn_bench.jsonl", "a") as out:
        def report(line):
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        for share in (float(s) for s in a.shares.split(",")):
            *args, touched = case(ops, sizes, a.top_k, share)
            need = touched * 3 * sizes[2] * sizes[3] * 2
            outputs = {}
            for name, form in forms.items():
                us = microseconds(form, args, a.calls, a.repeats)
                outputs[name] = np.asarray(form(*args))
                line = dict(common, form=name, touched=touched,
                            touched_share=touched / sizes[1], us_a_call=us,
                            touched_bytes=need, gb_s=need / us / 1e3)
                if len(outputs) == 2:
                    first, second = outputs.values()
                    line["largest_difference"] = float(
                        np.abs(first - second).max())
                    line["largest_output"] = float(np.abs(second).max())
                report(line)
        form, args = one_hot_layer(sizes, a.top_k)
        need = sizes[1] * 3 * sizes[2] * sizes[3] * 2
        us = microseconds(form, args, a.calls, a.repeats)
        report(dict(common, form="moe_ffn", touched=sizes[1],
                    touched_share=1.0, us_a_call=us, touched_bytes=need,
                    gb_s=need / us / 1e3))


if __name__ == "__main__":
    main()
