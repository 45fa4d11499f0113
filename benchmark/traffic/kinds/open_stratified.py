"""Open loop: arrivals on a schedule, never waiting for replies.

Parameters: ``rate_rps`` (offered requests per second), ``classes`` (each with
``share``, ``judged``, ``prompt_tokens``, ``output_tokens``), ``time_blocks``
(see ``dist.stratify``). N = round(rate x seconds). The N - 1 gaps are the
quantile midpoints of an exponential with that rate, scaled so that the last
arrival falls at (N - 1) / rate; each class's lengths are the quantile
midpoints of its distributions. The seed orders them and draws the token ids.

``arrangement_seed`` (optional) fixes the stratified order of lengths and gaps
for the mix: the arrival times are then the same for every seed, and the run's
seed only ROTATES the order of the requests (request i of the run is request
(i + seed) mod N of the arrangement) and draws the token ids. Two runs of one seed of a mix with long and short requests in one
queue agreed to about 1 % in the median gap between tokens, runs of different
freely ordered seeds only to 5-8 %: which short requests meet a long prefill
is decided by the order. A rotation keeps who meets whom.
"""

from __future__ import annotations

import asyncio

import numpy as np

from .. import dist


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
    rng = np.random.default_rng(seed)          # token ids, and the order if free
    fixed = params.get("arrangement_seed")
    order_rng = rng if fixed is None else np.random.default_rng(int(fixed))
    rate = float(params["rate_rps"])
    n = max(int(round(rate * seconds)), 1)
    blocks = int(params.get("time_blocks", 1))

    stretches = [[] for _ in range(max(1, min(blocks, n)))]
    counts = dist.class_counts(params["classes"], n)
    for cls, count in zip(params["classes"], counts):
        if not count:
            continue
        prompts = dist.quantiles(cls["prompt_tokens"], count)
        outputs = dist.quantiles(cls["output_tokens"], count)
        for b, rows in enumerate(dist.stratify(order_rng, count, 2,
                                               len(stretches))):
            stretches[b] += [{"class": cls["name"], "judged": bool(cls["judged"]),
                              "prompt_len": prompts[i],
                              "max_new_tokens": outputs[j]} for i, j in rows]
    specs = dist.run_order(order_rng, stretches)

    if fixed is not None:
        shift = seed % n
        specs = specs[shift:] + specs[:shift]
    due = np.zeros(n)
    if n > 1:
        gaps = dist.quantiles({"dist": "exponential", "rate": rate}, n - 1)
        order = dist.run_order(order_rng,
                               dist.stratify(order_rng, n - 1, 1, blocks))
        due[1:] = np.cumsum([gaps[i] for (i,) in order])
        # the midpoints' mean gap is a little under 1/rate: pin the span, so
        # that every seed's schedule covers the same stretch of the window
        due *= ((n - 1) / rate) / due[-1]
    return {"requests": [dict(s, due_s=float(t),
                              prompt=dist.token_ids(rng, s["prompt_len"], vocab))
                         for s, t in zip(specs, due)],
            "drain": True}


async def prepare(plan: dict, send) -> None:
    """Nothing to build before the window."""


async def drive(plan: dict, send, clock, seconds: float) -> None:
    """``send(spec, due_s)`` issues one request and records it; ``clock()``
    is seconds since the window opened."""
    async def one(spec):
        delay = spec["due_s"] - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        await send(spec, spec["due_s"])

    await asyncio.gather(*(one(s) for s in plan["requests"]))
