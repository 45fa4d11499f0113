"""Closed loop: C callers, each sends its next request when the last one
has ended. Above the knee by construction when C exceeds the engine's slots.

Parameters: ``callers``, ``requests_per_caller`` (more than a caller can
finish in the window; what is left over is never sent), ``ramp_s`` (the
callers start this long before the window opens, so that the window sees the
steady state and not 32 prefills at once; part of set-up), and one class with
``prompt_tokens`` and ``output_tokens``. The C x R lengths are the quantile
midpoints; the seed deals them to the callers and draws the token ids.
"""

from __future__ import annotations

import asyncio

import numpy as np

from .. import dist


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
    rng = np.random.default_rng(seed)
    callers, per = int(params["callers"]), int(params["requests_per_caller"])
    n = callers * per
    (cls,) = params["classes"]
    prompts = dist.quantiles(cls["prompt_tokens"], n)
    outputs = dist.quantiles(cls["output_tokens"], n)
    # round r of the callers is one stretch: a stratified sample of lengths
    rows = dist.run_order(rng, dist.stratify(rng, n, 2, per))
    queues = [[] for _ in range(callers)]
    for slot, (i, j) in enumerate(rows):
        queues[slot % callers].append({
            "class": cls["name"], "judged": bool(cls["judged"]),
            "prompt_len": prompts[i], "max_new_tokens": outputs[j],
            "prompt": dist.token_ids(rng, prompts[i], vocab)})
    return {"callers": queues, "ramp_s": float(params.get("ramp_s", 0.0)),
            "drain": False}


async def prepare(plan: dict, send) -> None:
    """The callers are started by ``drive``; ``ramp_s`` is spent there,
    before the window's clock reaches 0."""


async def drive(plan: dict, send, clock, seconds: float) -> None:
    async def caller(queue):
        for spec in queue:
            if clock() >= seconds:
                return
            await send(spec, clock())

    await asyncio.gather(*(caller(q) for q in plan["callers"]))
