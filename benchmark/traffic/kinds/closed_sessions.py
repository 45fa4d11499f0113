"""Closed loop of growing conversations: as ``closed_callers``, but each
caller is one session over its own long document.

Parameters: ``sessions``, ``context_tokens`` (the document, prefilled during
set-up by one request that asks for a single token), ``turn_tokens`` (new
user tokens per turn), ``output_tokens``, ``max_turns``, ``stagger_s`` (session
i sends its first turn i x ``stagger_s`` into the window: callers that start
together and take turns of equal length would arrive together for ever, and the
cell would measure that convoy). Each turn sends the
whole history — document, every earlier turn and every earlier answer — plus
the new tokens, and the next goes out when the answer has ended. The lengths
are distributions like any other; the seed draws the token ids and deals the
quantile midpoints to the sessions.
"""

from __future__ import annotations

import asyncio

import numpy as np

from .. import dist


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
    rng = np.random.default_rng(seed)
    n, turns = int(params["sessions"]), int(params["max_turns"])
    (cls,) = params["classes"]
    docs = dist.quantiles(cls["context_tokens"], n)
    docs = [docs[i] for i in rng.permutation(n)]
    new = dist.quantiles(cls["turn_tokens"], n * turns)
    out = dist.quantiles(cls["output_tokens"], n * turns)
    # turn t of the sessions is one stretch: a stratified sample of lengths
    rows = dist.run_order(rng, dist.stratify(rng, n * turns, 2, turns))
    sessions = []
    for s in range(n):
        sessions.append({
            "class": cls["name"], "judged": bool(cls["judged"]),
            "document": dist.token_ids(rng, docs[s], vocab),
            "turns": [{"new": dist.token_ids(rng, new[i], vocab),
                       "max_new_tokens": out[j]}
                      for i, j in rows[s::n]]})
    return {"sessions": sessions, "drain": False,
            "stagger_s": float(params.get("stagger_s", 0.0))}


async def prepare(plan: dict, send) -> None:
    """Build every session's context: one request per document, one token
    out, so that the prefix cache holds the document when the window opens.
    One at a time: set-up has to repeat, and eight 8k prefills in one batch
    would not."""
    for s in plan["sessions"]:
        rec = await send({"class": s["class"], "judged": False,
                          "prompt": s["document"], "max_new_tokens": 1,
                          "prompt_len": len(s["document"])}, None)
        if not rec.get("ok"):
            raise RuntimeError(f"session context failed: {rec.get('error')}")
        s["history"] = s["document"] + rec["tokens"]


async def drive(plan: dict, send, clock, seconds: float) -> None:
    async def session(i, s):
        await asyncio.sleep(max(i * plan.get("stagger_s", 0.0) - clock(), 0))
        history = s.get("history") or list(s["document"])
        for turn in s["turns"]:
            if clock() >= seconds:
                return
            prompt = history + turn["new"]
            rec = await send({"class": s["class"], "judged": s["judged"],
                              "prompt": prompt, "prompt_len": len(prompt),
                              "max_new_tokens": turn["max_new_tokens"]},
                             clock())
            if not rec.get("ok"):
                return
            history = prompt + rec["tokens"]

    await asyncio.gather(*(session(i, s)
                           for i, s in enumerate(plan["sessions"])))
