"""From request records to the end-to-end metrics: the arithmetic every PR
is judged by, kept where no later PR can change it.

A record is what ``client.Client.send`` makes: ``due_s`` (when the request was
due, on the window's clock), ``token_s`` (arrival time of every streamed
token), ``judged``, ``ok``. Latency metrics are taken over the mix's JUDGED
requests; a judged request that failed stays in the percentile's base at
+infinity (it misses any limit) and is never dropped silently. Throughput is
over every request.

Percentiles: the median is ``statistics.median``; any other percentile is
nearest-rank, the copy of ``tpu9/benchsuite/model.py:latency_stats`` — never
an optimistic lower value for small n.
"""

from __future__ import annotations

import math
import re
import statistics

INF = float("inf")


def percentile(values: list, p: int) -> float:
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    if p == 50:
        return statistics.median(xs)
    return xs[max(0, -(-p * len(xs) // 100) - 1)]


def in_window(records: list) -> list:
    """Requests that were due inside the window (set-up traffic has no due
    time, a closed loop's ramp a negative one) and were not cut off by the
    end of a closed loop's window."""
    return [r for r in records if r["due_s"] is not None and r["due_s"] >= 0
            and not r.get("cut")]


def ttft_ms(rec: dict) -> float:
    if not rec["ok"]:
        return INF
    return (rec["token_s"][0] - rec["due_s"]) * 1e3


def tpot_ms(rec: dict):
    """(last token - first token) / (tokens - 1); None for a one-token
    answer, which has no gap."""
    if not rec["ok"]:
        return INF
    n = len(rec["token_s"])
    if n < 2:
        return None
    return (rec["token_s"][-1] - rec["token_s"][0]) / (n - 1) * 1e3


def latency(records: list, quantity: str, p: int) -> dict:
    """``{"value", "n", "failed"}`` of one latency percentile over the judged
    requests of the window."""
    fn = {"ttft": ttft_ms, "tpot": tpot_ms}[quantity]
    judged = [r for r in in_window(records) if r["judged"]]
    samples = [x for x in map(fn, judged) if x is not None]
    if not samples:
        return {"value": None, "n": 0, "failed": 0}
    return {"value": percentile(samples, p), "n": len(samples),
            "failed": sum(1 for x in samples if x == INF)}


def out_tok_s(records: list, seconds: float) -> float:
    """Output tokens streamed inside the window, of ALL requests (cut ones
    too: their tokens were served), per second of window."""
    n = sum(1 for r in records if r["due_s"] is not None
            for t in r["token_s"] if 0.0 <= t <= seconds)
    return n / seconds


_LATENCY = re.compile(r"^(ttft|tpot)_p(\d+)_ms$")


def end_to_end(name: str, records: list, seconds: float, setup_s: float):
    """The value of one end-to-end metric by its name, or None if the window
    holds nothing to take it from. Names: ``ttft_p<NN>_ms``, ``tpot_p<NN>_ms``,
    ``out_tok_s``, ``setup_s``."""
    if name == "setup_s":
        return setup_s
    if name == "out_tok_s":
        return out_tok_s(records, seconds)
    m = _LATENCY.match(name)
    if not m:
        raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
    return latency(records, m.group(1), int(m.group(2)))["value"]


def counts(records: list) -> dict:
    window = in_window(records)
    return {"attempted": len(window),
            "failed": sum(1 for r in window if not r["ok"]),
            "judged": sum(1 for r in window if r["judged"]),
            "cut": sum(1 for r in records if r.get("cut")),
            "prompt_tokens": sum(r["prompt_len"] for r in window),
            "output_tokens": sum(len(r["token_s"]) for r in window)}


def gen_late_ms(records: list, p: int = 99):
    """How late the generator sent, against each request's due time."""
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in in_window(records)]
    return percentile(late, p) if late else None


def _decoding(records: list, seconds: float):
    """(context tokens, seconds of decoding inside the window) of every
    request that decoded in it: prompt + half the answer, between its first
    and last token."""
    for r in records:
        if r["due_s"] is None or len(r["token_s"]) < 2:
            continue
        a, b = max(r["token_s"][0], 0.0), min(r["token_s"][-1], seconds)
        if b > a:
            yield r["prompt_len"] + len(r["token_s"]) / 2, b - a


def mean_resident_context(records: list, seconds: float) -> float:
    """Time-average over the window of the context tokens held by requests
    that are decoding: sum over requests of (prompt + half the answer) x the
    time between its first and last token, over the window's length."""
    total = 0.0
    for context, duration in _decoding(records, seconds):
        total += context * duration
    return total / seconds


def mean_decoding_context(records: list, seconds: float):
    """Mean context of ONE request while it decodes: the same sum over the
    time the requests spent decoding instead of the window's length. Times
    the engine's mean decode batch it is the context resident at a decode
    step, which ``mean_resident_context`` understates where the device
    spends part of the window not decoding (a closed loop admits in waves)."""
    pairs = list(_decoding(records, seconds))
    if not pairs:
        return None
    return sum(c * d for c, d in pairs) / sum(d for _, d in pairs)


def finite(x) -> bool:
    return x is not None and isinstance(x, (int, float)) and math.isfinite(x)
