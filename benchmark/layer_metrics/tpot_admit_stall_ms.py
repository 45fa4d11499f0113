"""engine: milliseconds of a running stream's gap, per token, that the
admissions of OTHER requests cost it. A stream gets tokens only at a decode
window's fan-out; the engine adds up, over the lanes each window delivered
to, the seconds since the lane's delivery before (``gap_lane_period_s``),
the window's steps (``gap_lane_steps``) and the tokens (``gap_tokens``), and
the same period and steps over the windows no admission touched
(``gap_clean_lane_*``: ``decode_period_ms`` is their step). The stall is
what the lanes of the OTHER windows — those an admission episode touched, or
that were dispatched inside one — waited beyond that step for each of their
own steps: the prefill programs, the chip idle under the admission and its
host work, and NOT the decode windows interleaved inside the episode, whose
steps are paid for at the clean step. So

    engine gap a token = decode_period_ms x steps a token + this

and a change that interleaves more lowers it with ``tpot``. Deltas over the
window; None on a program without the counters, or where no window
delivered a token or none was clean."""
from benchmark import manifest, readers


def per_token_ms(ctx, seconds_key):
    seconds = readers.counter_delta(ctx, seconds_key)
    tokens = readers.counter_delta(ctx, "gap_tokens")
    if seconds is None or not tokens:
        return None
    return seconds / tokens * 1e3


def read(ctx):
    step = manifest.layer_reader("decode_period_ms").clean_step_s(ctx)
    period, clean_period, steps, clean_steps, tokens = counts = [
        readers.counter_delta(ctx, f"gap_{k}")
        for k in ("lane_period_s", "clean_lane_period_s", "lane_steps",
                  "clean_lane_steps", "tokens")]
    if step is None or None in counts or not tokens:
        return None
    touched_s, touched_steps = period - clean_period, steps - clean_steps
    return (touched_s - touched_steps * step) / tokens * 1e3
