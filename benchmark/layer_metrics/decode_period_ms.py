"""engine: the decode step as the streams see it — the period between two
fan-outs over the window's steps, over the lanes of the windows no admission
touched (``gap_clean_lane_period_s`` / ``gap_clean_lane_steps``: the
admission clock stood still since the delivery before and since the
window's own dispatch). Weighed by lanes, as ``tpot`` is by tokens: the
small batches of an open loop's first and last seconds (the window opens on
an empty engine, and the snapshots close behind the drain) weigh what they
delivered, so the number is the step under the cell's load. On the host's
clock, untraced, host work included: beside the trace's ``decode_step_ms``,
which is a median over decode runs, not weighed by lanes. Deltas; None on a
program without the counters, or where no such window was counted."""
from benchmark import readers


def clean_step_s(ctx):
    seconds = readers.counter_delta(ctx, "gap_clean_lane_period_s")
    steps = readers.counter_delta(ctx, "gap_clean_lane_steps")
    if seconds is None or not steps:
        return None
    return seconds / steps


def read(ctx):
    step = clean_step_s(ctx)
    return None if step is None else step * 1e3
