"""model step: passes over the layers that the device ran per token a decode
window produced — the engine's ``loop_passes`` (the sum of the count that
the pass loop carries on the device and returns beside each token) over
``loop_tokens``, as the delta over the window. A looped decoder that runs every pass for every token
reads its ``total_ut_steps``; a change that skips passes shows here before it
shows in ``correct``. A program that is not looped has neither counter, and
the metric is left out."""
from benchmark import readers


def read(ctx):
    passes = readers.counter_delta(ctx, "loop_passes")
    tokens = readers.counter_delta(ctx, "loop_tokens")
    if passes is None or not tokens:
        return None
    return passes / tokens
