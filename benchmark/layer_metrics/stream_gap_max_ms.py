"""engine: the largest interval between two deliveries of one stream
(``latency.gap_max``, the summary ``tpu9_engine_gap_max_s``), as the mean
over the requests that retired in the window: the hitch a reader of the
stream sees, which the mean gap hides. None on a program that tells no such
summary."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "gap_max")
