"""model step: the rows a TOUCHED held expert served, a decode step and
expert layer: the engine's ``moe_local_picks`` (picks of the live lanes that
fell on experts this chip holds) over ``moe_held_touched`` (held experts at
least one live lane's pick reached), as the delta over the window. 64 lanes
with 22 picks of 512 put 2.75 rows on a held expert; with n of the 128 held
touched a step it reads 2.75 x 128 / n: how many rows share one read of an
expert's two matrices. An engine without the counters: left out."""
from benchmark import readers


def read(ctx):
    picks = readers.counter_delta(ctx, "moe_local_picks")
    touched = readers.counter_delta(ctx, "moe_held_touched")
    if picks is None or not touched:
        return None
    return picks / touched
