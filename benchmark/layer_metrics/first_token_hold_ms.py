"""engine: mean hold between the end of a request's admission and the delivery
of its first token (``first_hold``): the first tokens of a batch of
admissions are synced and delivered together, after the last."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "first_hold")
