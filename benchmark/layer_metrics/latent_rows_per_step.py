"""model step: cache rows the live lanes attend in one decode step, every
lane's together (a layer reads each once): the engine's
``latent_rows_attended`` over ``latent_decode_steps``, a host mirror of the
lengths at each decode dispatch, as the delta over the window. Times the
family's bytes a row and layer it is what ``latent_attn_bw_share`` holds the
kernel's time to. An engine without the counters: left out."""
from benchmark import readers


def read(ctx):
    rows = readers.counter_delta(ctx, "latent_rows_attended")
    steps = readers.counter_delta(ctx, "latent_decode_steps")
    if rows is None or not steps:
        return None
    return rows / steps
