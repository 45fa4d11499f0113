"""engine: mean time of one admission — reserve, prefix lookup, chunk
dispatches, first-token sampling; ``engine.stats()`` ``prefill`` — over the
requests of the window. With ``engine_queue_wait_ms`` and
``first_token_hold_ms`` it adds up to the engine's own mean TTFT."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "prefill")
