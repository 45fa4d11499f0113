"""worker, runner: mean time of the runner's handler from its first line to
the response's headers written (``latency.ingest``, span ``runner.ingest``):
the body read, ``json.loads``, an ``int()`` a prompt token, the enqueue,
``prepare`` — over the streamed requests of the window."""
from benchmark import readers


def read(ctx):
    return readers.engine_phase_mean_ms(ctx, "ingest")
