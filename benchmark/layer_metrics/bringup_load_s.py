"""worker, runner: seconds to make the weights on the device from the seed
(``coldstart_load_s`` on the runner's /health; the span is set around the call
in ``benchmark/serve.py``)."""
from benchmark import readers


def read(ctx):
    return readers.coldstart(ctx, "load_s")
