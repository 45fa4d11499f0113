"""worker, runner: seconds from the handler's start until ``jax.devices()``
has answered in the runner container (``coldstart_device_open_s``)."""
from benchmark import readers


def read(ctx):
    return readers.coldstart(ctx, "device_open_s")
