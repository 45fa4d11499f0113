"""worker, runner: seconds in ``engine.precompile()`` — compiling every
serving program, or loading it from the persistent cache
(``coldstart_compile_ahead_s``)."""
from benchmark import readers


def read(ctx):
    return readers.coldstart(ctx, "compile_ahead_s")
