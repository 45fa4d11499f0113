"""kernels: share of the decode programs' device time under ``attn.core``, the
paged attention kernel, in %."""
from benchmark import device_scopes


def read(ctx):
    return device_scopes.share(ctx, device_scopes.ATTENTION)
