"""kernels: share of the decode programs' device time under the scopes of the
family's ``attention`` group (the decoder family's: the paged attention
kernel), in %."""
from benchmark import device_scopes


def read(ctx):
    return device_scopes.share(ctx, "attention")
