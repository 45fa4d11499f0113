"""model step: share of the decode programs' device time under the scopes of
the family's ``kv_pool`` group — reads of the pool outside the attention
kernel, the token's write, any re-stack of the pool (the round trip of ROADMAP
S3) — in %."""
from benchmark import device_scopes


def read(ctx):
    return device_scopes.share(ctx, "kv_pool")
