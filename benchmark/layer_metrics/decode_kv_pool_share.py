"""model step: share of the decode programs' device time under the ``kv.*``
scopes — the per-layer read of the pool, the token's write and the re-stack
of the pool (the round trip of ROADMAP S3) — in %."""
from benchmark import device_scopes


def read(ctx):
    return device_scopes.share(ctx, device_scopes.KV_POOL)
