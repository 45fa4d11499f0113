"""model step: share of the decode programs' device time under the scopes of
the family's ``ffn`` group (the decoder family's: the dense feed-forward or
the router, the experts and their combination) — the weight stream a decode
step exists to do — in %."""
from benchmark import device_scopes


def read(ctx):
    return device_scopes.share(ctx, "ffn")
