"""model step: share of the decode programs' device time under ``ffn`` or the
``moe.*`` scopes — the weight stream a decode step exists to do — in %."""
from benchmark import device_scopes


def read(ctx):
    return device_scopes.share(ctx, device_scopes.FFN)
