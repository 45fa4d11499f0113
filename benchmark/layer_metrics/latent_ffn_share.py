"""model step: share of the decode programs' device time spent under the
scopes of an expert layer whose routed experts work in a latent (the
family's ``MOE_SCOPES``: the router, the projection into the latent, the held
experts, the projection out, the shared expert, the combine), in %. A family
without such a layer names none, and a program that runs nothing under the
latent's scopes has no such layer: the metric is then left out."""
from benchmark import device_scopes


def read(ctx):
    scopes = getattr(ctx["family"], "MOE_SCOPES", None)
    seconds = device_scopes.decode_seconds(ctx)
    total = sum(seconds.values())
    if not scopes or not total or "moe.latent.in" not in seconds:
        return None
    return 100.0 * sum(seconds.get(s, 0.0) for s in scopes) / total
