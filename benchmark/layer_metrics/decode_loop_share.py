"""model step: share of the decode programs' device time under the scopes
that a looped decoder's pass loop adds beside the layers (the family's
``LOOP_SCOPES``: the norm that closes every pass, the exit gate, the
selection), in %. A family without a pass loop names no such scopes, and a
program without them runs nothing under them: the metric is then left out."""
from benchmark import device_scopes


def read(ctx):
    scopes = getattr(ctx["family"], "LOOP_SCOPES", ())
    seconds = device_scopes.decode_seconds(ctx)
    total = sum(seconds.values())
    if not scopes or not total:
        return None
    return 100.0 * sum(seconds.get(s, 0.0) for s in scopes) / total
