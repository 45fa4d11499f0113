"""model step: share of the decode programs' device time spent under the
gated short convolutions' scopes (the family's ``CONV_SCOPES``: the two
projections and the gates and taps of every conv layer), in %. Three layers
of four are such mixers, and each is two matrix products around three
multiplies and two adds a channel: what the step pays for them beside the
expert stream. A family without such scopes names none, and a program
without them runs nothing under them: the metric is then left out."""
from benchmark import device_scopes


def read(ctx):
    scopes = getattr(ctx["family"], "CONV_SCOPES", ())
    seconds = device_scopes.decode_seconds(ctx)
    total = sum(seconds.values())
    if not total or not any(s in seconds for s in scopes):
        return None
    return 100.0 * sum(seconds.get(s, 0.0) for s in scopes) / total
