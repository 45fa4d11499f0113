"""model step: share of the traced device busy time that went to summarising
closed windows (the operations under the family's ``EVA_SCOPES``, in the
decode, chunk and group programs alike), in %. 0 where the programs hold the
operation and no window closed inside the trace. A family without such
scopes names none, and a program without them runs nothing under them: the
metric is then left out."""
from benchmark import scope_events


def read(ctx):
    got = scope_events.read(ctx, getattr(ctx["family"], "EVA_SCOPES", ()))
    busy = (ctx["trace"] or {}).get("busy_s")
    if not got or not busy:
        return None
    return 100.0 * got["seconds"] / busy
