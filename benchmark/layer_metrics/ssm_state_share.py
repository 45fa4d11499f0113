"""model step: share of the decode programs' device time spent under the
state-space recurrence's scope (the family's ``SSM_STATE_SCOPE``: the state's
step in every state-space layer), in %. A family without such a scope names
none, and a program without it runs nothing under it: the metric is then
left out."""
from benchmark import device_scopes


def read(ctx):
    scope = getattr(ctx["family"], "SSM_STATE_SCOPE", None)
    seconds = device_scopes.decode_seconds(ctx)
    total = sum(seconds.values())
    if not scope or not total or scope not in seconds:
        return None
    return 100.0 * seconds[scope] / total
