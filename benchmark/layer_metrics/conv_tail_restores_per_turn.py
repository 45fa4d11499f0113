"""engine: admissions of the window that started behind a prefix hit with the
conv layers' tails restored from the hit's last page, over the admissions
begun (``conv_tail_restores`` over ``gap_admissions``, the engine's counters,
as the delta over the window). 1.0 where every turn was served behind its
history; less where a session's pages were let go and a turn prefilled its
whole context again. An engine without the counter: left out."""
from benchmark import readers


def read(ctx):
    restores = readers.counter_delta(ctx, "conv_tail_restores")
    admissions = readers.counter_delta(ctx, "gap_admissions")
    if restores is None or not admissions:
        return None
    return restores / admissions
