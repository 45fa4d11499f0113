"""kernels: share of the chip's HBM bandwidth that the bytes the state-space
recurrence NEEDS in a decode step (the family's ``kernel_cost`` of the
state's step: every LIVE lane's float32 state read once and written once in
every state-space layer; the mean decode batch is the live lanes) would take
in the device time that the step kernel's calls of a step TOOK (the
operations the trace prints under the family's ``SSM_STEP_KERNEL`` inside the
decode programs' runs, over the steps counted). The kernel's share of its
roofline: what the step needs, not what the kernel moves. Left out where the
family names no such kernel, the program runs none, or the trace holds no
counted step."""
from benchmark import peaks, readers


def read(ctx):
    family = ctx["family"]
    kernel = getattr(family, "SSM_STEP_KERNEL", None)
    took = readers.kernel_seconds_per_step(ctx, kernel) if kernel else None
    batch = readers.decode_batch(ctx)
    if not took or not batch:
        return None
    cost = family.kernel_cost(kernel, ctx["model"], ctx["engine"], batch, 0)
    if not cost:
        return None
    peak = peaks.chip_peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * cost["bytes"] / ctx["chips"] / took / peak
