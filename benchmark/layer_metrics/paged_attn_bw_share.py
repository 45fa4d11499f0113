"""kernels: share of the chip's HBM bandwidth that the bytes a decode step
NEEDS of the paged attention kernel (the family's ``kernel_cost``: the keys
and values of the context resident at the step, once a layer; per chip) would
take in the device time the kernel's calls of a step TOOK (the operations the
trace prints as ``paged_decode_attention`` inside the decode programs' runs,
over the steps counted). What the step needs, not what the kernel moves, as in
``decode_bw_share``. The resident context is the engine's mean decode batch
times the mean context of a decoding request (``metrics``): the kernel runs
only in decode steps, so the window's time-average would count too little
wherever the device spends part of the window not decoding. The pattern of a
kernel's share, written against the family and no configuration."""
from benchmark import metrics, peaks, readers

KERNEL = "paged_decode_attention"


def read(ctx):
    took = readers.kernel_seconds_per_step(ctx, KERNEL)
    batch = readers.decode_batch(ctx)
    each = metrics.mean_decoding_context(ctx["records"], ctx["seconds"])
    if not took or not batch or not each:
        return None
    cost = ctx["family"].kernel_cost(KERNEL, ctx["model"], ctx["engine"],
                                     batch, batch * each)
    if not cost:
        return None
    peak = peaks.chip_peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * cost["bytes"] / ctx["chips"] / took / peak
