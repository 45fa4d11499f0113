"""kernels: share of the chip's HBM bandwidth that the bytes a decode step
NEEDS of the held experts' kernel (the family's ``kernel_cost`` of its
``EXPERT_STEP_KERNEL``: the two matrices of every TOUCHED expert once an
expert layer — touched as the program counted them, ``moe_held_touched`` over
``moe_step_layers`` in the window — and the live rows in and their sum out, in
the experts' latent) would take in the device time the kernel's calls of a
step TOOK (the operations the trace prints under that name inside the decode
programs' runs, over the steps counted). The ungated ``held_ffn``'s share of
its roofline: what the step needs, not what the kernel moves. Left out where
the family names no such kernel, the program runs none or keeps no such
counters, or the trace holds no counted step."""
from benchmark import peaks, readers


def read(ctx):
    family = ctx["family"]
    kernel = getattr(family, "EXPERT_STEP_KERNEL", None)
    took = readers.kernel_seconds_per_step(ctx, kernel) if kernel else None
    batch = readers.decode_batch(ctx)
    touched = readers.counter_delta(ctx, "moe_held_touched")
    step_layers = readers.counter_delta(ctx, "moe_step_layers")
    if not took or not batch or not touched or not step_layers:
        return None
    cost = family.kernel_cost(kernel, ctx["model"], ctx["engine"], batch, 0,
                              touched=touched / step_layers)
    if not cost:
        return None
    peak = peaks.chip_peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * cost["bytes"] / ctx["chips"] / took / peak
