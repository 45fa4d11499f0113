"""kernels: share of the chip's HBM bandwidth that the bytes a decode step
NEEDS of latent attention (the family's ``kernel_cost`` of the decode kernel:
the latent row — 512 latent and 64 rotary numbers — of every cache row the
live lanes attend, once a layer) would take in the device time that the
operations under ``attn.mla.core`` TOOK a step inside the decode programs'
runs: the ``paged_latent_attention`` kernel, which reads the latents, AND the
gather of the rotary eighth of the rows and its scores in ``jax.numpy``
beside it, so that the time is that of everything the bytes pass through. The
rows are the engine's own count (``latent_rows_attended`` over
``latent_decode_steps``, a host mirror of the lengths at each decode
dispatch), as the delta over the window. What the step needs, not what the
kernel moves: whole pages and a padded table are its own affair. Left out
where the engine has no such counters or the trace no counted step."""
from benchmark import device_scopes, peaks, readers

SCOPE = "attn.mla.core"


def read(ctx):
    rows = readers.counter_delta(ctx, "latent_rows_attended")
    steps = readers.counter_delta(ctx, "latent_decode_steps")
    traced = (((ctx["trace"] or {}).get("programs") or {})
              .get("jit_decode") or {}).get("steps")
    took = device_scopes.decode_seconds(ctx).get(SCOPE)
    if not rows or not steps or not traced or not took:
        return None
    family = ctx["family"]
    cost = family.kernel_cost(family.STEP_MARKER, ctx["model"], ctx["engine"],
                              readers.decode_batch(ctx) or 0, rows / steps)
    if not cost:
        return None
    peak = peaks.chip_peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * cost["bytes"] / ctx["chips"] / (took / traced) / peak
