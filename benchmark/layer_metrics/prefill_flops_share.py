"""kernels: share of peak bf16 FLOP/s that the matmul FLOPs the prefilled
tokens NEED (the family's ``prefill_flops_per_token``; attention scores not
counted, so a lower bound) reach in the time the chunk programs TOOK, over
all chips."""
from benchmark import peaks, readers


def read(ctx):
    seconds, tokens = readers.prefill_time_and_tokens(ctx)
    if not tokens or not seconds:
        return None
    peak = peaks.chip_peaks(ctx["device"]["kind"])["bf16_tflops"] * 1e12
    need = ctx["family"].prefill_flops_per_token(ctx["model"]) * tokens
    return 100.0 * need / seconds / (peak * ctx["chips"])
