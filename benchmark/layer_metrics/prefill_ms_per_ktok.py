"""model step: device time of the chunked-prefill programs (chunk, fused
chunk group, splice, gather) per 1,000 prompt tokens, counted as the program
counts them: whole chunks."""
from benchmark import readers


def read(ctx):
    seconds, tokens = readers.prefill_time_and_tokens(ctx)
    if not tokens:
        return None
    return seconds * 1e3 / (tokens / 1e3)
