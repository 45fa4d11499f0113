"""model step: device time of the chunked-prefill programs of a listed
pattern (chunk, fused chunk group, splice, gather: the mixers' projections
and the chunkwise SSD scan of every state-space layer, the state carried
chunk to chunk) per 1,000 prompt tokens, counted as the program counts
them: whole chunks. ``prefill_ms_per_ktok``'s arithmetic, for the family
whose prefill runs the scan: that metric's list is pinned by an accepted
test and does not name this cell. A family without a state-space scope, and
a trace without a prefill program: left out."""
from benchmark import readers


def read(ctx):
    if not getattr(ctx["family"], "SSM_STATE_SCOPE", None):
        return None
    seconds, tokens = readers.prefill_time_and_tokens(ctx)
    if not tokens:
        return None
    return seconds * 1e3 / (tokens / 1e3)
