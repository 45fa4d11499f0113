"""KV pool: prefix-cache hits / (hits + misses), as the delta over the
window, in %."""
from benchmark import readers


def read(ctx):
    hits = readers.nested_delta(ctx, "prefix_cache", "hits")
    misses = readers.nested_delta(ctx, "prefix_cache", "misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
