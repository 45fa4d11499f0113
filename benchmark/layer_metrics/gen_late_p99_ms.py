"""client: 99th percentile of how late the generator sent a request against
its due time, on the generator's own clock. A starved generator must not be
read as a fast server."""
from benchmark import metrics


def read(ctx):
    return metrics.gen_late_ms(ctx["records"], 99)
