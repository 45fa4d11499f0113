"""client: mean time to first token on the generator's clock, over every
record of the window that got a first token (judged or not: the summaries
hold all of them), less the gateway's own leg of it (``pre`` + ``connect`` +
``first``: entry -> first token written): how late the generator sent, its
send and its stamp, and the transport on both sides of the gateway. None
where the gateway's summaries are not of the records' requests."""
from benchmark import manifest, readers


def ttfts_ms(ctx):
    """Due -> first token of every record of the window that got one."""
    return [(r["token_s"][0] - r["due_s"]) * 1e3 for r in ctx["records"]
            if r["due_s"] is not None and r["token_s"]]


def read(ctx):
    gw = manifest.layer_reader("gateway_pre_forward_ms")
    firsts = ttfts_ms(ctx)
    parts = [readers.gateway_summary_mean_ms(ctx, name)
             for name in (gw.PRE, gw.CONNECT, gw.FIRST)]
    if None in parts or not gw.same_requests(
            ctx, len(firsts), *(gw.observations(ctx, name)
                                for name in (gw.PRE, gw.CONNECT, gw.FIRST))):
        return None
    return sum(firsts) / len(firsts) - sum(parts)
