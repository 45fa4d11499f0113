"""The arithmetic from request records to end-to-end metrics."""

import statistics

import pytest

from benchmark import metrics


def rec(due=0.0, first=0.1, gap=0.02, n=5, judged=True, ok=True, cut=False,
        prompt_len=100, sent=None):
    times = [due + first + i * gap for i in range(n)] if n else []
    out = {"class": "c", "judged": judged, "prompt_len": prompt_len,
           "want_tokens": n, "due_s": due, "sent_s": due if sent is None else sent,
           "token_s": times, "tokens": [1] * n, "ok": ok, "error": "" if ok else "x"}
    if cut:
        out["cut"] = True
    return out


@pytest.mark.parametrize("n,p,rank", [(10, 90, 9), (10, 95, 10), (100, 90, 90),
                                      (130, 90, 117), (3, 99, 3), (1, 90, 1),
                                      (20, 95, 19)])
def test_tail_percentiles_are_nearest_rank(n, p, rank):
    values = [float(i) for i in range(1, n + 1)]
    assert metrics.percentile(values[::-1], p) == float(rank)


@pytest.mark.parametrize("values", [[3.0, 1.0, 2.0], [4.0, 1.0, 2.0, 3.0], [5.0]])
def test_the_median_is_the_statistics_median(values):
    assert metrics.percentile(values, 50) == statistics.median(values)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        metrics.percentile([], 50)


def test_latencies_are_taken_over_the_judged_class_only():
    records = [rec(first=0.1, judged=True) for _ in range(5)] + \
              [rec(first=9.0, judged=False) for _ in range(5)]
    got = metrics.latency(records, "ttft", 50)
    assert got["n"] == 5 and got["value"] == pytest.approx(100.0)
    assert metrics.end_to_end("ttft_p90_ms", records, 10, 1.0) == \
        pytest.approx(100.0)


def test_ttft_is_timed_from_when_the_request_was_due():
    late = rec(due=1.0, first=0.3, sent=1.2)     # sent 200 ms late
    assert metrics.ttft_ms(late) == pytest.approx(300.0)
    assert metrics.gen_late_ms([late]) == pytest.approx(200.0)


def test_tpot_is_last_minus_first_over_tokens_minus_one():
    assert metrics.tpot_ms(rec(gap=0.03, n=11)) == pytest.approx(30.0)
    assert metrics.tpot_ms(rec(n=1)) is None


@pytest.mark.parametrize("failed,p,inf", [(1, 50, False), (1, 90, False), (2, 90, True),
                                          (6, 50, True), (0, 90, False)])
def test_a_failed_request_stays_in_the_base_at_infinity(failed, p, inf):
    records = [rec(ok=False, n=0) for _ in range(failed)] + \
              [rec() for _ in range(10 - failed)]
    got = metrics.latency(records, "ttft", p)
    assert got["n"] == 10 and got["failed"] == failed
    assert (got["value"] == metrics.INF) is inf
    assert metrics.counts(records)["failed"] == failed


def test_set_up_and_ramp_traffic_is_outside_the_window():
    records = [rec(due=0.0), rec(due=-2.0), rec(due=1.0)]
    records[0]["due_s"] = None
    c = metrics.counts(records)
    assert c["attempted"] == 1 and c["judged"] == 1


def test_out_tok_s_counts_every_token_streamed_inside_the_window():
    records = [rec(due=0.0, first=0.5, gap=1.0, n=20, judged=False),  # 10 in
               rec(due=8.0, first=0.5, gap=0.11, n=30, cut=True),    # 14 in
               rec(due=-3.0, first=2.0, gap=1.0, n=5)]               # 4 in
    assert metrics.out_tok_s(records, 10.0) == pytest.approx(2.8)
    assert metrics.counts(records)["cut"] == 1
    assert metrics.counts(records)["attempted"] == 1


@pytest.mark.parametrize("name,want", [("setup_s", 12.5), ("out_tok_s", 0.5),
                                       ("ttft_p50_ms", 100.0),
                                       ("tpot_p50_ms", 20.0),
                                       ("tpot_p95_ms", 20.0)])
def test_end_to_end_metrics_are_found_by_name(name, want):
    assert metrics.end_to_end(name, [rec()], 10.0, 12.5) == pytest.approx(want)


def test_an_unknown_end_to_end_name_is_an_error():
    with pytest.raises(KeyError):
        metrics.end_to_end("goodput", [rec()], 10.0, 1.0)


def test_mean_resident_context_weights_by_decode_time():
    r = rec(due=0.0, first=1.0, gap=1.0, n=5, prompt_len=1000)  # decodes 1..5
    assert metrics.mean_resident_context([r], 10.0) == \
        pytest.approx((1000 + 2.5) * 4 / 10)


def test_mean_decoding_context_is_per_request_and_over_decode_time_only():
    """Two requests that decode one after the other through 8 of 10 s: the
    window's average holds 0.8 of a request, a decode step holds one."""
    a = rec(due=0.0, first=1.0, gap=1.0, n=5, prompt_len=1000)   # 1..5
    b = rec(due=0.0, first=5.0, gap=1.0, n=5, prompt_len=3000)   # 5..9
    each = metrics.mean_decoding_context([a, b], 10.0)
    assert each == pytest.approx((1002.5 + 3002.5) / 2)
    assert metrics.mean_resident_context([a, b], 10.0) == \
        pytest.approx(each * 0.8)
    # cut by the window's end: weighted by the time inside it
    assert metrics.mean_decoding_context([a, b], 7.0) == \
        pytest.approx((1002.5 * 4 + 3002.5 * 2) / 6)
    assert metrics.mean_decoding_context([rec(n=1)], 10.0) is None


@pytest.mark.parametrize("x,ok", [(1.0, True), (0, True), (None, False),
                                  (float("inf"), False), (float("nan"), False),
                                  ("1", False)])
def test_finite(x, ok):
    assert metrics.finite(x) is ok
