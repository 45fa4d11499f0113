"""The five readers that name a streamed request's time to first token hop by
hop (ISSUE 41), on hand-made snapshots: a window of requests whose stamps are
known, told to the readers the way a run tells them — cumulative mean and
count of each summary at the window's two ends, and the client's records."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest, readers

NEW = ("gateway_pre_forward_ms", "runner_door_ms", "runner_ingest_ms",
       "gateway_first_relay_ms", "client_hop_ms")
# the engine's parts and the stream lag: readers the benchmark had
HAD = ("engine_queue_wait_ms", "engine_admit_ms", "first_token_hold_ms",
       "stream_lag_ms")

# one request's stamps in ms, each on its own process's clock, as offsets
# from that process's first: client c0 due, c1 first token seen; gateway g0
# entry, g1 sent, g2 headers back, g3 first token written; runner r0 first
# line, r1 enqueued, r2 headers written, r3 first token written; engine
# e0 enqueue (= r1), e1 admission starts, e2 ends, e3 first token queued
BEFORE = [dict(c=(0, 90), g=(0, 2, 30, 84), r=(0, 1, 1.5, 52),
               e=(1, 4, 21, 41))] * 3
WINDOW = [dict(c=(0, 400), g=(0, 5, 160, 395), r=(0, 3, 4, 237),
               e=(3, 83, 113, 203)),
          dict(c=(0, 200), g=(0, 3, 60, 196), r=(0, 1, 2, 137),
               e=(1, 41, 71, 101)),
          dict(c=(0, 300), g=(0, 4, 110, 292), r=(0, 2, 3, 183),
               e=(2, 62, 92, 152)),
          dict(c=(0, 120), g=(0, 4, 20, 117), r=(0, 2, 3, 98),
               e=(2, 12, 42, 82))]

SUMMARIES = {
    "pre": lambda q: q["g"][1] - q["g"][0],
    "connect": lambda q: q["g"][2] - q["g"][1],
    "first": lambda q: q["g"][3] - q["g"][2],
    "ingest": lambda q: q["r"][2] - q["r"][0],
    "runner_first": lambda q: q["r"][3] - q["r"][2],
    "queue_wait": lambda q: q["e"][1] - q["e"][0],
    "prefill": lambda q: q["e"][2] - q["e"][1],
    "first_hold": lambda q: q["e"][3] - q["e"][2],
    "ttft": lambda q: q["e"][3] - q["e"][0],
    "stream_lag": lambda q: q["r"][3] - q["e"][3],
}
GATEWAY = {"pre": "tpu9_gateway_stream_pre_s",
           "connect": "tpu9_gateway_stream_connect_s",
           "first": "tpu9_gateway_stream_first_s"}


def _snapshots(requests):
    """(gateway /api/v1/metrics, runner /health) after ``requests``."""
    gateway, latency = {}, {}
    for part, fn in SUMMARIES.items():
        n = len(requests)
        mean = sum(fn(q) for q in requests) / 1e3 / n if n else 0.0
        if part in GATEWAY:
            gateway[GATEWAY[part]] = {"count": n, "mean": mean}
        else:
            latency[f"{part}_count"] = n
            latency[f"{part}_mean_s"] = mean
    return {"summaries": gateway}, {"latency": latency}


@pytest.fixture()
def ctx():
    gateway0, health0 = _snapshots(BEFORE)
    gateway1, health1 = _snapshots(BEFORE + WINDOW)
    records = [{"due_s": 1.0 + i, "token_s": [1.0 + i + q["c"][1] / 1e3],
                "judged": i % 2 == 0, "ok": True}
               for i, q in enumerate(WINDOW)]
    # set-up traffic has no due time; a request without a token has no TTFT
    records += [{"due_s": None, "token_s": [0.5], "judged": False, "ok": True},
                {"due_s": 9.0, "token_s": [], "judged": True, "ok": False}]
    return {"gateway0": gateway0, "gateway1": gateway1, "health0": health0,
            "health1": health1, "records": records, "seconds": 10.0,
            "engine": {"max_batch": 2}, "cell": "a-cell"}


def _read(name, ctx):
    return manifest.layer_reader(name).read(ctx)


def _mean(fn):
    return sum(fn(q) for q in WINDOW) / len(WINDOW)


@pytest.mark.parametrize("name, want", [
    ("gateway_pre_forward_ms", _mean(SUMMARIES["pre"])),
    ("runner_door_ms", _mean(lambda q: SUMMARIES["connect"](q)
                             - SUMMARIES["ingest"](q))),
    ("runner_ingest_ms", _mean(SUMMARIES["ingest"])),
    ("gateway_first_relay_ms", _mean(lambda q: SUMMARIES["first"](q)
                                     - SUMMARIES["runner_first"](q))),
    ("client_hop_ms", _mean(lambda q: q["c"][1] - q["g"][3])),
])
def test_a_reader_takes_the_windows_mean(ctx, name, want):
    assert _read(name, ctx) == pytest.approx(want, abs=1e-6)


def test_the_parts_add_up_to_the_clients_mean(ctx):
    """The five new metrics, the engine's three parts and the stream lag are
    the client's mean time to first token, less the step from the enqueue to
    the runner's headers (in ``ingest`` and in the queue wait both)."""
    parts = sum(_read(name, ctx) for name in NEW + HAD)
    client = _mean(lambda q: q["c"][1] - q["c"][0])
    twice = _mean(lambda q: q["r"][2] - q["r"][1])
    assert parts == pytest.approx(client + twice, abs=1e-6)
    # and the check that the runner's stamps are where they say
    assert readers.engine_phase_mean_ms(ctx, "runner_first") == pytest.approx(
        readers.engine_phase_mean_ms(ctx, "ttft")
        + readers.engine_phase_mean_ms(ctx, "stream_lag") - twice, abs=1e-6)


@pytest.mark.parametrize("name, missing", [
    ("gateway_pre_forward_ms", "tpu9_gateway_stream_pre_s"),
    ("runner_door_ms", "tpu9_gateway_stream_connect_s"),
    ("runner_door_ms", "ingest"),
    ("runner_ingest_ms", "ingest"),
    ("gateway_first_relay_ms", "tpu9_gateway_stream_first_s"),
    ("gateway_first_relay_ms", "runner_first"),
    ("client_hop_ms", "tpu9_gateway_stream_pre_s"),
    ("client_hop_ms", "tpu9_gateway_stream_connect_s"),
    ("client_hop_ms", "tpu9_gateway_stream_first_s"),
])
def test_a_reader_finds_nothing_where_a_summary_is_missing(ctx, name, missing):
    """As on a program that emits no such summary (the parent commit): None,
    and no exception."""
    for end in "01":
        ctx[f"gateway{end}"]["summaries"].pop(missing, None)
        for key in (f"{missing}_count", f"{missing}_mean_s"):
            ctx[f"health{end}"]["latency"].pop(key, None)
    assert _read(name, ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_snapshots_of_an_older_program(ctx, name):
    bare = dict(ctx, gateway0={}, gateway1={"summaries": {}},
                health0={}, health1={"latency": {}})
    assert _read(name, bare) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_window_without_requests(ctx, name):
    still = dict(ctx, gateway1=ctx["gateway0"], health1=ctx["health0"],
                 records=[])
    assert _read(name, still) is None


@pytest.mark.parametrize("name, summary, reads", [
    ("runner_door_ms", "tpu9_gateway_stream_connect_s", True),
    ("gateway_first_relay_ms", "tpu9_gateway_stream_first_s", True),
    ("client_hop_ms", "tpu9_gateway_stream_first_s", True),
    ("gateway_pre_forward_ms", "tpu9_gateway_stream_pre_s", False),
])
def test_counts_that_differ_by_more_than_a_batch_read_nothing(
        ctx, name, summary, reads):
    """Requests in flight at either end of the window are seen by one side
    and not the other: up to ``max_batch`` of them is the same requests,
    more is two different sets, and a difference of their means is no
    interval."""
    near = copy.deepcopy(ctx)
    near["gateway1"]["summaries"][summary]["count"] += 2
    assert _read(name, near) is not None
    far = copy.deepcopy(ctx)
    far["gateway1"]["summaries"][summary]["count"] += 3
    # a metric of one summary alone has nothing to compare its count with
    assert (_read(name, far) is None) == reads


def test_client_hop_counts_every_record_with_a_first_token(ctx):
    """Judged or not, cut or not: the summaries hold all of them."""
    more = copy.deepcopy(ctx)
    more["records"] += [{"due_s": 8.0, "token_s": [8.5], "judged": False,
                         "ok": False, "cut": True}] * 3
    assert _read("client_hop_ms", more) is None      # 7 records, 4 observed
    more["engine"]["max_batch"] = 3
    assert _read("client_hop_ms", more) == pytest.approx(
        (sum(q["c"][1] for q in WINDOW) + 3 * 500) / 7
        - _mean(lambda q: q["g"][3]), abs=1e-6)


def test_the_tool_prints_the_waterfall_of_a_saved_run(ctx, tmp_path):
    run_dir = tmp_path / "a-cell.seed1.trace1"
    run_dir.mkdir()
    (run_dir / "context.json").write_text(json.dumps(ctx))
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "tools", "ttft.py"),
         str(run_dir)], capture_output=True, text=True, check=True)
    got = json.loads(out.stdout)
    assert got["cell"] == "a-cell"
    assert list(got["waterfall_ms"]) == [
        "client_hop_ms", "gateway_pre_forward_ms", "runner_door_ms",
        "runner_ingest_ms", "engine_queue_wait_ms", "engine_admit_ms",
        "first_token_hold_ms", "stream_lag_ms", "gateway_first_relay_ms"]
    assert got["client_ttft_mean_ms"] == pytest.approx(255.0)
    assert got["unnamed_ms"] == pytest.approx(-1.0)
    assert got["named_ms"] + got["unnamed_ms"] == pytest.approx(255.0)
    assert got["check"]["runner_first_ms"] == pytest.approx(
        got["check"]["ttft_plus_stream_lag_ms"] - 1.0)
    assert set(got["observations"].values()) == {4}
