"""Idle time charged to host phases, on hand-made planes: the innermost
phase wins, the five shares add up to the chip's idle share, idle under no
phase is ``unnamed``, and a trace without the phases reads as nothing."""

from types import SimpleNamespace as NS

import pytest

from benchmark import host_phases, manifest

MS = 1e6


@pytest.fixture(autouse=True)
def fresh_memo(monkeypatch):
    """The per-file memo is the run's; each test reduces its own planes."""
    monkeypatch.setattr(host_phases, "_reduced", {})


def line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=a, duration_ns=d)
                                 for n, a, d in events])


def planes(phases, ops, modules=(), extra_host=()):
    """One chip and one host plane in ``ProfileData``'s form."""
    return [
        NS(name="/device:TPU:0", lines=[line("XLA Ops", ops),
                                        line("XLA Modules", modules)]),
        NS(name="/device:TPU:1", lines=[line("XLA Ops", [("x", 0, 1)])]),
        NS(name="/host:CPU", lines=[line("pjrt-tpu-tasks/1", extra_host),
                                    line("python3", phases)])]


# 0..100 ms traced: the device runs 10-30, 40-50, 60-90; idle is 0-10 (before
# the first op: outside the span), 30-40, 50-60. The span is 10..90 = 80 ms.
OPS = [("%fusion.1 = f32[8]{0} fusion(%a)", 10 * MS, 20 * MS),
       ("%fusion.2 = f32[8]{0} fusion(%a)", 40 * MS, 10 * MS),
       ("%fusion.3 = f32[8]{0} fusion(%a)", 60 * MS, 30 * MS)]
PHASES = [
    ("engine.window.dispatch", 5 * MS, 3 * MS),
    ("engine.window.sync", 28 * MS, 4 * MS),          # idle 30-32
    ("engine.admit", 32 * MS, 20 * MS),               # 32..52
    ("engine.admit.dispatch", 33 * MS, 2 * MS),       # idle 33-35, innermost
    ("engine.yield", 36 * MS, 2 * MS),                # idle 36-38, innermost
    ("runner.heartbeat", 36.5 * MS, 1 * MS),          # inside the yield
    ("PjitFunction(decode)", 39 * MS, 1 * MS),        # not a phase
    ("engine.window.fanout", 52 * MS, 3 * MS),        # idle 52-55
]                                                     # idle 55-60: no phase


def test_the_innermost_phase_owns_an_instant():
    segs = host_phases.innermost([e for e in PHASES
                                  if host_phases.PHASE.match(e[0])])
    owner = {(a / MS, b / MS): n for a, b, n in segs}
    assert owner[(32.0, 33.0)] == "engine.admit"
    assert owner[(33.0, 35.0)] == "engine.admit.dispatch"
    assert owner[(35.0, 36.0)] == "engine.admit"
    assert owner[(36.0, 36.5)] == "engine.yield"
    assert owner[(36.5, 37.5)] == "runner.heartbeat"
    assert owner[(37.5, 38.0)] == "engine.yield"
    assert owner[(38.0, 52.0)] == "engine.admit"
    # ordered and disjoint
    assert all(a1 >= b0 for (_, b0, _), (a1, _, _) in zip(segs, segs[1:]))


def test_idle_is_charged_to_phases_and_the_shares_add_up():
    data = host_phases.pick(planes(PHASES, OPS))
    assert [n for n, _, _ in data["phases"]] == [
        n for n, _, _ in PHASES if n != "PjitFunction(decode)"]
    red = host_phases.reduce(data)
    assert red["span_ns"] == 80 * MS and red["idle_ns"] == 20 * MS
    by = {k: v / MS for k, v in red["by_phase"].items() if v}
    assert by == pytest.approx({
        "engine.window.sync": 2.0, "engine.admit": 1.0 + 1.0 + 2.0 + 2.0,
        "engine.admit.dispatch": 2.0, "engine.yield": 1.0,
        "runner.heartbeat": 1.0, "engine.window.fanout": 3.0,
        "unnamed": 5.0})
    shares = host_phases.idle_by_group(red)
    assert shares == pytest.approx({
        "admit": 100 * 8 / 80, "window": 100 * 3 / 80,
        "eventloop": 100 * 2 / 80, "blocked": 100 * 2 / 80,
        "unnamed": 100 * 5 / 80})
    assert sum(shares.values()) == pytest.approx(100 * 20 / 80)


def test_uncovered_idle_is_unnamed():
    only_marker = [("engine.window.dispatch", 5 * MS, 1 * MS)]
    red = host_phases.reduce(host_phases.pick(planes(only_marker, OPS)))
    assert red["by_phase"] == {"unnamed": 20 * MS}
    shares = host_phases.idle_by_group(red)
    assert shares["unnamed"] == pytest.approx(25.0)
    assert shares["admit"] == shares["blocked"] == 0.0


def test_a_phase_no_group_names_counts_as_unnamed():
    phases = [("engine.window.dispatch", 5 * MS, 1 * MS),
              ("engine.later_phase", 30 * MS, 10 * MS)]
    shares = host_phases.idle_by_group(
        host_phases.reduce(host_phases.pick(planes(phases, OPS))))
    assert shares["unnamed"] == pytest.approx(25.0)
    assert sum(shares.values()) == pytest.approx(25.0)


@pytest.mark.parametrize("case", ["no_host_plane", "no_phase_line", "no_ops",
                                  "no_trace"])
def test_readers_return_none_without_phases(case, monkeypatch):
    """An older program (no annotations) or a cell without a trace: every
    reader returns None and the line leaves the metric out."""
    made = {"no_host_plane": planes(PHASES, OPS)[:2],
            "no_phase_line": planes([("PjitFunction(decode)", 1, 1)], OPS),
            "no_ops": planes(PHASES, []), "no_trace": None}[case]
    ctx = {"trace": {"file": "made-up"} if made else {}}
    if made:
        monkeypatch.setitem(host_phases._loaded, "made-up",
                            host_phases.pick(made))
    for metric in ("idle_admit_share", "idle_window_share",
                   "idle_eventloop_share", "idle_blocked_share",
                   "idle_unnamed_share"):
        assert manifest.layer_reader(metric).read(ctx) is None


def test_the_five_readers_sum_to_the_idle_share(monkeypatch):
    monkeypatch.setitem(host_phases._loaded, "made-up",
                        host_phases.pick(planes(PHASES, OPS)))
    ctx = {"trace": {"file": "made-up", "window_s": 0.08, "busy_s": 0.06}}
    values = [manifest.layer_reader(m).read(ctx) for m in (
        "idle_admit_share", "idle_window_share", "idle_eventloop_share",
        "idle_blocked_share", "idle_unnamed_share")]
    assert sum(values) == pytest.approx(
        manifest.layer_reader("device_idle_share").read(ctx))


def test_a_trace_file_is_read_once(monkeypatch, tmp_path):
    calls = []

    class FakeProfileData:
        @staticmethod
        def from_file(path):
            calls.append(path)
            return NS(planes=iter(planes(PHASES, OPS)))   # read-once

    import jax.profiler
    monkeypatch.setattr(jax.profiler, "ProfileData", FakeProfileData)
    path = str(tmp_path / "t.xplane.pb")
    first = host_phases.load(path)
    assert host_phases.load(path) is first and calls == [path]
    assert len(first["ops"]) == 3 and first["phases"]
    host_phases._loaded.pop(path)


def test_clock_margins():
    modules = [("jit_decode(7)", 10 * MS, 20 * MS),
               ("jit_chunk(9)", 40 * MS, 10 * MS),
               ("jit_decode(7)", 60 * MS, 30 * MS)]
    phases = [("engine.window.dispatch", 8 * MS, 1 * MS),
              ("engine.window.sync", 25 * MS, 6 * MS),
              ("engine.window.dispatch", 57 * MS, 1 * MS),
              ("engine.window.sync", 80 * MS, 12 * MS)]
    got = host_phases.clock_margins(
        host_phases.pick(planes(phases, OPS, modules)))
    assert got["runs"] == 2
    assert got["dispatch_leads_module_ms"] == pytest.approx(2.5)
    assert got["sync_ends_after_module_ms"] == pytest.approx(1.5)
    assert host_phases.clock_margins(
        host_phases.pick(planes(phases, OPS))) is None


def test_host_ms_per_window_reads_the_counters():
    read = manifest.layer_reader("host_ms_per_window").read
    h0 = {"windows_processed": 100, "host_phase_s": {
        "engine.window.dispatch": 1.0, "engine.window.sync": 50.0,
        "engine.park": 9.0}}
    h1 = {"windows_processed": 300, "host_phase_s": {
        "engine.window.dispatch": 1.5, "engine.window.fanout": 0.3,
        "engine.yield": 0.2, "engine.window.sync": 70.0,
        "engine.first_sync": 3.0, "engine.park": 9.5}}
    assert read({"health0": h0, "health1": h1}) == pytest.approx(
        (0.5 + 0.3 + 0.2) / 200 * 1e3)
    # an older program reports no phases; a window without windows: nothing
    assert read({"health0": {"windows_processed": 1},
                 "health1": {"windows_processed": 9}}) is None
    assert read({"health0": h1, "health1": h1}) is None


@pytest.mark.parametrize("metric,part", [
    ("engine_admit_ms", "prefill"), ("first_token_hold_ms", "first_hold"),
    ("stream_lag_ms", "stream_lag")])
def test_ttft_parts_read_the_latency_summaries(metric, part):
    read = manifest.layer_reader(metric).read
    h0 = {"latency": {f"{part}_count": 10, f"{part}_mean_s": 0.100}}
    h1 = {"latency": {f"{part}_count": 30, f"{part}_mean_s": 0.200}}
    assert read({"health0": h0, "health1": h1}) == pytest.approx(250.0)
    assert read({"health0": {"latency": {}}, "health1": {"latency": {}}}) \
        is None
