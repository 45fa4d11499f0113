"""The mailbox's trace is bounded by decode steps, with seconds as the
ceiling (``serve.bounded_trace``), and every traffic file says which bound it
sets and why."""

import glob
import json
import os
import time

import pytest

from benchmark import manifest, serve


class Profiler:
    """``jax.profiler``'s two calls, recorded."""

    def __init__(self, stop_takes=0.0):
        self.calls, self.stop_takes = [], stop_takes

    def start_trace(self, directory, **options):
        self.calls.append(("start", directory, options))

    def stop_trace(self):
        self.calls.append(("stop",))
        time.sleep(self.stop_takes)


class Engine:
    """A counter that a serve loop moves ``k`` steps every ``every`` seconds."""

    def __init__(self, k, every):
        self.k, self.every, self.t0 = k, every, time.monotonic()

    def steps(self):
        if not self.every:
            return 17                    # an idle engine: it never moves
        return 17 + self.k * int((time.monotonic() - self.t0) / self.every)


# (steps asked, seconds asked, the engine's K, seconds a window) -> which bound
CASES = {
    "steps come first": (40, 5.0, 8, 0.05, "steps"),
    "seconds come first": (4000, 0.3, 8, 0.05, "seconds"),
    "an engine that does not move": (40, 0.25, 8, 0.0, "seconds"),
    "no step bound in the file": (None, 0.25, 8, 0.05, "seconds"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_trace_stops_at_its_steps_or_at_its_seconds_whichever_comes_first(case):
    steps, seconds, k, every, bound = CASES[case]
    prof, engine = Profiler(stop_takes=0.05), Engine(k, every)
    t0 = time.monotonic()
    out = serve.bounded_trace(prof, engine.steps, "/somewhere", seconds, steps,
                              profiler_options="opts")
    took = time.monotonic() - t0
    assert [c[0] for c in prof.calls] == ["start", "stop"]
    assert prof.calls[0][1:3] == ("/somewhere", {"profiler_options": "opts"})
    assert set(out) == {"start_s", "traced_s", "traced_steps", "stop_s"}
    assert out["stop_s"] >= 0.04          # the three are rounded to ms
    assert took >= out["traced_s"] + out["stop_s"] - 0.01
    if bound == "steps":
        # stopped within a few polls of the window that reached it
        assert steps <= out["traced_steps"] <= steps + 3 * k
        assert out["traced_s"] < seconds / 2
    else:
        assert seconds - 0.001 <= out["traced_s"] < seconds + 0.5
        assert out["traced_steps"] == (0 if not every else pytest.approx(
            out["traced_s"] / every * k, abs=2 * k))


def test_a_faster_step_shortens_the_trace_and_not_its_steps():
    """What the bound is for: a program whose step is twice as fast traces
    the same number of steps, in half the time."""
    got = [serve.bounded_trace(Profiler(), Engine(8, every).steps, "/d", 5.0, 160)
           for every in (0.04, 0.02)]
    assert abs(got[0]["traced_steps"] - got[1]["traced_steps"]) <= 16
    assert got[1]["traced_s"] < 0.75 * got[0]["traced_s"]


TRAFFIC = sorted(glob.glob(os.path.join(manifest.HERE, "traffic", "*.json")))


@pytest.mark.parametrize("path", TRAFFIC, ids=[os.path.basename(p) for p in TRAFFIC])
def test_every_traffic_file_states_its_trace_bounds_and_why(path):
    with open(path) as f:
        mix = json.load(f)
    if "trace_steps" in mix:
        steps = mix["trace_steps"]
        assert isinstance(steps, int) and not isinstance(steps, bool) and steps > 0
        assert len(mix.get("trace_steps_why", "")) > 40
    # the ceiling in seconds stays, and fits the mailbox's 300 s
    assert 0 < mix.get("trace_seconds", 5) <= 10
    for key in mix:
        if key.startswith("trace_") and key.endswith("_why"):
            assert key[:-len("_why")] in mix
