"""``benchmark/run.py`` end to end at tiny size on the CPU backend (gateway,
worker, runner container, probes against the reference, the window, the
per-layer readers), and the real entry's refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest

RUN = os.path.join(manifest.HERE, "run.py")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, cwd=manifest.ROOT, timeout=420):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, RUN, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def rehearsal_line(proc):
    infos = [json.loads(ln)["info"] for ln in proc.stdout.splitlines()
             if ln.startswith('{"info"')]
    lines = [i["rehearsal_line"] for i in infos if "rehearsal_line" in i]
    assert len(lines) == 1, proc.stdout[-2000:] + proc.stderr[-3000:]
    return lines[0], infos


@pytest.mark.parametrize("cell,trace", [(manifest.load()["workloads"][0]["name"], 0),
                                        (manifest.load()["workloads"][1]["name"], 1)])
def test_rehearsal_walks_every_step_and_prints_no_result(cell, trace):
    proc = run("--workload", cell, "--seed", str(2 ** 31 + 4242 + trace),
               "--seconds", "4", "--trace", str(trace), "--rehearse")
    # a rehearsal is not a chip run: exit 3, and the last line is no result
    assert proc.returncode == 3, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert "correct" not in json.loads(last)
    line, infos = rehearsal_line(proc)
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    m = manifest.load()
    group = "per_layer" if trace else "end_to_end"
    declared = {x["name"]: x["unit"]
                for x in manifest.cell_metrics(m, cell, group)}
    assert line["metrics"] and set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]
        assert isinstance(got["value"], (int, float))
    if not trace:
        assert set(line["metrics"]) == set(declared)      # every one, each > 0
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:
        # the CPU backend has no device plane: the trace readers return
        # nothing and their metrics are left out, the counters' stay
        assert "decode_step_ms" not in line["metrics"]
        assert "decode_batch_mean" in line["metrics"]
        assert "busy_s" not in line["device"]
        # the trace's bounds and what they held: steps first, seconds the
        # ceiling (at these sizes the seconds come first)
        profile = next(i["profile"] for i in infos if "profile" in i)
        assert set(profile) == {"start_s", "traced_s", "traced_steps", "stop_s"}
        assert profile["traced_steps"] > 0 and profile["traced_s"] > 0
    # no device node here, so nothing to wait for; the wait, the release and
    # where the run's seconds went are said all the same
    started = next(i for i in infos if "stack_s" in i)
    assert started["chips_wait_s"] == 0.0
    assert isinstance(started["native_build"], bool)
    assert next(i for i in infos if "setup_s" in i)["chips_wait_s"] == 0.0
    assert next(i for i in infos if "probes" in i)["chips_held_by_runner"] == []
    end = next(i for i in infos if "wall_s" in i)
    assert end["chips_release_s"] == 0.0
    assert list(end["wall_s"]) == [
        "stack_up", "window_open", "window_end", "measured", "stack_stopped",
        *(["trace_read"] if trace else []), "reduced", "chips_released"]
    assert list(end["wall_s"].values()) == sorted(end["wall_s"].values())
    ref = next(i["reference"] for i in infos if "reference" in i)
    assert ref["tokens_checked"] == 96
    counts = next(i["counts"] for i in infos if "counts" in i)
    assert counts["failed"] == 0 and counts["attempted"] == line["attempted"]


def test_the_real_entry_refuses_a_machine_without_a_tpu():
    proc = run("--workload", manifest.load()["workloads"][0]["name"],
               "--seed", "1", "--seconds", "2", "--trace", "0", timeout=180)
    assert proc.returncode not in (0, 3)
    assert "TPU chips" in proc.stderr
    assert not any("correct" in ln for ln in proc.stdout.splitlines())


def test_it_refuses_a_directory_that_holds_only_the_benchmark(tmp_path):
    m = manifest.load()
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for path in m["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload",
         m["workloads"][0]["name"], "--seed", "1", "--seconds", "2",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env={k: v for k, v in os.environ.items()
                          if k != "PYTHONPATH"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_an_unknown_cell_is_an_error():
    proc = run("--workload", "no-such-cell", "--seed", "1", "--seconds", "2",
               "--trace", "0", timeout=120)
    assert proc.returncode != 0 and "no-such-cell" in proc.stderr
