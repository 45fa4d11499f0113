"""A run starts its worker only on free chips, and that wait is not set-up:
``benchmark/chips.py`` on a directory of fake device nodes, one of them held
by a child process, and ``run.result_line``'s ``setup_s`` with and without a
wait."""

import errno
import json
import os
import subprocess
import sys
import time
import types

import pytest

from benchmark import chips, manifest, run, stack

HOLD = ("import sys, time; f = open(sys.argv[1]); print('held', flush=True); "
        "time.sleep(float(sys.argv[2]))")


@pytest.fixture
def dev(tmp_path):
    (tmp_path / "vfio").mkdir()
    for name in ("0", "1", "vfio"):     # /dev/vfio/vfio is no chip
        (tmp_path / "vfio" / name).write_text("")
    return str(tmp_path)


@pytest.fixture
def holder():
    procs = []

    def hold(path, seconds=60.0):
        p = subprocess.Popen([sys.executable, "-c", HOLD, path, str(seconds)],
                             stdout=subprocess.PIPE, text=True)
        procs.append(p)
        assert p.stdout.readline().strip() == "held"
        return p

    yield hold
    for p in procs:
        p.kill()
        p.wait(10)


def test_the_nodes_are_the_workers_inventory(dev):
    assert chips.nodes(dev) == [f"{dev}/vfio/0", f"{dev}/vfio/1"]
    open(f"{dev}/accel0", "w").close()
    assert chips.nodes(dev) == [f"{dev}/accel0"]
    assert chips.nodes(os.path.join(dev, "nothing")) == []


def test_a_node_reads_busy_while_a_process_holds_it_and_free_after(dev, holder):
    assert chips.busy(dev) == {}
    child = holder(f"{dev}/vfio/1")
    held = chips.busy(dev)
    assert list(held) == [f"{dev}/vfio/1"]
    (pid, cmd), = held[f"{dev}/vfio/1"]
    assert pid == child.pid and "time.sleep" in cmd
    child.kill()
    child.wait(10)
    assert chips.busy(dev) == {}
    assert chips.wait_free(timeout=1, dev=dev) == 0.0


def test_a_node_that_refuses_open_is_busy_with_no_holder_to_name(dev, monkeypatch):
    real = os.open

    def refuse(path, flags, *a, **kw):
        if str(path).endswith("vfio/0"):
            raise OSError(errno.EBUSY, "Device or resource busy")
        return real(path, flags, *a, **kw)

    monkeypatch.setattr(chips.os, "open", refuse)
    held = chips.busy(dev)
    assert held == {f"{dev}/vfio/0": []}
    assert "no process /proc shows" in chips.describe(held)


def test_the_wait_ends_when_the_holder_does_and_says_how_long_it_took(dev, holder):
    holder(f"{dev}/vfio/0", seconds=0.6)
    waited = chips.wait_free(timeout=20, dev=dev, poll=0.05)
    assert 0.0 < waited < 10.0
    assert chips.busy(dev) == {}


def test_the_wait_times_out_and_names_the_node_and_its_holder(dev, holder):
    child = holder(f"{dev}/vfio/1")
    t0 = time.monotonic()
    with pytest.raises(chips.ChipsBusy) as exc:
        chips.wait_free(timeout=0.4, dev=dev, poll=0.05)
    assert 0.4 <= time.monotonic() - t0 < 5
    msg = str(exc.value)
    assert f"{dev}/vfio/1" in msg and f"pid {child.pid}" in msg
    assert "time.sleep" in msg and "vfio/0" not in msg


def test_a_stack_refuses_to_start_on_busy_chips_and_starts_nothing(
        tmp_path, monkeypatch):
    def never(*a, **kw):
        raise chips.ChipsBusy("after 60 s still busy: /dev/vfio/2 held by "
                              "pid 7 (python3 -m tpu9.runner)")

    monkeypatch.setattr(stack.chips, "wait_free", never)
    s = stack.Stack(str(tmp_path), dict(os.environ), 4, fake_chips=False)
    with pytest.raises(stack.StackError, match="/dev/vfio/2 held by pid 7"):
        s.start()
    assert s.procs == [] and s.release() == 0.0


def rec(due, n=20, first=0.1, gap=0.02):
    """A served request as ``client.Client.send`` records it."""
    return {"class": "c", "judged": True, "prompt_len": 100, "want_tokens": n,
            "due_s": due, "sent_s": due, "ok": True, "error": "",
            "token_s": [due + first + i * gap for i in range(n)],
            "tokens": [1] * n}


def fake_run(wait_s, t_start=1000.0, open_s=7.25, to_window=100.0) -> tuple:
    """A session and what ``measure`` hands back, as far as an untraced
    ``result_line`` reads them."""
    m = manifest.load()
    cell = m["workloads"][0]
    s = types.SimpleNamespace(
        args=types.SimpleNamespace(trace=0, rehearse=False), manifest=m,
        cell=cell, traffic={}, config={"correct_tolerance_logit": 0.25},
        stack=types.SimpleNamespace(chips_wait_s=wait_s))
    got = {"records": [rec(i * 0.1) for i in range(30)],
           "health_ready": {"coldstart_device_open_s": open_s},
           "health1": {"graph_compiles_post_warmup": 0},
           "reference": {"worst_margin": 0.01}, "window_end_clock": 10.0,
           "memory": {"peak_bytes_by_device": [5]},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           # everything takes as long as ever; the wait comes on top
           "t_window_open": t_start + to_window + wait_s}
    return s, got


@pytest.mark.parametrize("wait_s", [0.0, 19.3])
def test_a_wait_for_free_chips_is_not_setup_time(wait_s, monkeypatch, capsys):
    monkeypatch.setattr(run, "T_START", 1000.0)
    s, got = fake_run(wait_s)
    if "setup_s" not in {x["name"] for x in manifest.cell_metrics(
            s.manifest, s.cell["name"], "end_to_end")}:
        pytest.skip("the first cell reports no setup_s")
    line = run.result_line(s, got, 10.0)
    assert line["metrics"]["setup_s"]["value"] == pytest.approx(100.0 - 7.25)
    infos = [json.loads(ln)["info"] for ln in capsys.readouterr().out.splitlines()]
    shown = next(i for i in infos if "setup_s" in i)
    assert shown["chips_wait_s"] == wait_s
    assert shown["setup_s"] == pytest.approx(92.75)
