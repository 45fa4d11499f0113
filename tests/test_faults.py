"""Unit tests for the deterministic fault-injection plane (ISSUE 15):
spec parsing, trigger arithmetic, seeded reproducibility, flag-file
arming, and engine-instance instrumentation — the plane the chaos e2e
tests drive."""

import os

import pytest

from tpu9.testing.faults import FaultPlane, FaultSpec, parse_spec


def test_parse_spec_full_grammar():
    specs = parse_spec("crash:after_tokens=8,flag=1;"
                       "rpc_error:times=2,prob=0.5;"
                       "peer_read_slow:delay_s=0.25;"
                       "stall:duration_s=3.5,after_calls=2")
    assert set(specs) == {"crash", "rpc_error", "peer_read_slow", "stall"}
    assert specs["crash"].after_tokens == 8 and specs["crash"].flag
    assert specs["rpc_error"].times == 2
    assert specs["rpc_error"].prob == pytest.approx(0.5)
    assert specs["peer_read_slow"].delay_s == pytest.approx(0.25)
    assert specs["stall"].duration_s == pytest.approx(3.5)
    assert specs["stall"].after_calls == 2


def test_parse_spec_rejects_garbage_loudly():
    with pytest.raises(ValueError):
        parse_spec("crash:after_tokens")          # not key=value
    with pytest.raises(ValueError):
        parse_spec(":after_tokens=3")             # no kind


def test_unknown_options_are_kept_forward_compatible():
    specs = parse_spec("crash:new_option=zzz")
    assert specs["crash"].extra == {"new_option": "zzz"}


def test_crash_defaults_to_oneshot():
    plane = FaultPlane(parse_spec("crash:after_tokens=4"))
    assert not plane.fire("crash", tokens=3)      # not armed yet
    assert plane.fire("crash", tokens=4)
    assert not plane.fire("crash", tokens=99)     # oneshot spent
    assert plane.snapshot()["crash"] == {"fired": 1, "calls": 3}


def test_times_bounds_repeating_faults():
    plane = FaultPlane(parse_spec("rpc_error:times=2"))
    fired = [plane.fire("rpc_error") for _ in range(5)]
    assert fired == [True, True, False, False, False]


def test_after_calls_arms_from_the_nth_call():
    plane = FaultPlane(parse_spec("rpc_error:after_calls=3,times=1"))
    assert [plane.fire("rpc_error") for _ in range(4)] == \
        [False, False, True, False]


def test_prob_schedule_is_seed_deterministic():
    def run(seed):
        plane = FaultPlane(parse_spec("rpc_error:prob=0.5"), seed=seed)
        return [plane.fire("rpc_error") for _ in range(32)]

    assert run(1) == run(1)
    assert run(1) != run(2)        # astronomically unlikely to collide
    assert any(run(1)) and not all(run(1))


def test_per_kind_rngs_are_independent():
    # firing one kind must not perturb another's schedule
    a = FaultPlane(parse_spec("rpc_error:prob=0.5;peer_read_error:prob=0.5"),
                   seed=3)
    b = FaultPlane(parse_spec("rpc_error:prob=0.5;peer_read_error:prob=0.5"),
                   seed=3)
    seq_a = []
    for i in range(20):
        if i % 2 == 0:
            b.fire("peer_read_error")    # extra interleaved draws on b
        seq_a.append((a.fire("rpc_error"), b.fire("rpc_error")))
    assert all(x == y for x, y in seq_a)


def test_unknown_kind_never_fires():
    plane = FaultPlane(parse_spec("crash:after_tokens=1"))
    assert not plane.fire("nope")
    assert not plane.active("nope")
    assert plane.delay_s("nope") == 0.0


def test_window_fault_opens_and_autoclears(monkeypatch):
    import tpu9.testing.faults as faults_mod
    t = [100.0]
    monkeypatch.setattr(faults_mod.time, "monotonic", lambda: t[0])
    plane = FaultPlane(parse_spec("stall:duration_s=2.0"))
    assert plane.active("stall")
    t[0] += 1.0
    assert plane.active("stall")
    t[0] += 1.5                      # 2.5s after arming: window closed
    assert not plane.active("stall")
    # recovery is permanent — the window does not re-open
    assert not plane.active("stall")


def test_flag_file_arms_per_container(tmp_path):
    plane = FaultPlane(parse_spec("crash:flag=1"),
                       container_id="c-victim", flag_dir=str(tmp_path))
    assert not plane.fire("crash", tokens=0)
    open(os.path.join(str(tmp_path), "crash-c-other"), "w").close()
    assert not plane.fire("crash", tokens=0)     # someone ELSE's flag
    open(os.path.join(str(tmp_path), "crash-c-victim"), "w").close()
    assert plane.fire("crash", tokens=0)


def test_from_env_roundtrip():
    env = {"TPU9_FAULTS": "crash:after_tokens=5", "TPU9_FAULTS_SEED": "9",
           "TPU9_CONTAINER_ID": "c1", "TPU9_FAULTS_FLAG_DIR": "/tmp/x"}
    plane = FaultPlane.from_env(env)
    assert plane is not None
    assert plane.seed == 9 and plane.container_id == "c1"
    assert plane.specs["crash"].after_tokens == 5
    assert FaultPlane.from_env({}) is None


def test_delay_s_respects_prob_and_times():
    plane = FaultPlane(parse_spec("peer_read_slow:delay_s=0.5,times=1"))
    assert plane.delay_s("peer_read_slow") == pytest.approx(0.5)
    assert plane.delay_s("peer_read_slow") == 0.0     # times spent


def test_instrument_engine_patches_the_instance_only():
    class FakeEngine:
        def __init__(self):
            self._stats = {"tokens_generated": 0}
            self.dispatches = 0

        def _dispatch_window(self):
            self.dispatches += 1
            return "window"

    eng = FakeEngine()
    plane = FaultPlane(parse_spec("crash:after_tokens=3"))
    assert plane.instrument_engine(eng) is eng
    assert eng._dispatch_window() == "window"       # not armed
    eng._stats["tokens_generated"] = 3
    with pytest.raises(RuntimeError, match="induced engine crash"):
        eng._dispatch_window()
    # oneshot: the patched dispatch recovers to the original behavior
    assert eng._dispatch_window() == "window"
    assert eng.dispatches == 2
    # a plane with no engine faults leaves the instance untouched
    eng2 = FakeEngine()
    FaultPlane(parse_spec("rpc_error:times=1")).instrument_engine(eng2)
    assert eng2._dispatch_window.__self__ is eng2 \
        if hasattr(eng2._dispatch_window, "__self__") else True


def test_instrument_engine_stall_spins_without_progress():
    class FakeEngine:
        def __init__(self):
            self._stats = {"tokens_generated": 10}

        def _dispatch_window(self):
            return "window"

    eng = FakeEngine()
    plane = FaultPlane(parse_spec("stall:after_tokens=5"))
    plane.instrument_engine(eng)
    assert eng._dispatch_window() is None           # wedged
    assert eng._dispatch_window() is None
