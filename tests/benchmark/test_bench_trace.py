"""The reduction from a profiler trace to numbers, on made-up planes, on a
short recording of a real TPU trace (``data/recorded_planes.json.gz``, device
planes as ``trace.read_planes`` returns them), and on a trace file made here."""

import gzip
import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MARKER = "paged_decode_attention"   # the kernel the made-up planes count by


@pytest.mark.parametrize("intervals,want", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 10)], 15), ([(0, 10), (2, 3)], 10),
    ([(20, 5), (0, 10)], 15), ([(0, 10), (10, 10)], 20)])
def test_union_of_intervals(intervals, want):
    assert trace.union_ns(intervals) == want


@pytest.mark.parametrize("name,key", [
    ("%fusion.123 = bf16[8,16]{1,0} fusion(...)", "fusion.123"),
    ("fusion.7", "fusion.7"), ("all-reduce.3", "all-reduce.3")])
def test_op_key(name, key):
    assert trace.op_key(name) == key


@pytest.mark.parametrize("name,group", [
    ("%fusion.14 = bf16[8,32,4096]{2,1,0:T(8,128)(2,1)} fusion(bf16[1]{0} %p)",
     "fusion:bf16[8,32,4096]"),
    ("%paged_decode_attention.24 = bf16[32,8,4,128]{3,2,1,0} custom-call(s32[32,33]{1,0} %x)",
     "paged_decode_attention:bf16[32,8,4,128]"),
    ("fusion.9", "fusion"), ("%all-reduce.3 = f32[4096]{0} all-reduce(%y)",
                             "all-reduce:f32[4096]")])
def test_op_group_merges_the_layers(name, group):
    assert trace.op_group(name) == group


def test_program_key_drops_the_run_id():
    assert trace.program_key("jit_decode(1234567)") == "jit_decode"


@pytest.mark.parametrize("name,ok", [("/device:TPU:0", True),
                                     ("/device:TPU:3", True),
                                     ("/host:CPU", False),
                                     ("/device:TPU:0 SparseCore 1", False)])
def test_device_planes(name, ok):
    assert trace.is_device_plane(name) is ok


def made_up_planes():
    """Two chips, two layers. A K=2 decode program (4 attention calls) then a
    gap of 1 ms, a chunk program, a K=1 decode program."""
    ms = 1e6
    ops, mods = [], []
    mods.append(("jit_decode(1)", 0.0, 4 * ms))
    for i in range(4):
        ops.append(("paged_decode_attention.1", i * ms, 0.25 * ms))
        ops.append(("fusion.9", i * ms + 0.25 * ms, 0.5 * ms))
    ops.append(("all-reduce.2", 3.8 * ms, 0.2 * ms))
    mods.append(("jit_chunk(2)", 5 * ms, 2 * ms))
    ops.append(("fusion.11", 5 * ms, 2 * ms))
    mods.append(("jit_decode(3)", 7 * ms, 1 * ms))
    for i in range(2):
        ops.append(("paged_decode_attention.1", 7 * ms + i * 0.5 * ms, 0.1 * ms))
    ops.append(("fusion.9", 7.6 * ms, 0.4 * ms))
    lines = {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}
    return [{"name": "/device:TPU:0", "lines": lines},
            {"name": "/device:TPU:1", "lines": lines}]


def test_reduce_made_up_planes():
    got = trace.reduce_planes(made_up_planes(), MARKER, 2)
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(8e-3)
    assert got["busy_s"] == pytest.approx((4 * 0.75 + 0.2 + 2 + 0.2 + 0.4) * 1e-3)
    assert got["collective_s"] == pytest.approx(0.2e-3)
    assert got["programs"]["jit_decode"] == {
        "runs": 2, "seconds": pytest.approx(5e-3), "steps": 3}
    # 4 ms over 2 steps, 1 ms over 1 step: the median of 2 and 1
    assert got["decode_step_ms"] == pytest.approx(1.5)
    assert got["device_ops"][0] == ["jit_decode/fusion", pytest.approx(2.4e-3)]
    assert got["idle_gaps"] == [["jit_decode->jit_chunk", pytest.approx(1e-3)]]
    # every operation group, per chip: the kernels' readers take theirs here
    assert got["op_seconds"] == pytest.approx({
        "jit_decode/paged_decode_attention": 1.2e-3, "jit_decode/fusion": 2.4e-3,
        "jit_decode/all-reduce": 0.2e-3, "jit_chunk/fusion": 2e-3})


@pytest.mark.parametrize("marker,per_step,steps,step_ms", [
    ("paged_decode_attention", 2, 3, 1.5), ("paged_decode_attention", 1, 6, 0.75),
    ("fusion", 2, 2, 2.0), ("no_such_kernel", 2, None, None),
    ("", 2, None, None), ("paged_decode_attention", 0, None, None)])
def test_steps_are_counted_by_the_marker_the_family_names(marker, per_step,
                                                          steps, step_ms):
    got = trace.reduce_planes(made_up_planes(), marker, per_step)
    assert got["programs"]["jit_decode"].get("steps") == steps
    assert got["decode_step_ms"] == (pytest.approx(step_ms) if step_ms
                                     else None)


def test_asynchronous_collectives_count_from_start_to_done():
    planes = made_up_planes()
    for p in planes:
        p["lines"] = dict(p["lines"], **{trace.ASYNC_LINE: [
            ("%all-reduce-start.5 = f32[4096]{0} all-reduce-start(%x)", 1e6, 3e5),
            ("%copy-start.1 = bf16[8]{0} copy-start(%y)", 2e6, 9e5)]})
    got = trace.reduce_planes(planes, MARKER, 2)
    assert got["collective_s"] == pytest.approx(0.5e-3)
    assert got["busy_s"] == pytest.approx((4 * 0.75 + 0.2 + 2 + 0.2 + 0.4) * 1e-3)


def test_no_device_plane_reduces_to_nothing():
    assert trace.reduce_planes([]) == {}
    assert trace.reduce_planes([{"name": "/device:TPU:0", "lines": {}}]) == {}


def test_reduce_the_recorded_tpu_trace():
    path = os.path.join(DATA, "recorded_planes.json.gz")
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    with open(os.path.join(DATA, "recorded_planes.expect.json")) as f:
        expect = json.load(f)
    got = trace.reduce_planes(planes, MARKER, expect["n_layers"])
    assert got["chips"] == expect["chips"]
    for key in ("window_s", "busy_s", "decode_step_ms"):
        assert got[key] == pytest.approx(expect[key], rel=1e-6), key
    assert 0 < got["busy_s"] <= got["window_s"]
    assert [k for k, _ in got["device_ops"][:3]] == expect["top_ops"]
    assert set(expect["programs"]) <= set(got["programs"])


def test_a_trace_file_made_here_is_read_with_profiledata(tmp_path):
    """The CPU backend writes no device plane: the reduction finds nothing,
    and says so by returning nothing."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    assert any(name.startswith("/host:") for name in trace.describe(path))
    assert trace.reduce_dir(str(tmp_path)) == {}
    assert trace.find_xplane(str(tmp_path / "nothing")) is None
