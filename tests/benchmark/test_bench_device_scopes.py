"""Decode-program device time by scope, on hand-made events."""

import pytest

from benchmark import device_scopes, host_phases, manifest

M = manifest.load()
# the scopes are the Mistral family's: the first configuration's family
FAMILY = manifest.family(manifest.load_config(M, M["configs"][0]["name"]))

MS = 1e6
MAPS = {
    "decode_1": {"kv.slice": ["fusion.1"], "kv.pack": ["fusion.2"],
                 "attn.core": ["custom-call.3"], "ffn": ["fusion.4"],
                 "head": ["fusion.5"]},
    # the K=8 program numbers its instructions apart
    "decode_8": {"kv.write": ["fusion.1"], "attn.core": ["custom-call.9"],
                 "moe.experts": ["fusion.2", "fusion.7"],
                 "moe.route": ["fusion.4"]},
    "chunk_128": {"ffn": ["fusion.1", "fusion.2", "fusion.4"]},
}
MODULES = [("jit_decode(11)", 0.0, 10 * MS), ("jit_chunk(5)", 10 * MS, 5 * MS),
           ("jit_decode(22)", 20 * MS, 10 * MS),
           ("jit_decode(11)", 30 * MS, 10 * MS)]


def op(name, start_ms, dur_ms):
    return (f"%{name} = f32[8]{{0}} fusion(%x)", start_ms * MS, dur_ms * MS)


OPS = [
    # program 11 is the K=1 program: fusion.1/2/4/5 and custom-call.3
    op("fusion.1", 0, 2), op("fusion.2", 2, 2), op("custom-call.3", 4, 1),
    op("fusion.4", 5, 4), op("fusion.5", 9, 0.5), op("copy.6", 9.5, 0.5),
    # a chunk program's operations are not decode time
    op("fusion.1", 10, 5),
    # program 22 is the K=8 program: a while container around its body
    ("%while.1 = (f32[8]{0}) while(%t)", 20 * MS, 10 * MS),
    op("fusion.1", 20, 1), op("custom-call.9", 21, 3), op("fusion.2", 24, 2),
    op("fusion.7", 26, 2), op("fusion.4", 28, 2),
    # program 11 again
    op("fusion.1", 30, 2), op("fusion.4", 32, 8),
    # outside every program run
    op("fusion.1", 45, 1)]


def test_seconds_by_scope_pick_each_programs_own_map():
    got = device_scopes.by_scope(OPS, MODULES, MAPS)
    ms = {k: v * 1e3 for k, v in got.items()}
    assert ms == pytest.approx({
        "kv.slice": 4.0, "kv.pack": 2.0, "kv.write": 1.0,
        "attn.core": 1.0 + 3.0, "ffn": 4.0 + 8.0, "head": 0.5,
        "moe.experts": 4.0, "moe.route": 2.0, "other": 0.5})
    # all of the decode runs' operations, the container left out
    assert sum(ms.values()) == pytest.approx(30.0)


def test_nothing_without_a_map_or_without_decode_runs():
    assert device_scopes.by_scope(OPS, MODULES, {}) == {}
    assert device_scopes.by_scope(OPS, MODULES, None) == {}
    assert device_scopes.by_scope(OPS, MODULES,
                                  {"chunk_128": MAPS["chunk_128"]}) == {}
    assert device_scopes.by_scope(OPS, [("jit_chunk(5)", 0.0, 50 * MS)],
                                  MAPS) == {}


def test_the_three_readers(monkeypatch):
    monkeypatch.setattr(device_scopes, "_seconds", {})
    monkeypatch.setitem(host_phases._loaded, "made-up",
                        {"phases": None, "ops": OPS, "modules": MODULES})
    ctx = {"trace": {"file": "made-up"}, "family": FAMILY,
           "health_ready": {"device_scopes": MAPS}}
    got = {m: manifest.layer_reader(m).read(ctx) for m in (
        "decode_kv_pool_share", "decode_attention_share", "decode_ffn_share")}
    assert got == pytest.approx({
        "decode_kv_pool_share": 100 * 7 / 30,
        "decode_attention_share": 100 * 4 / 30,
        "decode_ffn_share": 100 * 18 / 30})
    assert sum(got.values()) <= 100.0
    # an older program reports no maps; a run without a trace has no file
    for bare in (dict(ctx, health_ready={}), dict(ctx, trace={})):
        assert all(manifest.layer_reader(m).read(bare) is None for m in got)


def test_the_groups_cover_the_programs_scopes():
    """Every ``kv.*`` / attention / feed-forward scope the program declares
    is in a group; the rest (embed, projections, rope, head, sample) is what
    PERF.md names as the remainder."""
    from tpu9.models.transformer import DEVICE_SCOPES
    assert sorted(FAMILY.SCOPE_GROUPS) == ["attention", "ffn", "kv_pool"]
    grouped = {s for names in FAMILY.SCOPE_GROUPS.values() for s in names}
    assert grouped <= set(DEVICE_SCOPES)
    assert set(DEVICE_SCOPES) - grouped == {
        "embed", "attn.qkv", "attn.rope", "attn.out", "head", "sample"}
