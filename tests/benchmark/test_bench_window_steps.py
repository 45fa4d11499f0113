"""``decode_steps_per_window`` (ISSUE 65) on hand-made snapshots and on a
saved run's ``context.json``: the counters are the parent's too, so both
sides of a comparison read."""

import os

import pytest

from benchmark import manifest

NAME = "decode_steps_per_window"


def _ctx(before: dict, after: dict) -> dict:
    return {"health0": before, "health1": after}


@pytest.mark.parametrize("before,after,want", [
    # ling-reason's newest run of the parent (ISSUE 65's table): 999 steps
    # in 608 windows
    (dict(decode_steps=40, windows_processed=21),
     dict(decode_steps=1039, windows_processed=629), 999 / 608),
    # every window the largest bucket
    (dict(decode_steps=0, windows_processed=0),
     dict(decode_steps=800, windows_processed=100), 8.0),
    # nothing decoded inside the window: no number, not a zero
    (dict(decode_steps=16, windows_processed=2),
     dict(decode_steps=16, windows_processed=2), None),
    # a program without the counters says nothing and does not raise
    ({}, {}, None),
    (dict(windows_processed=3), dict(windows_processed=9), None),
], ids=["collapsed", "wide", "idle", "no-counters", "no-steps"])
def test_steps_over_windows(before, after, want):
    got = manifest.layer_reader(NAME).read(_ctx(before, after))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_manifest_names_it_for_every_cell():
    entry = [m for m in manifest.load()["per_layer"] if m["name"] == NAME]
    assert entry == [{"name": NAME, "unit": "steps", "better": "higher",
                      "source": "program_counter", "layer": "engine",
                      "moves": "tpot_p50_ms"}]
    assert os.path.exists(manifest.layer_reader_path(NAME))


def test_every_cell_reports_it():
    """No ``workloads`` key: every cell reports ``tpot_p50_ms``, which it
    moves, and every configuration's engine keeps the two counters."""
    m = manifest.load()
    for cell in m["workloads"]:
        assert NAME in {x["name"] for x in manifest.cell_metrics(
            m, cell["name"], "per_layer")}, cell["name"]
        assert "tpot_p50_ms" in {x["name"] for x in manifest.cell_metrics(
            m, cell["name"], "end_to_end")}, cell["name"]
