"""``BENCHMARK.json`` against the contract, and against the files its names
stand for: every name resolves to a file, and a cell, a configuration, a mix
or a per-layer metric can be added as files, editing none that is there."""

import json
import os
import re
import shutil

import pytest

from benchmark import manifest

M = manifest.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_size$|_dim$|_rank$|_width$|expansion|experts_per_tok)")
CELLS = [w["name"] for w in M["workloads"]]
E2E = {m["name"]: m for m in M["end_to_end"]}


def test_top_level_keys_are_exactly_the_contracts():
    assert sorted(M) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["paths"]) <= 16 and len(M["command"]) <= 32
    assert 1 <= len(M["workloads"]) <= 24 and 1 <= len(M["configs"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128


def test_the_command_names_only_files_under_paths():
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in M["paths"])
            assert os.path.exists(os.path.join(manifest.ROOT, word))


def test_the_full_check_fits_with_24_cells():
    rs = M["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("group,keys,optional", [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}, {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"})])
def test_entries_have_just_the_keys_shown(group, keys, optional):
    names = [e["name"] for e in M[group]]
    assert len(set(names)) == len(names)
    for entry in M[group]:
        assert keys <= set(entry) <= keys | optional, entry["name"]
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                    and "\t" not in entry[key]


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric["name"] in E2E:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["name"] not in E2E


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_configuration_files(config):
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    body = manifest.load_config(M, config["name"])
    assert body["source"] == config["source"]
    assert config["source"].startswith("https://")
    assert sorted(config["reduced"]) == sorted(body["reduced"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert any(w["config"] == config["name"] for w in M["workloads"])
    assert [c["file"] for c in M["configs"]].count(config["file"]) == 1
    assert os.path.exists(os.path.join(
        manifest.HERE, "reference", body["reference"] + ".py"))
    assert body["correct_tolerance_logit"] > 0 and body["correct_tolerance_why"]
    for key in ("topology", "max_batch", "max_seq_len", "kv_block_size",
                "prefill_chunk", "kv_pool_blocks", "prefix_cache_blocks",
                "decode_steps", "admit_group_chunks"):
        assert key in body["engine"], key
    assert body["endpoint"]["tpu"].startswith("v5e-")


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files_and_reports_enough(cell):
    assert cell["chips"] in (1, 4)
    for key in ("config", "traffic"):
        assert NAME.match(cell[key])
    manifest.config_entry(M, cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    kind = manifest.traffic_kind(traffic["kind"])
    assert callable(kind.plan)
    body = manifest.load_config(M, cell["config"])
    assert body["endpoint"]["tpu"] == f"v5e-{cell['chips']}"
    e2e = [m["name"] for m in manifest.cell_metrics(M, cell["name"], "end_to_end")]
    layer = manifest.cell_metrics(M, cell["name"], "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and len(layer) >= 1
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_reader_and_moves_a_metric_its_cells_report(metric):
    assert os.path.exists(manifest.layer_reader_path(metric["name"]))
    assert callable(manifest.layer_reader(metric["name"]).read)
    assert metric["moves"] in E2E
    for cell in CELLS:
        if manifest.reports(metric, cell):
            assert manifest.reports(E2E[metric["moves"]], cell), \
                f"{cell} reports {metric['name']} but not {metric['moves']}"


def test_one_layer_one_spelling():
    layers = {m["layer"] for m in M["per_layer"]}
    assert len({x.lower().replace(" ", "") for x in layers}) == len(layers)


def test_four_chip_cells_stay_within_their_share():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)


def test_paths_hold_the_benchmark_and_its_files_are_well_named():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in M["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        for dirpath, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                assert ok.match(os.path.relpath(os.path.join(dirpath, name),
                                                manifest.ROOT)), name


def test_no_harness_code_names_a_cell_a_configuration_or_a_mix():
    names = set(CELLS) | {c["name"] for c in M["configs"]} | \
        {w["traffic"] for w in M["workloads"]}
    for dirpath, dirs, files in os.walk(manifest.HERE):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "out", "tools")]
        for name in files:
            if not name.endswith(".py"):
                continue
            if dirpath.endswith("layer_metrics"):
                continue    # one metric's own file may be about one cell
            with open(os.path.join(dirpath, name)) as f:
                text = f.read()
            for cell in names:
                assert cell not in text, f"{name} names {cell}"


def test_a_cell_is_added_by_files_alone(tmp_path, monkeypatch):
    """A later PR's cell: a new mix, a new per-layer metric and entries in
    BENCHMARK.json; no file that is there is edited."""
    here = tmp_path / "benchmark"
    shutil.copytree(manifest.HERE, here, ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    mix = manifest.load_traffic(M["workloads"][0]["traffic"])
    mix["rate_rps"] = 1.0
    (here / "traffic" / "later-mix.json").write_text(json.dumps(mix))
    (here / "layer_metrics" / "ttft_p90_ms.later-cell.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    grown = json.loads(json.dumps(M))
    grown["workloads"].append({"name": "later-cell",
                               "config": M["workloads"][0]["config"],
                               "traffic": "later-mix", "chips": 1, "why": "x"})
    grown["per_layer"].append({"name": "ttft_p90_ms.later-cell", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "client", "moves": "tpot_p50_ms",
                               "workloads": ["later-cell"]})
    monkeypatch.setattr(manifest, "HERE", str(here))
    cell = manifest.cell(grown, "later-cell")
    traffic = manifest.load_traffic(cell["traffic"])
    plan = manifest.traffic_kind(traffic["kind"]).plan(traffic, 1, 10, 32000)
    assert len(plan["requests"]) == 10
    names = [m["name"] for m in manifest.cell_metrics(grown, "later-cell",
                                                      "per_layer")]
    assert "ttft_p90_ms.later-cell" in names
    assert manifest.layer_reader("ttft_p90_ms.later-cell").read({}) == 42.0
    assert all(p.read_bytes() == data for p, data in before.items())
