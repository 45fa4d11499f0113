"""``BENCHMARK.json`` against the contract, and against the files its names
stand for: every name resolves to a file, and a cell, a configuration, a mix
or a per-layer metric can be added as files, editing none that is there."""

import json
import os
import re
import shutil

import pytest

from benchmark import correctness, device_scopes, host_phases, manifest, trace

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
    assert NAME.match(body["family"]) and os.path.exists(os.path.join(
        manifest.HERE, "families", body["family"] + ".py"))
    family = manifest.family(body)
    for name in ("model_sizes", "program_config", "decode_bytes_per_step",
                 "prefill_flops_per_token", "kernel_cost",
                 "marker_calls_per_step"):
        assert callable(getattr(family, name)), name
    assert isinstance(family.STEP_MARKER, str) and family.STEP_MARKER
    assert sorted(family.SCOPE_GROUPS) == ["attention", "ffn", "kv_pool"]
    assert family.marker_calls_per_step(family.model_sizes(body)) >= 1
    assert set(manifest.HARNESS_KEYS) <= set(body)
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


LATER_FAMILY = '''"""A made-up architecture: the decoder's block with the gates of the chosen
experts left as the router gave them, a token exchange before the experts
under a scope of its own, and an attention kernel of its own in every other
layer."""
from benchmark.families import decoder

STEP_MARKER = "latent_decode_attention"
SCOPE_GROUPS = dict(decoder.SCOPE_GROUPS,
                    ffn=decoder.SCOPE_GROUPS["ffn"] + ("moe.dispatch",))
decode_bytes_per_step = decoder.decode_bytes_per_step
prefill_flops_per_token = decoder.prefill_flops_per_token


def model_sizes(config):
    if config.get("norm_topk_prob") is not False:
        raise ValueError("norm_topk_prob: this family builds only False")
    rest = {k: v for k, v in config.items() if k != "norm_topk_prob"}
    return dict(decoder.model_sizes(rest), norm_topk_prob=False)


def program_config(model):
    return {"decoder": decoder.program_config(model),
            "renormalise_gates": model["norm_topk_prob"]}


def marker_calls_per_step(model):
    return model["num_hidden_layers"] // 2


def kernel_cost(kernel, model, engine, batch, resident_context):
    if kernel == STEP_MARKER:
        rows = marker_calls_per_step(model) * resident_context
        return {"bytes": 512.0 * rows, "flops": 4.0 * 512 * rows}
    return decoder.kernel_cost(kernel, model, engine, batch, resident_context)
'''
LATER_REFERENCE = '''"""The made-up architecture's plain reference (here: the decoder's)."""
from benchmark.reference import decoder


def forward(params, tokens, model):
    return decoder.forward(params, tokens, model)
'''
LATER_READER = '''"""kernels: bytes a step needs of the made-up kernel over its seconds."""
from benchmark import readers

KERNEL = "latent_decode_attention"


def read(ctx):
    took = readers.kernel_seconds_per_step(ctx, KERNEL)
    cost = ctx["family"].kernel_cost(KERNEL, ctx["model"], ctx["engine"],
                                     4, 1000.0)
    return cost["bytes"] / took if took and cost else None
'''


def add_a_configuration_of_another_family(root, family):
    """What a later ``model_config`` PR adds, in a copy of the benchmark
    under ``root``: a family, a reference, a configuration with its
    rehearsal sizes, a mix, a per-layer reader and the entries. Returns
    (the grown manifest, every file that was there with its bytes)."""
    here = root / "benchmark"
    shutil.copytree(manifest.HERE, here, ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    first = M["configs"][0]
    body = manifest.load_config(M, first["name"])
    body.update(family=family, reference="later_reference",
                norm_topk_prob=False)
    (here / "families" / "later_family.py").write_text(LATER_FAMILY)
    (here / "reference" / "later_reference.py").write_text(LATER_REFERENCE)
    (here / "configs" / "later-model.json").write_text(json.dumps(body))
    shutil.copy(here / "rehearsal" / (first["name"] + ".json"),
                here / "rehearsal" / "later-model.json")
    mix = manifest.load_traffic(M["workloads"][0]["traffic"])
    (here / "traffic" / "later-mix.json").write_text(json.dumps(mix))
    (here / "layer_metrics" / "latent_attn_bytes_per_s.py").write_text(
        LATER_READER)
    grown = json.loads(json.dumps(M))
    grown["configs"].append(dict(first, name="later-model",
                                 file="benchmark/configs/later-model.json"))
    grown["workloads"].append({"name": "later-cell", "config": "later-model",
                               "traffic": "later-mix", "chips": 1, "why": "x"})
    grown["per_layer"].append({"name": "latent_attn_bytes_per_s",
                               "unit": "B/s", "better": "higher",
                               "source": "device_trace", "layer": "kernels",
                               "moves": "tpot_p50_ms",
                               "workloads": ["later-cell"]})
    return grown, before


def later_planes():
    """One chip, a model of four layers whose every other layer calls the
    made-up kernel: a K=2 decode run (4 calls) and a K=1 run (2 calls)."""
    ms = 1e6
    starts = (0, 1, 2, 3, 6, 7)
    kernel = "%latent_decode_attention.3 = bf16[4,8,128]{2,1,0} custom-call(%q)"
    ops = [(kernel, t * ms, 0.5 * ms) for t in starts]
    ops += [("%fusion.9 = bf16[4,4096]{1,0} fusion(%x)", t * ms + 0.5 * ms,
             0.25 * ms) for t in starts]
    mods = [("jit_decode(1)", 0.0, 4 * ms), ("jit_decode(2)", 6 * ms, 2 * ms)]
    return [{"name": "/device:TPU:0",
             "lines": {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}}]


def test_a_configuration_of_another_family_is_added_by_files_alone(
        tmp_path, monkeypatch):
    """A later PR's configuration of an architecture the decoder family does
    not build: new files and entries, walked from the manifest to the step
    count; no file that is there is edited."""
    grown, before = add_a_configuration_of_another_family(tmp_path,
                                                         "later_family")
    monkeypatch.setattr(manifest, "HERE", str(tmp_path / "benchmark"))
    cell = manifest.cell(grown, "later-cell")
    config = manifest.load_config(grown, cell["config"], root=str(tmp_path))
    # the family: sizes with the key the decoder family refuses, the
    # program's config, at the published and at the rehearsal sizes
    family = manifest.family(config)
    assert family.__file__.startswith(str(tmp_path))
    model = family.model_sizes(config)
    assert model["norm_topk_prob"] is False and model["hidden_size"] == 4096
    program = family.program_config(model)
    assert program["renormalise_gates"] is False
    assert program["decoder"].dim == 4096
    with open(os.path.join(manifest.HERE, "rehearsal", "later-model.json")) as f:
        tiny = dict(config, **json.load(f)["model"])
    assert family.program_config(family.model_sizes(tiny))["decoder"].dim == 128
    # the reference and the mix
    assert callable(correctness.load_reference(config["reference"]).forward)
    traffic = manifest.load_traffic(cell["traffic"])
    plan = manifest.traffic_kind(traffic["kind"]).plan(traffic, 1, 10,
                                                       model["vocab_size"])
    assert plan["requests"]
    # the step count, by the family's marker: 4 + 2 calls, 2 to a step
    assert family.marker_calls_per_step(model) == 2
    reduced = trace.reduce_planes(later_planes(), family.STEP_MARKER,
                                  family.marker_calls_per_step(model))
    assert reduced["programs"]["jit_decode"]["steps"] == 3
    assert reduced["decode_step_ms"] == pytest.approx(2.0)
    # the decoder family's marker finds no step in this trace
    other = manifest.family(manifest.load_config(M, M["configs"][0]["name"]))
    assert trace.reduce_planes(later_planes(), other.STEP_MARKER, 4)[
        "decode_step_ms"] is None
    # the reader, through the family's cost function: 3 ms of the kernel
    # over 3 steps; 2 calls x 1000 tokens x 512 B a step
    names = [m["name"] for m in manifest.cell_metrics(grown, "later-cell",
                                                      "per_layer")]
    assert "latent_attn_bytes_per_s" in names
    ctx = {"trace": dict(reduced, file="made-up-later"), "family": family,
           "model": model, "engine": config["engine"],
           "health_ready": {"device_scopes": {"decode_1": {
               "attn.core": ["latent_decode_attention.3"],
               "moe.dispatch": ["fusion.9"]}}}}
    assert manifest.layer_reader("latent_attn_bytes_per_s").read(ctx) == \
        pytest.approx(2 * 1000 * 512 / 1e-3)
    # the scope groups: the new scope counts under ``ffn`` for this family
    # and falls out of it for the decoder family
    monkeypatch.setattr(device_scopes, "_seconds", {})
    plane = later_planes()[0]["lines"]
    monkeypatch.setitem(host_phases._loaded, "made-up-later", {
        "phases": None, "ops": plane[trace.OPS_LINE],
        "modules": plane[trace.MODULES_LINE]})
    assert device_scopes.share(ctx, "ffn") == pytest.approx(100 * 1.5 / 4.5)
    assert device_scopes.share(ctx, "attention") == pytest.approx(100 * 3 / 4.5)
    assert device_scopes.share(dict(ctx, family=other), "ffn") == 0.0
    assert all(p.read_bytes() == data for p, data in before.items())


def test_the_decoder_family_refuses_the_other_architectures_key(
        tmp_path, monkeypatch):
    """The same files under ``"family": "decoder"``: refused, not built as
    the nearest thing the decoder family knows."""
    grown, _ = add_a_configuration_of_another_family(tmp_path, "decoder")
    monkeypatch.setattr(manifest, "HERE", str(tmp_path / "benchmark"))
    config = manifest.load_config(grown, "later-model", root=str(tmp_path))
    with pytest.raises(ValueError, match="norm_topk_prob"):
        manifest.family(config).model_sizes(config)


@pytest.mark.parametrize("config,lacks", [
    ({}, "family"), ({"family": "no_such_family"}, "no_such_family")])
def test_a_configuration_without_a_family_is_an_error(config, lacks):
    with pytest.raises(KeyError, match=lacks):
        manifest.family(config)


def test_a_family_that_lacks_a_name_is_an_error(tmp_path, monkeypatch):
    here = tmp_path / "benchmark"
    (here / "families").mkdir(parents=True)
    (here / "families" / "half.py").write_text("STEP_MARKER = 'k'\n")
    monkeypatch.setattr(manifest, "HERE", str(here))
    with pytest.raises(KeyError, match="SCOPE_GROUPS"):
        manifest.family({"family": "half"})
