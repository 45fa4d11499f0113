"""``BENCHMARK.json`` and the files its names stand for. Everything that
belongs to one configuration, one traffic mix or one per-layer metric sits in
a file of its own, found here by name: adding a cell edits no file that is
there.

    configuration <c>   benchmark/configs/<c>.json      (its ``file``)
    its reference       benchmark/reference/<r>.py      (named in the file)
    traffic mix <t>     benchmark/traffic/<t>.json
    its generator kind  benchmark/traffic/kinds/<k>.py  (named in the file)
    per-layer metric m  benchmark/layer_metrics/<m>.py
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, config_entry(manifest, name)["file"])) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def traffic_kind(kind: str):
    return importlib.import_module(f"benchmark.traffic.kinds.{kind}")


def reports(metric: dict, cell_name: str) -> bool:
    """Whether ``cell_name`` reports ``metric`` (no ``workloads`` key: every
    cell does)."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell_metrics(manifest: dict, cell_name: str, group: str) -> list:
    return [m for m in manifest[group] if reports(m, cell_name)]


def layer_reader_path(name: str) -> str:
    return os.path.join(HERE, "layer_metrics", f"{name}.py")


def layer_reader(name: str):
    """The reader module of one per-layer metric. Loaded by path: a metric's
    name may hold ``.`` and ``-``, which a module name may not."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + "".join(
            ch if ch.isalnum() else "_" for ch in name),
        layer_reader_path(name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
