"""``BENCHMARK.json`` and the files its names stand for. Everything that
belongs to one configuration, one traffic mix or one per-layer metric sits in
a file of its own, found here by name: adding a cell edits no file that is
there.

    configuration <c>   benchmark/configs/<c>.json      (its ``file``)
    its family          benchmark/families/<f>.py       (named in the file)
    its reference       benchmark/reference/<r>.py      (named in the file)
    traffic mix <t>     benchmark/traffic/<t>.json
    its generator kind  benchmark/traffic/kinds/<k>.py  (named in the file)
    per-layer metric m  benchmark/layer_metrics/<m>.py
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# what a family module gives; nothing else in the harness knows an architecture
FAMILY_GIVES = ("model_sizes", "program_config", "decode_bytes_per_step",
                "prefill_flops_per_token", "kernel_cost", "STEP_MARKER",
                "marker_calls_per_step", "SCOPE_GROUPS")
# keys of a configuration file that are the harness's own, not the source's
HARNESS_KEYS = ("source", "family", "reference", "reduced", "assumed",
                "deployment", "correct_tolerance_logit",
                "correct_tolerance_why", "engine", "endpoint")


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


def module(*path: str):
    """The module in the file ``benchmark/<path>.py``. By path and not by
    import: a name may hold ``.`` and ``-``, which a module name may not, and
    the file is looked for under ``HERE`` as it stands now. It is named as an
    import would name it, so that a relative import inside it finds the
    benchmark's packages."""
    file = os.path.join(HERE, *path) + ".py"
    if not os.path.exists(file):
        raise KeyError(f"no {'/'.join(path)}.py under benchmark/")
    spec = importlib.util.spec_from_file_location(
        ".".join(("benchmark",) + path), file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str):
    return module("traffic", "kinds", kind)


def family(config: dict):
    """The family module a configuration file names: all that the harness
    knows about its architecture. No ``family`` key is an error, not a
    default."""
    if "family" not in config:
        raise KeyError("the configuration file names no \"family\" "
                       "(benchmark/families/<f>.py)")
    mod = module("families", config["family"])
    lacks = [name for name in FAMILY_GIVES if not hasattr(mod, name)]
    if lacks:
        raise KeyError(f"families/{config['family']}.py lacks {lacks}")
    return mod


def reports(metric: dict, cell_name: str) -> bool:
    """Whether ``cell_name`` reports ``metric`` (no ``workloads`` key: every
    cell does)."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell_metrics(manifest: dict, cell_name: str, group: str) -> list:
    return [m for m in manifest[group] if reports(m, cell_name)]


def layer_reader_path(name: str) -> str:
    return os.path.join(HERE, "layer_metrics", f"{name}.py")


def layer_reader(name: str):
    """The reader module of one per-layer metric."""
    return module("layer_metrics", name)
