"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration is the file its entry names; a traffic mix is
``traffic/<traffic>.json``; a cell's limits for the comparison with the
reference are ``limits/<workload>.json``; a per-layer metric is read by
``metrics/<name>.py``.  Adding a cell or a metric adds files and entries
and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> dict:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {
        "workload": w,
        "config_name": conf["name"],
        "config": _json(os.path.join(root, conf["file"])),
        "traffic": _json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        "limits": _json(os.path.join(HERE, "limits", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
