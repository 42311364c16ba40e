"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration is the file its entry names, and its architecture is
``families/<model_type>.py``, by the file's own Hugging Face
``model_type``; a traffic mix is ``traffic/<traffic>.json``; a cell's
limits for the comparison with the reference are
``limits/<workload>.json``; a per-layer metric is read by
``metrics/<name>.py``.  Adding a cell, an architecture or a metric adds
files and entries and edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FAMILIES = os.path.join(HERE, "families")


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


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # a dataclass in the module looks its own module up while it is made
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _load(os.path.join(HERE, "metrics", name + ".py"),
                 f"metric_{name}").read


def family(conf: dict):
    """The module of ``families/<model_type>.py`` for a configuration."""
    kind = conf.get("model_type")
    path = os.path.join(FAMILIES, f"{kind}.py")
    if not (isinstance(kind, str) and os.path.isfile(path)):
        known = sorted(f[:-3] for f in os.listdir(FAMILIES)
                       if f.endswith(".py"))
        raise KeyError(f"no architecture family for model_type {kind!r} in "
                       f"{FAMILIES}; there are {known}")
    return _family_at(path, f"family_{kind}")


@functools.lru_cache(maxsize=None)
def _family_at(path: str, name: str):
    # once per file, so what is compiled for a family is found again
    return _load(path, name)
