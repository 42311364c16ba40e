"""Every BENCHMARK.json entry resolves to its files by name, and the files
say what the harness needs."""
import json
import os
import re
import subprocess
import sys

import pytest

import cells

BENCH = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(w):
    cell = cells.load_cell(w)
    dims = cells.family(cell["config"]).read_dims(cell["config_name"],
                                                  cell["config"])
    t = cell["traffic"]
    assert t["batch"] > 0 and t["prompt_tokens"] > 0 and t["new_tokens"] > 1
    assert dims.n_heads % dims.n_kv_heads == 0
    assert set(cell["limits"]) >= {"gap", "mean_gap"}
    if t["device"]["route"] != "clean":
        assert {"faulted_top1"} <= set(cell["limits"])
        assert t["fault_free_every"] >= 1 and t["device"]["ber"]
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
        assert m["moves"] in names


def test_names_and_entries():
    every = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in every)
    assert len(every) == len(set(every))
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"harness", "engine", "model step", "kernels", "device"}


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_reduced_keys_are_the_changed_ones(c):
    conf = json.load(open(os.path.join(cells.ROOT, c["file"])))
    changed = {k for k, v in conf["published"].items() if conf[k] != v}
    assert changed == set(c["reduced"])


def test_no_tpu_no_result():
    """On a CPU backend the command exits nonzero and prints nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(cells.HERE, "run.py"),
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
