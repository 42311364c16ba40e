"""The architecture seam: a configuration's ``model_type`` names the family
module that makes its weights, its reference and the program's
configuration, and a new architecture is a new file."""
import hashlib
import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

import cells
import harness
import reference
import weights
from conftest import TINY

BENCH = json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))
HASHES = json.load(open(os.path.join(os.path.dirname(__file__), "data",
                                     "family_hashes.json")))
BUILD_SEED, READ_SEED = 2 ** 31 + 3, 7
CONTROL = {"linear": 4, "attn": 4, "head": 4}


def tree_hash(tree) -> str:
    """Every leaf's path, type, shape and bytes, in path order."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def readings_hash(r: dict) -> str:
    h = hashlib.sha256()
    named = [(k, r[k]) for k in ("best", "argmax", "at_served")]
    for name, a in named + [("control", a) for a in r["at_control"]]:
        a = np.asarray(a)
        h.update(f"{name}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations_resolve_through_the_seam(c):
    conf = json.load(open(os.path.join(cells.ROOT, c["file"])))
    fam = cells.family(conf)
    assert os.path.basename(fam.__file__) == conf["model_type"] + ".py"
    dims = fam.read_dims(c["name"], conf)
    assert dims.n_layers == conf["num_hidden_layers"]
    assert fam.model_config(dims).n_layers == dims.n_layers
    assert cells.family(conf) is fam        # loaded once


@pytest.mark.parametrize("family", sorted(TINY))
def test_weights_and_readings_are_the_parents(family):
    """The tree and the reference's readings hash as they did before the
    seam (``data/family_hashes.json``, recorded from the functions it
    replaced)."""
    fam = cells.family(TINY[family])
    dims = fam.read_dims(family, TINY[family])
    assert tree_hash(weights.build_params(fam, dims, BUILD_SEED)) == \
        HASHES[family]["params"]
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, dims.vocab, (3, 10)).astype(np.int32)
    served = rng.integers(0, dims.vocab, (3, 6)).astype(np.int32)
    r = reference.readings(fam, dims, READ_SEED, prompts, served,
                           controls=[CONTROL], row_block=2)
    assert readings_hash(r) == HASHES[family]["readings"]


def test_new_family_is_a_new_file(cell_factory, monkeypatch, tmp_path):
    """A family written beside the others under a model_type of its own
    serves a cell through ``run_cell``; no file there is edited."""
    families = tmp_path / "families"
    families.mkdir()
    shutil.copy(os.path.join(cells.FAMILIES, "decoder.py"),
                families / "tiny_decoder.py")
    monkeypatch.setattr(cells, "FAMILIES", str(families))
    cell = cell_factory("llama", "chat.clean")
    cell["config"]["model_type"] = "tiny_decoder"
    r = harness.run_cell(cell, 5, 1.0, False, time.perf_counter(),
                         str(tmp_path), log=lambda s: None)
    assert r["correct"], r["checks"]
    fam = cells.family(cell["config"])
    assert fam.__file__ == str(families / "tiny_decoder.py")


def test_unknown_model_type_names_the_known_ones():
    with pytest.raises(KeyError) as e:
        cells.family({"model_type": "no_such_family"})
    for known in ("decoder", "llama", "starcoder2"):
        assert known in str(e.value)
    with pytest.raises(KeyError, match="llama"):
        cells.family({})
