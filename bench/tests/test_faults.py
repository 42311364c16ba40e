"""A run with its timed path broken underneath comes out not correct.

Each test drives the rest of a run on the CPU at a tiny size (everything
after the look for a chip), once sound and once with one fault planted in
the serving program: a token altered where it is produced, and, in aged
cells, the accumulator upsets left out.  Faults of training (a state left
unchanged, half a batch left out) and of several chips (an exchange left
out) cannot occur in these one-chip serving cells.
"""
import time

import numpy as np
import pytest

import harness
import program
from repro.models import layers
from repro.serve import engine as serve_engine

HIGH_BER = 1e-3     # enough upsets at 64 wide that every token is hit


class HighBers:
    """The tiny cell's aged device: every operator at HIGH_BER."""

    def __init__(self, ops):
        self.ops = ops

    def op_bers(self):
        return {op: HIGH_BER for op in self.ops}

    age_years = 9.0

    def total_power(self):
        return 1.0


def run(cell, tmp_path, seed=11):
    return harness.run_cell(cell, seed, 1.5, False, time.perf_counter(),
                            str(tmp_path), log=lambda s: None)


def aged_cell(cell_factory, monkeypatch):
    cell = cell_factory("starcoder2", "chat.aged9y")
    ops = list(cell["traffic"]["device"]["ber"])
    cell["traffic"]["device"]["ber"] = {op: HIGH_BER for op in ops}
    monkeypatch.setattr(program, "aged_runtime", lambda dev: HighBers(ops))
    return cell


def alter_one_token(monkeypatch):
    real = serve_engine.ServeEngine.generate

    def generate(self, prompts, n_steps, **kw):
        res = real(self, prompts, n_steps, **kw)
        res.tokens = res.tokens.copy()
        res.tokens[:, -1] = (res.tokens[:, -1] + 1) % self.cfg.vocab
        return res
    monkeypatch.setattr(serve_engine.ServeEngine, "generate", generate)


def test_clean_sound_then_token_altered(cell_factory, monkeypatch, tmp_path):
    cell = cell_factory("llama", "chat.clean")
    sound = run(cell, tmp_path)
    assert sound["correct"], sound["checks"]
    alter_one_token(monkeypatch)
    bad = run(cell, tmp_path)
    assert not bad["correct"]
    assert bad["checks"]["gap"]["value"] > bad["checks"]["gap"]["limit"]


def test_aged_sound(cell_factory, monkeypatch, tmp_path):
    r = run(aged_cell(cell_factory, monkeypatch), tmp_path)
    assert r["correct"], r["checks"]
    assert r["checks"]["faulted_top1"]["value"] < 0.2


def test_aged_token_altered(cell_factory, monkeypatch, tmp_path):
    cell = aged_cell(cell_factory, monkeypatch)
    alter_one_token(monkeypatch)
    r = run(cell, tmp_path)
    assert not r["correct"]
    assert r["checks"]["gap"]["value"] > r["checks"]["gap"]["limit"]


def test_aged_upsets_left_out(cell_factory, monkeypatch, tmp_path):
    cell = aged_cell(cell_factory, monkeypatch)
    monkeypatch.setattr(layers.FaultConfig, "ber_for",
                        lambda self, op: np.float32(0.0))
    serve_engine.clear_caches()          # retrace with the fault planted
    r = run(cell, tmp_path)
    serve_engine.clear_caches()
    assert not r["correct"]
    c = r["checks"]["faulted_top1"]
    assert c["value"] > c["limit"]


def test_aged_wrong_bers(cell_factory, monkeypatch, tmp_path):
    cell = aged_cell(cell_factory, monkeypatch)
    cell["traffic"]["device"]["ber"] = {
        op: HIGH_BER * 10 for op in cell["traffic"]["device"]["ber"]}
    r = run(cell, tmp_path)
    assert not r["correct"]
    assert r["checks"]["ber_decades"]["value"] == pytest.approx(1.0)


def test_compile_in_window_is_caught(cell_factory, monkeypatch, tmp_path):
    cell = cell_factory("llama", "chat.clean")
    real = harness.window

    def window(engine, *a, **kw):
        serve_engine.clear_caches()      # every call in the window retraces
        return real(engine, *a, **kw)
    monkeypatch.setattr(harness, "window", window)
    r = run(cell, tmp_path)
    assert not r["correct"] and r["checks"]["window_compiles"]["value"] > 0



SMALL_LLAMA = {"model_type": "llama", "hidden_act": "silu",
               "hidden_size": 512, "intermediate_size": 1024,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 4,
               "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
               "vocab_size": 8192}


def control_cell(cell_factory, monkeypatch, kind):
    """A cell at a size where its control parts from the program, with
    limits between the two readings there (CPU, seeds 1-3, one call of
    8 x 16 + 16): clean, bf16 against int8 at 512 wide (program gap <=
    0.017, mean <= 0.0002; control >= 0.057, >= 0.00096); aged, int8
    against int4 at 64 wide (see ``AGED_LIMITS``)."""
    if kind == "clean":
        cell = cell_factory("llama", "chat.clean")
        cell["config"] = dict(SMALL_LLAMA)
        cell["limits"] = {"gap": 0.03, "mean_gap": 0.0005}
    else:
        cell = aged_cell(cell_factory, monkeypatch)
    cell["traffic"].update(batch=8, prompt_tokens=16, new_tokens=16,
                           check_requests=8)
    return cell


@pytest.mark.parametrize("kind", ["clean", "aged"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(cell_factory, monkeypatch, tmp_path,
                                       kind, seed):
    """The reference one precision below the cell's, put in the program's
    place and held to the cell's limits by the harness's own comparison.
    The window is one call (the loop always makes the first), so the
    sample, and each reading, is fixed by the seed."""
    import control
    cell = control_cell(cell_factory, monkeypatch, kind)
    r = harness.run_cell(cell, seed, 1e-3, False, time.perf_counter(),
                         str(tmp_path), log=lambda s: None,
                         controls=[control.control_precision(cell["traffic"])])
    exact = {k: (r["checks"][k]["value"], r["checks"][k]["limit"])
             for k in ("gap", "mean_gap")}
    assert harness.verdict(exact), exact
    assert not r["controls"][0]["correct"], r["controls"][0]
