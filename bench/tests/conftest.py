"""Tiny cells on the CPU: the benchmark's code paths at sizes a test run
can hold.  Run by hand: ``python -m pytest bench/tests``."""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

TINY = {
    "llama": {"model_type": "llama", "hidden_act": "silu", "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_hidden_layers": 2, "num_key_value_heads": 4,
              "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
              "vocab_size": 256},
    "starcoder2": {"model_type": "starcoder2",
                   "hidden_act": "gelu_pytorch_tanh", "hidden_size": 64,
                   "intermediate_size": 128, "num_attention_heads": 4,
                   "num_hidden_layers": 2, "num_key_value_heads": 2,
                   "norm_type": "layer_norm", "norm_epsilon": 1e-5,
                   "rope_theta": 1e6, "sliding_window": 12,
                   "vocab_size": 256},
}


def tiny_cell(family: str, traffic_name: str) -> dict:
    """A cell of the real traffic file, cut to 2 x 16 + 4 tokens."""
    with open(os.path.join(BENCH, "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    traffic.update(batch=2, prompt_tokens=16, new_tokens=4,
                   check_requests=4, check_faulted_requests=4)
    aged = traffic["device"]["route"] != "clean"
    limits = {"gap": 0.05, "mean_gap": 0.01}
    if aged:
        limits["faulted_top1"] = 0.5
    return {"workload": {"name": f"tiny.{traffic_name}", "chips": 1},
            "config_name": family, "config": copy.deepcopy(TINY[family]),
            "traffic": traffic, "limits": limits,
            "end_to_end": [{"name": "tok_s", "unit": "tokens/s"},
                           {"name": "req_p95_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


@pytest.fixture
def cell_factory():
    return tiny_cell
