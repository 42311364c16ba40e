"""A configuration file's published keys, read into the sizes the yardstick
uses: the weight generator, the reference, and the cost functions.

The file holds the model's own ``config.json`` keys (Hugging Face names),
as it is run.  Only decoder-only transformers with RoPE attention are
read here; a configuration of another kind brings its own reader.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

ACTIVATIONS = {"silu": "gated", "gelu_pytorch_tanh": "plain"}


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp: str                 # "gated" (SwiGLU) | "plain" (GELU, tanh form)
    norm: str                # "rms" | "ln"
    norm_eps: float
    rope_theta: float
    window: Optional[int]    # sliding-window attention span, None = full

    @property
    def linear_shapes(self):
        """(name, K, N) of every weight matmul of one layer."""
        d, hd = self.d_model, self.head_dim
        out = [("q", d, self.n_heads * hd), ("k", d, self.n_kv_heads * hd),
               ("v", d, self.n_kv_heads * hd), ("o", self.n_heads * hd, d)]
        if self.mlp == "gated":
            out.append(("gate", d, self.d_ff))
        out += [("up", d, self.d_ff), ("down", self.d_ff, d)]
        return out


def read_dims(name: str, conf: dict) -> Dims:
    act = conf["hidden_act"]
    if act not in ACTIVATIONS:
        raise ValueError(f"{name}: hidden_act {act!r} has no reader")
    ln = conf.get("norm_type") == "layer_norm"
    h = conf["num_attention_heads"]
    return Dims(
        name=name,
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(h),
        n_kv_heads=int(conf.get("num_key_value_heads", h)),
        head_dim=int(conf.get("head_dim") or conf["hidden_size"] // h),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        mlp=ACTIVATIONS[act],
        norm="ln" if ln else "rms",
        norm_eps=float(conf["norm_epsilon"] if ln else conf["rms_norm_eps"]),
        rope_theta=float(conf.get("rope_theta", 10000.0)),
        window=conf.get("sliding_window"),
    )
