"""The plain decoder-only family: a sequential pre-norm block with RoPE
attention (MHA or GQA, optionally within a sliding window) and a gated
(SwiGLU) or plain (GELU, tanh form) MLP, RMSNorm or LayerNorm, and an
untied lm_head.  DeepSeek LLM and StarCoder2 are of it (``llama.py`` and
``starcoder2.py`` re-export this module).

A family module is what the benchmark knows of one architecture: how a
configuration file's published keys become sizes (``read_dims``), the
seeded weights (``layer_weights``, ``outer_weights``), the plain float32
reference (``embed``, ``block``, ``head_logits``), how the reference lays
each weight over several chips (``OUT_AXES``), and the program's
``ModelConfig`` (``model_config``).  ``cells.family`` loads it by the
configuration's ``model_type``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

import weights
from reference import model

__all__ = ["Dims", "read_dims", "layer_weights", "outer_weights", "embed",
           "block", "head_logits", "model_config", "OUT_AXES"]

ACTIVATIONS = {"silu": "gated", "gelu_pytorch_tanh": "plain"}

# the axis of each weight that holds its output columns: the reference
# spreads a weight over several chips along it (norms stay whole)
OUT_AXES = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_gate": 1, "w_up": 1,
            "w_down": 1, "embed": 0, "lm_head": 1}


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


# --- weights: layer l draws from fold_in(fold_in(key, 1), l) alone, so a
# layer can be made on its own; norm scales are drawn near 1 and LayerNorm
# biases near 0, so a program that drops either is caught by the comparison

def _norm(key, dims: Dims, dtype):
    ks, kb = jax.random.split(key)
    p = {"scale": (1.0 + 0.1 * jax.random.normal(ks, (dims.d_model,),
                                                  jnp.float32)).astype(dtype)}
    if dims.norm == "ln":
        p["bias"] = (0.1 * jax.random.normal(kb, (dims.d_model,),
                                             jnp.float32)).astype(dtype)
    return p


def layer_weights(key: jax.Array, layer, dims: Dims,
                  dtype=weights.DTYPE) -> dict:
    """One transformer block, as the program's ``b0_attn`` entry holds it."""
    d, H, KV, hd, f = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                       dims.head_dim, dims.d_ff)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                             layer), 9)
    normal = lambda k, shape, s: jax.random.normal(k, shape, dtype) \
        * jnp.asarray(s, dtype)
    ffn = {"w_up": normal(ks[5], (d, f), d ** -0.5),
           "w_down": normal(ks[6], (f, d), f ** -0.5)}
    if dims.mlp == "gated":
        ffn["w_gate"] = normal(ks[4], (d, f), d ** -0.5)
    return {
        "norm1": _norm(ks[7], dims, dtype),
        "norm2": _norm(ks[8], dims, dtype),
        "attn": {"wq": normal(ks[0], (d, H, hd), d ** -0.5),
                 "wk": normal(ks[1], (d, KV, hd), d ** -0.5),
                 "wv": normal(ks[2], (d, KV, hd), d ** -0.5),
                 "wo": normal(ks[3], (H, hd, d), (H * hd) ** -0.5)},
        "ffn": ffn,
    }


def outer_weights(key: jax.Array, dims: Dims, dtype=weights.DTYPE) -> dict:
    """Embedding, final norm and lm_head."""
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 2), 3)
    d, V = dims.d_model, dims.vocab
    return {
        "embed": jax.random.normal(ke, (V, d), dtype),
        "final_norm": _norm(kn, dims, dtype),
        "lm_head": jax.random.normal(kh, (d, V), dtype)
        * jnp.asarray(d ** -0.5, dtype),
    }


# --- the reference: x += Wo attn(RoPE(Wq n1(x)), RoPE(Wk n1(x)), Wv n1(x));
# x += MLP(n2(x)); logits = lm_head nf(x)

def block(x, w, dims: Dims, prec):
    """One layer on hidden states x (n, T, d)."""
    n, T, d = x.shape
    H, KV, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    bits = prec["linear"]
    a = w["attn"]
    h = model.norm(x, w["norm1"], dims.norm, dims.norm_eps)
    q = model.matmul(h, a["wq"].reshape(d, H * hd), bits).reshape(
        n, T, H, hd)
    k = model.matmul(h, a["wk"].reshape(d, KV * hd), bits).reshape(
        n, T, KV, hd)
    v = model.matmul(h, a["wv"].reshape(d, KV * hd), bits).reshape(
        n, T, KV, hd)
    q, k = model.rope(q, dims.rope_theta), model.rope(k, dims.rope_theta)
    o = model.attention(q, k, v, dims.window, prec["attn"])
    x = x + model.matmul(o.reshape(n, T, H * hd),
                         a["wo"].reshape(H * hd, d), bits)
    h = model.norm(x, w["norm2"], dims.norm, dims.norm_eps)
    f = w["ffn"]
    if dims.mlp == "gated":
        u = jax.nn.silu(model.matmul(h, f["w_gate"], bits)) \
            * model.matmul(h, f["w_up"], bits)
    else:
        u = model.gelu_tanh(model.matmul(h, f["w_up"], bits))
    return x + model.matmul(u, f["w_down"], bits)


def embed(outer, tokens):
    return outer["embed"][tokens].astype(jnp.float32)


def head_logits(x, outer, dims: Dims, prec):
    """Final norm and lm_head: x (n, P, d) -> logits (n, P, V)."""
    h = model.norm(x, outer["final_norm"], dims.norm, dims.norm_eps)
    return model.matmul(h, outer["lm_head"], prec["head"])


def model_config(dims: Dims):
    """The serving program's configuration of this model."""
    import program
    return program.ModelConfig(
        name=dims.name, family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_ff=dims.d_ff, vocab=dims.vocab,
        head_dim=dims.head_dim, mlp=dims.mlp, norm=dims.norm, pos="rope",
        rope_theta=dims.rope_theta, window=dims.window)
