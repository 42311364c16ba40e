"""Teacher-forced reference logits for served requests, layer by layer.

``readings`` runs the plain float32 model (``model.py``) over each
request's prompt and served tokens, regenerating one layer's weights at a
time from the seed, and returns per served position what the comparison
needs: the reference's best logit, its argmax, and its logit of the token
the program served.  Given control precisions, the same pass also runs
each control and returns the reference's logit of the token the control
puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights
from reference import model


@functools.lru_cache(maxsize=None)
def _compiled(dims):
    layer = jax.jit(lambda key, l: weights.layer_weights(key, l, dims))
    outer = jax.jit(lambda key: weights.outer_weights(key, dims))

    @functools.partial(jax.jit, static_argnames=("prec",))
    def run_block(x, w, prec):
        return model.block(x, w, dims, dict(prec))

    @functools.partial(jax.jit, static_argnames=("prec",))
    def logits(x, outer_w, prec):
        return model.head_logits(x, outer_w, dims, dict(prec))

    return layer, outer, run_block, logits


def _frozen(prec: dict):
    return tuple(sorted(prec.items()))


def readings(dims, seed: int, prompts: np.ndarray, served: np.ndarray,
             controls=(), row_block: int = 4) -> dict:
    """prompts (n, S) and served (n, N) token ids -> numpy arrays (n, N):
    ``best``, ``argmax``, ``at_served``, and ``at_control[i]`` for each
    control precision in ``controls``."""
    layer, outer, run_block, logits = _compiled(dims)
    key = weights.base_key(seed)
    n, S = prompts.shape
    N = served.shape[1]
    seqs = jnp.asarray(np.concatenate([prompts, served[:, :-1]], 1),
                       jnp.int32)
    outer_w = outer(key)
    precs = [_frozen(model.F32)] + [_frozen(c) for c in controls]
    rows = [slice(i, min(i + row_block, n)) for i in range(0, n, row_block)]
    xs = [[model.embed(outer_w["embed"], seqs[r]) for r in rows]
          for _ in precs]
    for l in range(dims.n_layers):
        w = layer(key, jnp.int32(l))
        xs = [[run_block(x, w, p) for x in xp] for xp, p in zip(xs, precs)]
        del w
    # logits only where a served token is predicted: positions S-1 .. S+N-2
    out = {"best": [], "argmax": [], "at_served": [],
           "at_control": [[] for _ in controls]}
    for j, r in enumerate(rows):
        ref = logits(xs[0][j][:, S - 1:], outer_w, precs[0])
        tok = jnp.asarray(served[r], jnp.int32)
        out["best"].append(np.asarray(ref.max(-1)))
        out["argmax"].append(np.asarray(ref.argmax(-1)))
        out["at_served"].append(np.asarray(
            jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]))
        for c in range(len(controls)):
            top = logits(xs[c + 1][j][:, S - 1:], outer_w,
                         precs[c + 1]).argmax(-1)
            out["at_control"][c].append(np.asarray(
                jnp.take_along_axis(ref, top[..., None], -1)[..., 0]))
    cat = lambda parts: np.concatenate(parts, 0).astype(np.float64)
    return {"best": cat(out["best"]), "argmax": np.concatenate(out["argmax"]),
            "at_served": cat(out["at_served"]),
            "at_control": [cat(c) for c in out["at_control"]]}
