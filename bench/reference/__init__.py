"""Teacher-forced reference logits for served requests, layer by layer.

``readings`` runs a family's plain float32 model (its ``embed``, ``block``
and ``head_logits``, built on ``model.py``) over each request's prompt and
served tokens, regenerating one layer's weights at a time from the seed,
and returns per served position what the comparison needs: the
reference's best logit, its argmax, and its logit of the token the
program served.  Given control precisions, the same pass also runs each
control and returns the reference's logit of the token the control puts
first.

On several chips each regenerated layer, and the outer weights, are made
spread over the chips, each matrix along its output axis (the family's
``OUT_AXES``), and the hidden states stay whole on every chip, so no chip
holds more than its share of a layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import weights
from reference import model


def _placed(fam, make, mesh, *args):
    """``make`` jitted so that each matrix it returns for ``args`` is
    spread over ``mesh`` along its output axis, where that axis divides."""
    if mesh is None:
        return jax.jit(make)
    n = mesh.devices.size

    def where(path, leaf):
        axis = fam.OUT_AXES.get(getattr(path[-1], "key", None))
        spec = [None] * leaf.ndim
        if axis is not None and leaf.shape[axis] % n == 0:
            spec[axis] = "c"
        return NamedSharding(mesh, P(*spec))
    return jax.jit(make, out_shardings=jax.tree_util.tree_map_with_path(
        where, jax.eval_shape(make, *args)))


@functools.lru_cache(maxsize=None)
def _compiled(fam, dims, chips: int):
    mesh = None
    whole = {}
    if chips > 1:
        mesh = Mesh(np.asarray(jax.devices()[:chips]), ("c",))
        whole = {"out_shardings": NamedSharding(mesh, P())}
    key = jax.random.PRNGKey(0)
    layer = _placed(fam, lambda key, l: fam.layer_weights(key, l, dims),
                    mesh, key, jnp.int32(0))
    outer = _placed(fam, lambda key: fam.outer_weights(key, dims), mesh,
                    key)

    @functools.partial(jax.jit, **whole)
    def embed(outer_w, tokens):
        return fam.embed(outer_w, tokens)

    @functools.partial(jax.jit, static_argnames=("prec",), **whole)
    def run_block(x, w, prec):
        return fam.block(x, w, dims, dict(prec))

    @functools.partial(jax.jit, static_argnames=("prec",), **whole)
    def logits(x, outer_w, prec):
        return fam.head_logits(x, outer_w, dims, dict(prec))

    return layer, outer, embed, run_block, logits


def _frozen(prec: dict):
    return tuple(sorted(prec.items()))


def readings(fam, dims, seed: int, prompts: np.ndarray, served: np.ndarray,
             controls=(), row_block: int = 4, chips: int = 1) -> dict:
    """prompts (n, S) and served (n, N) token ids -> numpy arrays (n, N):
    ``best``, ``argmax``, ``at_served``, and ``at_control[i]`` for each
    control precision in ``controls``."""
    layer, outer, embed, run_block, logits = _compiled(fam, dims, chips)
    key = weights.base_key(seed)
    n, S = prompts.shape
    N = served.shape[1]
    seqs = jnp.asarray(np.concatenate([prompts, served[:, :-1]], 1),
                       jnp.int32)
    outer_w = outer(key)
    precs = [_frozen(model.F32)] + [_frozen(c) for c in controls]
    rows = [slice(i, min(i + row_block, n)) for i in range(0, n, row_block)]
    xs = [[embed(outer_w, seqs[r]) for r in rows] for _ in precs]
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
