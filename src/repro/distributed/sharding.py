"""Name-based sharding rules: param pytree path -> PartitionSpec.

Mesh layout (``repro.launch.mesh``): ``("data", "model")`` single-pod,
``("pod", "data", "model")`` multi-pod.  Data parallelism shards the batch
over ``("pod", "data")``; tensor/expert parallelism shards weights over
``"model"``.

Rules are *right-aligned*: a base spec like ``(None, "model", None)`` for
``wq (d, H, hd)`` is padded with leading ``None`` so the same rule covers the
group-stacked form ``(n_groups, d, H, hd)`` produced by the layer scan.

Divisibility-aware fallbacks (recorded in DESIGN.md Sec. 5):

* attention heads ``H % tp != 0`` (arctic 56H, starcoder 36H, whisper 20H,
  paligemma 8H, recurrentgemma 10H): shard the *d_model contraction* side
  instead of the head axis (Megatron-style head sharding needs H % tp == 0);
* GQA ``KV < tp``: KV projections/cache are not KV-sharded — the decode KV
  cache is *sequence*-sharded over ``"model"`` (partial-softmax decode
  attention, the pjit-expressible analogue of ring decode);
* vocab ``V % tp != 0`` (whisper 51866): vocab-parallel head falls back to a
  contraction-sharded head.

Every ``d_ff`` and MoE expert count in the assigned pool divides tp = 16, so
FFN/expert sharding never falls back.

Two layouts share the rule machinery (``layout=`` on :func:`param_pspec`):

* ``"train"`` (default) — the Megatron-style rules above: row-parallel
  ``wo``/``w_down`` contract a sharded dim and rely on a psum, which
  reorders the float reduction.  Maximum-bandwidth, NOT bit-reproducible
  against a single device.
* ``"serve"`` — the exact-TP layout the mesh serving engine uses
  (DESIGN.md §Sharded-Serving): weights shard ONLY on output
  (non-contraction) dims — head axis for ``wq/wk/wv``, ``d_model`` for
  ``wo``/``w_down``, vocab for the (possibly tied) head, the expert axis
  for MoE — and every fallback *replicates* instead of contraction- or
  sequence-sharding.  Activations are pinned replicated over ``"model"``
  at op boundaries (:func:`constrain_replicated` under
  :func:`serve_mesh_scope`), so each shard computes full-contraction
  column slices and every collective is an all-gather: pure data
  movement, no float-reduction reorder.  Sharded generation is therefore
  bit-exact vs the single-device scanned path (locked down by
  ``tests/test_serve_sharded.py``; int8 x int8 -> int32 faulted
  accumulation is associative and stays exact under any split).
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig

MODEL_AXIS = "model"


def make_mesh(shape, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes.

    Every layout here relies on GSPMD propagation plus
    ``with_sharding_constraint``; ``jax.make_mesh``'s default ``Explicit``
    axes would instead demand an output sharding at every ambiguous gather.
    """
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the batch shards over (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def _tp(mesh: Mesh) -> int:
    return mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1


def _dp(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


# --------------------------------------------------------------------------- #
# parameter rules
# --------------------------------------------------------------------------- #
def _base_spec(name: str, base_ndim: int, cfg: ModelConfig, tp: int):
    """Right-aligned base PartitionSpec entries for one named parameter."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    head_ok = H > 0 and H % tp == 0
    kv_ok = KV > 0 and KV % tp == 0
    vocab_ok = cfg.vocab % tp == 0

    if name == "wq":
        return (None, MODEL_AXIS, None) if head_ok else (MODEL_AXIS, None, None)
    if name in ("wk", "wv"):
        if kv_ok:
            return (None, MODEL_AXIS, None)
        # KV < tp: keep KV whole; shard the d_model contraction side.
        return (MODEL_AXIS, None, None)
    if name == "wo":
        return (MODEL_AXIS, None, None) if head_ok else (None, None, MODEL_AXIS)
    if name in ("w_gate", "w_up"):
        if base_ndim == 3:                       # MoE expert-stacked (E, d, f)
            return (MODEL_AXIS, None, None)
        return (None, MODEL_AXIS)                # dense (d, f)
    if name == "w_down":
        if base_ndim == 3:                       # (E, f, d)
            return (MODEL_AXIS, None, None)
        return (MODEL_AXIS, None)                # (f, d)
    if name == "w_in":                            # rwkv channel-mix (d, f)
        return (None, MODEL_AXIS)
    if name == "w_out":
        # rwkv cm (f, d) & rglru out (d, d): both contract a sharded dim
        return (MODEL_AXIS, None)
    if name in ("w_x", "w_a", "w_i", "w_r", "w_k", "w_v", "w_g"):
        return (None, MODEL_AXIS)                # (d, d) column-parallel
    if name == "w_o":                             # rwkv out proj (d, d)
        return (MODEL_AXIS, None)
    if name == "w_router":
        return (None, None)
    if name == "embed":
        return (None, MODEL_AXIS)                # d always divides tp here
    if name == "lm_head":
        return (None, MODEL_AXIS) if vocab_ok else (MODEL_AXIS, None)
    if name in ("prefix_proj", "dec_pos"):
        return (None, MODEL_AXIS)
    return None                                   # replicate (norms, vectors…)


def _serve_base_spec(name: str, base_ndim: int, cfg: ModelConfig, tp: int):
    """Exact-TP serve layout: shard output dims only, replicate fallbacks.

    Returning ``None`` replicates the leaf.  Divisibility of the chosen
    dim is re-checked generically in :func:`param_pspec` (mismatch ->
    replicate), so e.g. ``wo (H, hd, d)`` only d-shards when d % tp == 0.
    """
    H, KV = cfg.n_heads, cfg.n_kv_heads
    head_ok = H > 0 and H % tp == 0
    kv_ok = KV > 0 and KV % tp == 0
    vocab_ok = cfg.vocab % tp == 0

    if name == "wq":
        return (None, MODEL_AXIS, None) if head_ok else None
    if name in ("wk", "wv"):
        return (None, MODEL_AXIS, None) if kv_ok else None
    if name == "wo":                              # (H, hd, d): output d
        return (None, None, MODEL_AXIS)
    if name in ("w_gate", "w_up"):
        if base_ndim == 3:                        # MoE (E, d, f): experts
            return (MODEL_AXIS, None, None)       # are independent -> exact
        return (None, MODEL_AXIS)
    if name == "w_down":
        if base_ndim == 3:                        # (E, f, d)
            return (MODEL_AXIS, None, None)
        return (None, MODEL_AXIS)                 # (f, d): output d
    if name in ("w_in", "w_x", "w_a", "w_i", "w_r", "w_k", "w_v", "w_g",
                "w_out", "w_o"):
        return (None, MODEL_AXIS)                 # all column-parallel
    if name == "embed":
        # vocab-sharded: the row gather adds zeros from non-owner shards
        # (exact) and the tied unembed becomes column-parallel (exact).
        if vocab_ok:
            return (MODEL_AXIS, None)
        # non-divisible vocab: d-shard the lookup only; a tied head would
        # contract the sharded d -> replicate instead
        return None if cfg.tie_embeddings else (None, MODEL_AXIS)
    if name == "lm_head":
        return (None, MODEL_AXIS) if vocab_ok else None
    if name in ("prefix_proj", "dec_pos"):
        return (None, MODEL_AXIS)
    return None                                   # replicate (norms, router…)


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            out.append(str(e.key))
        elif isinstance(e, jax.tree_util.GetAttrKey):
            out.append(e.name)
        elif isinstance(e, jax.tree_util.SequenceKey):
            out.append(f"[{e.idx}]")
        else:
            out.append(str(e))
    return tuple(out)


def param_pspec(path, leaf, cfg: ModelConfig, mesh: Mesh, *,
                fsdp: bool = False, layout: str = "train") -> P:
    """PartitionSpec for one parameter leaf, by path name + rank.

    ``fsdp=True`` additionally shards every >=2-D weight over the data
    axes (ZeRO-3 style): the first replicated dim that all data axes divide
    gets the data axes.  GSPMD then all-gathers each layer group's weights
    inside the layer scan — parameter+optimizer memory drops by the DP
    degree at the cost of a per-layer weight all-gather (the trade the
    collective roofline term makes visible; required for arctic/qwen3 train
    cells to fit HBM — DESIGN.md Sec. 5).

    ``layout="serve"`` selects the exact-TP rules (:func:`_serve_base_spec`
    — output-dim sharding only, replicated fallbacks), the layout whose
    sharded generation is bit-exact vs a single device.
    """
    tp = _tp(mesh)
    if tp == 1 and not fsdp:
        return P()
    names = _path_names(path)
    name = names[-1]
    if name in ("int8_q", "int8_s") and len(names) >= 2:
        name = names[-2]        # quantised leaf: inherit the weight's rule
    ndim = len(leaf.shape)
    # leading stack axes: "groups" (layer scan) and/or enc/dec_layers (vmap)
    n_stack = sum(1 for n in names if n in ("groups", "enc_layers",
                                            "dec_layers"))
    base_ndim = ndim - n_stack
    rule = _serve_base_spec if layout == "serve" else _base_spec
    base = rule(name, base_ndim, cfg, tp) if tp > 1 else None
    if base is None or len(base) != base_ndim:
        base = (None,) * base_ndim
    # verify divisibility of the sharded dim; replicate on mismatch
    spec = [None] * n_stack + list(base)
    for dim, ax in zip(leaf.shape, spec):
        if ax is not None and dim % tp != 0:
            spec = [None] * ndim
            break
    if fsdp and base_ndim >= 2:
        daxes = data_axes(mesh)
        dp = int(np.prod([mesh.shape[a] for a in daxes]))
        if dp > 1:
            for i in range(n_stack, ndim):
                if spec[i] is None and leaf.shape[i] % dp == 0:
                    spec[i] = daxes if len(daxes) > 1 else daxes[0]
                    break
    if all(ax is None for ax in spec):
        return P()
    return P(*spec)


def param_specs(abstract_params, cfg: ModelConfig, mesh: Mesh, *,
                fsdp: bool = False, layout: str = "train"):
    """Pytree of PartitionSpec matching an (abstract) param tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(path, leaf, cfg, mesh, fsdp=fsdp,
                                       layout=layout),
        abstract_params)


def state_specs(abstract_state, cfg: ModelConfig, mesh: Mesh, *,
                fsdp: bool = False):
    """TrainState specs: params + mirrored opt moments + replicated scalars."""
    def one(path, leaf):
        if len(leaf.shape) == 0:
            return P()
        return param_pspec(path, leaf, cfg, mesh, fsdp=fsdp)
    return jax.tree_util.tree_map_with_path(one, abstract_state)


# --------------------------------------------------------------------------- #
# activation / input rules
# --------------------------------------------------------------------------- #
def batch_spec(global_batch: int, mesh: Mesh):
    """Largest prefix of the data axes that divides the global batch."""
    axes = []
    prod = 1
    for a in data_axes(mesh):
        if global_batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
        else:
            break
    return tuple(axes) if axes else None


def input_shardings(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                    kind: str) -> Dict[str, NamedSharding]:
    """NamedShardings for every model input of a step kind."""
    b = batch_spec(global_batch, mesh)
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    out = {"tokens": ns(b, None)}
    if kind == "train":
        out["labels"] = ns(b, None)
    if cfg.prefix_tokens:
        out["prefix_embeds"] = ns(b, None, None)
    if cfg.n_encoder_layers:
        out["frames"] = ns(b, None, None)
    return out


def cache_specs(cfg: ModelConfig, mesh: Mesh, batch: int):
    """PartitionSpec pytree for the decode cache (matches init_cache).

    Attention KV caches: batch over data axes; KV heads over "model" when
    divisible, otherwise the *sequence* axis is sharded over "model"
    (partial-softmax decode attention).  Recurrent states shard their
    feature axis over "model" when divisible.
    """
    tp = _tp(mesh)
    b = batch_spec(batch, mesh)
    kv_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % tp == 0 and tp > 1

    def attn_spec(stacked: bool):
        lead = (None,) if stacked else ()
        if tp == 1:
            sp = (b, None, None, None)
        elif kv_ok:
            sp = (b, None, MODEL_AXIS, None)
        else:
            sp = (b, MODEL_AXIS, None, None)     # sequence-sharded cache
        return {"k": P(*lead, *sp), "v": P(*lead, *sp)}

    def rec_spec(stacked: bool):
        lead = (None,) if stacked else ()
        d_ok = cfg.d_model % tp == 0 and tp > 1
        ax = MODEL_AXIS if d_ok else None
        return {"conv": P(*lead, b, None, ax), "h": P(*lead, b, ax)}

    def rwkv_spec(stacked: bool):
        lead = (None,) if stacked else ()
        H = cfg.d_model // cfg.rwkv_head_dim
        h_ok = H % tp == 0 and tp > 1
        ax = MODEL_AXIS if h_ok else None
        d_ok = cfg.d_model % tp == 0 and tp > 1
        dax = MODEL_AXIS if d_ok else None
        return {"tm": {"shift": P(*lead, b, dax),
                       "wkv": P(*lead, b, ax, None, None)},
                "cm_shift": P(*lead, b, dax)}

    def one(kind: str, stacked: bool):
        if kind == "attn":
            return attn_spec(stacked)
        if kind == "rec":
            return rec_spec(stacked)
        if kind == "rwkv":
            return rwkv_spec(stacked)
        raise ValueError(kind)

    pat = cfg.block_pattern
    n_groups = cfg.n_layers // len(pat)
    kinds = [pat[i % len(pat)] for i in range(cfg.n_layers)]
    tail_kinds = kinds[n_groups * len(pat):]
    out: Dict[str, Any] = {}
    if n_groups:
        out["groups"] = {f"b{i}_{kind}": one(kind, True)
                         for i, kind in enumerate(pat)}
    if tail_kinds:
        out["tail"] = [{f"b0_{kind}": one(kind, False)} for kind in tail_kinds]
    return out


def encdec_cache_spec(cfg: ModelConfig, mesh: Mesh, batch: int):
    """Whisper decoder self-attn cache (L, B, S, KV, hd)."""
    tp = _tp(mesh)
    b = batch_spec(batch, mesh)
    if tp == 1:
        sp = P(None, b, None, None, None)
    elif cfg.n_kv_heads % tp == 0:
        sp = P(None, b, None, MODEL_AXIS, None)
    else:
        sp = P(None, b, MODEL_AXIS, None, None)
    return {"k": sp, "v": sp}


# --------------------------------------------------------------------------- #
def shard_tree(tree, specs, mesh: Mesh):
    """device_put a pytree according to a spec pytree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


# --------------------------------------------------------------------------- #
# activation sharding constraints (EXPERIMENTS.md §Perf HC2)
# --------------------------------------------------------------------------- #
# GSPMD propagates shardings poorly across scan (while-loop) boundaries: the
# loop-carried activation can silently lose its batch sharding, after which
# every collective in the body operates on the REPLICATED full-batch f32
# tensor (measured: 6.4 GiB single all-reduces in the deepseek train cell).
# Pinning the carry with with_sharding_constraint at each group boundary
# keeps the batch axis sharded through the whole scan — the standard MaxText
# -style fix.  Disabled (None) by default so baselines measure the naive
# behaviour; the dry-run hillclimb variants enable it.
_ACTIVATION_SHARDING: Optional[NamedSharding] = None


def set_activation_sharding(sharding: Optional[NamedSharding]):
    global _ACTIVATION_SHARDING
    _ACTIVATION_SHARDING = sharding


def constrain_activation(x):
    """Apply the configured (batch, None, None) constraint to (B, S, d)."""
    if _ACTIVATION_SHARDING is not None and getattr(x, "ndim", 0) == 3:
        return jax.lax.with_sharding_constraint(x, _ACTIVATION_SHARDING)
    return x


# --------------------------------------------------------------------------- #
# serve-mesh context: exact-TP activation pinning (DESIGN.md §Sharded-Serving)
# --------------------------------------------------------------------------- #
# While a serve mesh is in scope (the MeshServeEngine enters it around the
# trace of its generate function), every op-boundary output in the model
# (op_linear / op_einsum / op_batched_matmul, the embedding gather, the
# unembed, the MoE expert buffers) is pinned REPLICATED over "model" via
# with_sharding_constraint.  Combined with the output-dim-only serve param
# layout this guarantees no float contraction ever spans shards: each
# device computes exact column slices of every matmul and GSPMD's only
# collectives are all-gathers (exact data movement) — the property the
# sharded-vs-single-device bit-exactness tests rely on.  Outside the scope
# (the default) the hook is a no-op, so train/dry-run graphs are untouched.
_SERVE_MESH: Optional[Mesh] = None


def serve_mesh_active() -> Optional[Mesh]:
    """The mesh of the enclosing :func:`serve_mesh_scope`, if any."""
    return _SERVE_MESH


@contextlib.contextmanager
def serve_mesh_scope(mesh: Optional[Mesh]):
    """Trace-time scope enabling the exact-TP activation constraints."""
    global _SERVE_MESH
    prev = _SERVE_MESH
    _SERVE_MESH = mesh
    try:
        yield
    finally:
        _SERVE_MESH = prev


def constrain_replicated(x):
    """Pin ``x`` replicated over the serve mesh (no-op outside the scope)."""
    if _SERVE_MESH is not None and getattr(x, "ndim", 0) >= 1:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_SERVE_MESH, P()))
    return x


def serve_shard_map_info(n_out: int) -> Optional[Tuple[Mesh, str, int]]:
    """Serve-layout axis metadata for the shard_map fused-kernel route.

    Returns ``(mesh, MODEL_AXIS, tp)`` when the enclosing serve mesh can
    shard_map an aged matmul over its ``n_out`` output columns — i.e. a
    serve mesh is in scope, it actually has tensor parallelism, and the
    output dim splits evenly over the axis (each shard's column block is
    then exactly the block :func:`repro.kernels.ops.shard_slices` assigns,
    so the kernel and kernel-free streams line up).  ``None`` means the
    caller must stay on the kernel-free GSPMD route — same streams, so the
    downgrade never changes sampled tokens (see ``aged_linear``).
    """
    mesh = _SERVE_MESH
    if mesh is None or MODEL_AXIS not in mesh.axis_names:
        return None
    tp = _tp(mesh)
    if tp <= 1 or n_out % tp != 0:
        return None
    return mesh, MODEL_AXIS, tp
