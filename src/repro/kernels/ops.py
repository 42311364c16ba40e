"""Public jit'd wrappers around the Pallas kernels.

* Shapes are padded to block multiples here, so callers can use arbitrary
  sizes.
* ``interpret`` defaults to True off-TPU (this container is CPU-only; the
  kernels TARGET TPU and are validated in interpret mode against ``ref.py``).
* :func:`aged_linear` is the model-facing op: a float matmul executed the
  way the paper's accelerator executes it — int8 quantisation, int32
  systolic accumulation, BER-parameterised accumulator bit upsets, dequant.
  Its default fast path is ONE fused kernel (:func:`fused_aged_matmul`):
  upsets drawn by the in-kernel PRNG at the accumulator flush, dequant
  fused, nothing but ``a``, ``b``, scales and the float output touching
  HBM.  The seed-free three-pass route survives as the oracle fallback.
  It takes the weight as a float array, quantised in place, or as a
  :class:`QuantizedWeight` that the caller quantised ahead.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import REGISTRY
from . import ref
from .bitflip import bitflip_words
from .fused_aged_matmul import fused_aged_matmul as _fused_aged_matmul_kernel
from .systolic_matmul import systolic_matmul

# faulted weight-matmul sites by where their weight was quantised:
# "prequantized" (a QuantizedWeight) or "inline" (quantised in the call).
# Ticks when the Python body runs, i.e. while jax traces a jitted caller.
AGED_WEIGHTS = REGISTRY.trace_counter("aged_weights")


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quantized_matmul(a: jax.Array, b: jax.Array, *, bm: int = 256,
                     bn: int = 256, bk: int = 256,
                     interpret: bool | None = None) -> jax.Array:
    """int8 (M,K) @ int8 (K,N) -> int32 (M,N), arbitrary shapes (padded)."""
    if interpret is None:
        interpret = _default_interpret()
    (bm_, bn_, bk_), ap, bp = _resolve_blocks(a, b, bm, bn, bk)
    out = systolic_matmul(ap, bp, bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:a.shape[0], :b.shape[1]]


def _ceil_mult(dim: int, base: int = 128) -> int:
    """Requested block ``base``, shrunk to a pow2 >= 8 for small dims."""
    if dim >= base:
        return base
    # small test shapes: round up to the sublane multiple
    return max(8, int(2 ** np.ceil(np.log2(max(dim, 1)))))


def _resolve_blocks(a: jax.Array, b: jax.Array, bm: int, bn: int, bk: int):
    """Shared preamble of the matmul wrappers: honor the requested block
    shape (shrunk for small dims) and zero-pad operands to multiples."""
    bm_, bn_, bk_ = (_ceil_mult(a.shape[0], bm), _ceil_mult(b.shape[1], bn),
                     _ceil_mult(a.shape[1], bk))
    return (bm_, bn_, bk_), _pad_to(a, bm_, bk_), _pad_to(b, bk_, bn_)


def make_flip_randoms(key: jax.Array, shape: tuple[int, ...]):
    """Uniforms + bit positions for the injection kernel (shared w/ oracle)."""
    ku, kp = jax.random.split(key)
    u = jax.random.uniform(ku, shape, jnp.float32)
    pos = jax.random.randint(kp, shape, 0, 32, jnp.int32)
    return u, pos


def _flip_inputs(x: jax.Array, key: jax.Array, block_rows: int = 256):
    """Shared injection preamble: (R, 128)-tiled words + their randoms.

    The layout (and therefore the random stream) is identical for the
    Pallas kernel and the jnp oracle, so the two routes are bit-exact.
    """
    n = int(np.prod(x.shape))
    rows = -(-n // 128)
    rows_pad = -(-rows // block_rows) * block_rows
    # zero-pad (NOT jnp.resize, which tiles real accumulator words into the
    # pad region — wasted RNG spent flipping copies of live data)
    xf = jnp.pad(x.reshape(-1), (0, rows_pad * 128 - n)).reshape(rows_pad,
                                                                 128)
    u, pos = make_flip_randoms(key, (rows_pad, 128))
    return xf, u, pos, n


@functools.partial(jax.jit, static_argnames=("interpret",))
def inject_bitflips(x: jax.Array, ber, key: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """Flip bits of an int32 tensor at per-bit error rate ``ber``.

    Any shape; internally flattened to (R, 128) tiles for the TPU kernel.
    """
    if interpret is None:
        interpret = _default_interpret()
    block_rows = 256
    xf, u, pos, n = _flip_inputs(x, key, block_rows)
    q = 1.0 - (1.0 - jnp.asarray(ber, jnp.float32)) ** 32
    out = bitflip_words(xf, u, pos, q[None], block_rows=block_rows,
                        interpret=interpret)
    return out.reshape(-1)[:n].reshape(x.shape)


@jax.jit
def inject_bitflips_ref(x: jax.Array, ber, key: jax.Array) -> jax.Array:
    """Pure-jnp injection, bit-exact vs :func:`inject_bitflips`.

    Same word layout, same random draws, same flip rule — only the
    executor differs (``ref.bitflip_words_ref`` instead of the Pallas
    kernel).  This is what the kernel-free ``aged_linear`` route uses:
    unlike a ``pallas_call`` in interpret mode, plain jnp vectorises
    cleanly under ``vmap`` (the resilience-characterisation sweep maps
    whole fault grids over lanes; see ``benchmarks/resilience_bench.py``).
    """
    xf, u, pos, n = _flip_inputs(x, key)
    q = 1.0 - (1.0 - jnp.asarray(ber, jnp.float32)) ** 32
    out = ref.bitflip_words_ref(xf, u, pos, q[None])
    return out.reshape(-1)[:n].reshape(x.shape)


def shard_slices(n: int, n_shards: int) -> list:
    """Split points assigning ``n`` columns/heads to shards: shard ``s``
    owns ``[s*n//S, (s+1)*n//S)`` — for divisible ``n`` this is exactly the
    contiguous equal-block assignment ``NamedSharding`` uses, and for
    ``n < S`` trailing shards own empty blocks (they hold no heads)."""
    return [s * n // n_shards for s in range(1, n_shards)]


def upset_counter_block(acc: jax.Array, ber, seed) -> jax.Array:
    """Upset one 2-D accumulator block with the fused kernel's counter
    stream over the SAME (bm, bn) tile grid the shard-local kernel wrapper
    resolves for this block shape — bit-exact vs :func:`fused_aged_matmul`
    run on the block with the same seed (``tests/test_shard_map_fused.py``).
    """
    from .fused_aged_matmul import tile_counter_bits, upset_words
    M, N = acc.shape
    bits = tile_counter_bits(M, N, seed, bm=_ceil_mult(M, 256),
                             bn=_ceil_mult(N, 256))
    q = 1.0 - (1.0 - jnp.asarray(ber, jnp.float32)) ** 32
    return upset_words(acc, bits, q)


def inject_bitflips_sharded(x: jax.Array, bers, key: jax.Array | None = None,
                            *, seed=None, axis: int = -1) -> jax.Array:
    """Per-shard accumulator upsets: block ``s`` of ``axis`` flips at
    ``bers[s]`` with a shard-distinct stream.

    ``bers`` is an ``(S,)`` vector — one BER per mesh shard of the serve
    layout (each shard of the weight's output dim is a physically distinct
    array region with its own ΔVth history).  Each shard's stream is an
    fmix32 fold of the base seed (``fold_seed(seed, s)``; ``seed`` hashed
    from ``key`` when only a key is given) expanded by the fused kernel's
    *counter PRNG* over the block's own resolved tile grid
    (:func:`upset_counter_block`): the draws are exactly what
    :func:`fused_aged_matmul` would generate running shard-locally on that
    column block, so the shard_map-wrapped kernel route and this pure-jnp
    route are bit-exact BY CONSTRUCTION — this is the kernel route's
    oracle.  Everything here is plain jnp, so the op partitions under
    GSPMD, vectorises under ``vmap``, and a hand-built reference (slice ->
    fold -> counter draws -> xor) reproduces it exactly
    (``tests/test_serve_sharded.py``).  Rank > 2 inputs (the qkt/sv
    flattened-head blocks) collapse their leading dims, keeping the last
    dim as the tile-layout columns.

    Implementation note: the per-shard blocks are NOT materialised with
    ``jnp.split``/``jnp.concatenate``.  On a serve mesh with a non-trivial
    data axis, XLA's SPMD partitioner miscompiles that concat-of-slices
    pattern on replicated operands — every data replica's contribution is
    summed, returning ``data_parallelism x`` the true accumulator (seen on
    jax 0.4.37 CPU; ``tests/test_shard_map_fused.py`` pins the parity that
    caught it).  Instead, each element's shard id, block-local row/column,
    and resolved tile parameters are precomputed as static constants and
    the whole array is upset in one elementwise pass — identical draws,
    nothing for the partitioner to reassemble.
    """
    from .fused_aged_matmul import counter_bits, upset_words
    bers = jnp.asarray(bers, jnp.float32)
    S = int(bers.shape[0])
    if seed is None:
        seed = seed_from_key(key)
    ax = axis % x.ndim
    n_ax = x.shape[ax]
    D = x.shape[-1]
    R = int(np.prod(x.shape[:-1]))
    bounds = np.asarray([0] + shard_slices(n_ax, S) + [n_ax])
    widths = np.diff(bounds)
    q = 1.0 - (1.0 - bers) ** 32                                  # (S,)
    seeds = fold_seed(seed, np.arange(S, dtype=np.uint32)) \
        .astype(jnp.uint32)                                       # (S,)

    x2 = x.reshape(R, D)
    row = jnp.arange(R, dtype=jnp.uint32)[:, None]
    col = jnp.arange(D, dtype=jnp.uint32)[None, :]
    U = lambda a: jnp.asarray(np.asarray(a, np.uint32))
    if ax == x.ndim - 1:
        # column split: block s is (R, W_s); per-column constants
        sid = np.searchsorted(bounds[1:-1], np.arange(D), side="right")
        bn_s = np.asarray([_ceil_mult(max(int(w), 1), 256)
                           for w in widths])
        grid_s = np.maximum(-(-widths // bn_s), 1)
        bm = np.uint32(_ceil_mult(R, 256))
        lcol = U(np.arange(D) - bounds[sid])
        bn, grid = U(bn_s[sid])[None, :], U(grid_s[sid])[None, :]
        tile_id = (row // bm) * grid + lcol[None, :] // bn
        offset = (row % bm) * bn + lcol[None, :] % bn
        bits = counter_bits(offset, seeds[sid][None, :], tile_id)
        return upset_words(x2, bits, q[sid][None, :]).reshape(x.shape)
    # leading-axis split (flattened-head blocks): block s is
    # (lead, W_s, mid, D) reshaped to (lead * W_s * mid, D); per-row
    # constants recover each row's block-local index and block size
    mid = int(np.prod(x.shape[ax + 1:-1], dtype=np.int64))
    g = np.arange(R)
    h = (g // mid) % n_ax
    a_ = g // (mid * n_ax)
    b_ = g % mid
    sid_ax = np.searchsorted(bounds[1:-1], np.arange(n_ax), side="right")
    s_row = sid_ax[h]
    r_loc = U((a_ * widths[s_row] + (h - bounds[s_row])) * mid + b_)
    rows_s = (R // n_ax) * widths
    bm_row = U(np.asarray([_ceil_mult(max(int(r), 1), 256)
                           for r in rows_s])[s_row])[:, None]
    bn = np.uint32(_ceil_mult(D, 256))
    grid_n = np.uint32(-(-D // int(bn)))
    tile_id = (r_loc[:, None] // bm_row) * grid_n + col // bn
    offset = (r_loc[:, None] % bm_row) * bn + col % bn
    bits = counter_bits(offset, seeds[s_row][:, None], tile_id)
    return upset_words(x2, bits, q[s_row][:, None]).reshape(x.shape)


def _fused_aged_matmul_sharded(xq, wq, bers, seed, mesh,
                               shard_axis: str, interpret):
    """shard_map the fused kernel over ``mesh``'s ``shard_axis``.

    Each shard runs :func:`fused_aged_matmul` — int8 matmul + in-flush
    accumulator upsets, ONE Pallas kernel — locally on the output-column
    block it owns under the serve layout, at ``bers[s]`` with the
    shard-distinct stream ``fold_seed(seed, s)`` passed as shard-local
    scalars.  Inputs/outputs follow the serve layout's invariants:
    activations replicated, weight columns sharded, output column-sharded
    (the caller's ``constrain_replicated`` pin turns the gather into pure
    data movement).  BERs and the seed are traced — shard age/BER updates
    between calls re-jit nothing.

    Returns the faulted **int32 accumulator**, not the dequantised float:
    the caller applies the same ``acc.astype(f32) * xs * ws`` epilogue as
    the kernel-free route.  Fusing the dequant into the kernel would hand
    XLA a differently-shaped program on the oracle side, and its simplifier
    is then free to reassociate the two broadcast multiplies differently —
    last-ulp float drift that breaks cross-route token equality.  Keeping
    the epilogue textually identical in both routes keeps them bit-exact by
    construction; the byte win that matters (no materialised randoms, no
    separate flip-pass round-trip) is unaffected.
    """
    from jax.sharding import PartitionSpec as P

    def body(xq, wq_blk, bers, seed):
        s = jax.lax.axis_index(shard_axis)
        return fused_aged_matmul(xq, wq_blk, ber=bers[s],
                                 seed=fold_seed(seed, s),
                                 interpret=interpret)

    col = P(None, shard_axis)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), col, P(), P()),
                         out_specs=col, check_vma=False)(
        xq, wq, jnp.asarray(bers, jnp.float32),
        jnp.asarray(seed, jnp.int32))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fused_aged_matmul(a: jax.Array, b: jax.Array,
                      xs: jax.Array | None = None,
                      ws: jax.Array | None = None, *, ber=0.0, seed=0,
                      bm: int = 256, bn: int = 256, bk: int = 256,
                      interpret: bool | None = None) -> jax.Array:
    """Fused int8 matmul + in-accumulator bit upsets, arbitrary shapes.

    One kernel pass replaces ``quantized_matmul`` -> ``make_flip_randoms``
    -> ``inject_bitflips``: the upset is applied to the accumulator tile in
    VMEM during the K-final flush, keyed on ``(seed, tile)``, so no
    output-sized random arrays and no extra int32 HBM round-trip exist.
    With scales ``xs (M, 1)`` / ``ws (1, N)`` the dequant epilogue is fused
    as well and the result is float32.
    """
    assert (xs is None) == (ws is None), "pass both scales or neither"
    if interpret is None:
        interpret = _default_interpret()
    M, N = a.shape[0], b.shape[1]
    (bm_, bn_, bk_), ap, bp = _resolve_blocks(a, b, bm, bn, bk)
    if xs is not None:
        xs = _pad_to(xs, bm_, 1)
        ws = _pad_to(ws, 1, bn_)
    out = _fused_aged_matmul_kernel(ap, bp, xs, ws, ber, seed, bm=bm_,
                                    bn=bn_, bk=bk_, interpret=interpret)
    return out[:M, :N]


def seed_from_key(key: jax.Array) -> jax.Array:
    """Derive the fused kernel's int32 seed from a ``jax.random`` key."""
    return jax.random.bits(key, (), jnp.uint32).astype(jnp.int32)


def fold_seed(seed: jax.Array, *indices) -> jax.Array:
    """Mix indices into an int32 seed — the in-trace stream derivation.

    Uses the fused kernel's own fmix32 stream mix (``stream_constant``), so
    nearby (seed, index) pairs never alias, and each fold is ~5 integer ops
    on a scalar: cheap enough to sit inside a ``lax.scan`` decode body once
    per operator per step.  This is how per-(call, operator, layer, step)
    upset streams are derived during scanned generation without threading
    threefry keys through the scan carry.
    """
    from .fused_aged_matmul import stream_constant
    s = jnp.asarray(seed).astype(jnp.uint32)
    for idx in indices:
        s = stream_constant(s, jnp.asarray(idx).astype(jnp.uint32))
    return s.astype(jnp.int32)


def quantize_int8(x: jax.Array, axis: int = -1):
    """Symmetric per-row absmax int8 quantisation; returns (q, scale).

    Its ops carry ``jax.named_scope("quantize")`` in their metadata."""
    with jax.named_scope("quantize"):
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_weight(w: jax.Array):
    """Per-column int8 quantisation of a ``(..., K, N)`` weight, the
    scale in float32 whatever ``w``'s dtype.  A bf16 scale would be
    rounded where it is stored and not where XLA fuses its computation
    into the consumer, so a weight quantised in place and one quantised
    ahead would dequantise differently; in float32 nothing is rounded."""
    return quantize_int8(w.astype(jnp.float32), axis=-2)


@dataclasses.dataclass(frozen=True)
class QuantizedWeight:
    """A float weight viewed as ``(K, N)`` and quantised ahead for
    :func:`aged_linear`: ``q`` int8 ``(K, N)`` and ``scale`` ``(1, N)``,
    exactly ``quantize_weight(w)``.  ``out_dims`` are the float
    weight's output dims (``(N,)``, or ``(H, hd)`` for a fused-head
    projection).  A registered pytree: stacked layers carry a leading axis
    on ``q`` and ``scale``, which a layer scan slices away."""
    q: jax.Array
    scale: jax.Array
    out_dims: tuple


jax.tree_util.register_dataclass(
    QuantizedWeight, data_fields=("q", "scale"), meta_fields=("out_dims",))


def aged_linear(x: jax.Array, w, *, ber=0.0,
                key: jax.Array | None = None,
                seed: jax.Array | None = None,
                interpret: bool | None = None,
                use_kernel: bool = True,
                fused: bool = True,
                shard_axis: str | None = None,
                mesh=None) -> jax.Array:
    """``x (.., K) @ w (K, N)`` executed as the paper's systolic array does.

    Quantise activations per-row and weights per-column to int8, multiply
    with int32 accumulation, inject accumulator bit errors at ``ber``, then
    dequantise.  ``ber=0`` with ``use_kernel=False`` is the clean fast path
    used during training.

    Injection is requested by passing ``seed`` (int32 scalar) or ``key``
    (a ``jax.random`` key; hashed down to a seed for the fused path).  With
    ``fused=True`` (default) and ``use_kernel=True`` the faulted matmul is
    ONE kernel — upset + dequant fused into the flush step, no materialised
    randoms, no int32 HBM round-trip.  ``fused=False`` keeps the original
    three-pass route (matmul -> ``make_flip_randoms`` -> ``bitflip_words``),
    retained as the oracle / fallback path.

    ``ber`` may be an ``(S,)`` per-shard vector (mesh serving): shard ``s``
    of the output columns then flips at ``bers[s]`` with the shard-distinct
    counter stream ``fold_seed(seed, s)``.  Two bit-identical realisations:

    * With ``mesh`` / ``shard_axis`` given (the serve engine passes the
      active serve mesh) and ``N`` divisible by the axis size ``S``, the
      matmul is wrapped in ``shard_map`` and every shard runs the ONE fused
      kernel locally on its own output-column block — the fused path's HBM
      byte economy survives tensor parallelism.  Requires ``use_kernel``
      and ``fused``.
    * Otherwise ``use_kernel=fused=True`` is **silently downgraded** to the
      pure-jnp kernel-free route — a ``pallas_call`` is a single-device
      program and does not partition under GSPMD, so without a mesh to
      shard_map over there is no way to run the kernel per shard.  The
      downgrade draws the SAME counter streams via
      :func:`inject_bitflips_sharded`, so routing affects performance only,
      never sampled tokens, and the kernel-free route doubles as the
      shard_map route's oracle (``tests/test_shard_map_fused.py``).
    """
    if isinstance(w, QuantizedWeight):
        AGED_WEIGHTS["prequantized"] += 1
        wq, ws = w.q, w.scale
    else:
        AGED_WEIGHTS["inline"] += 1
        wq, ws = quantize_weight(w)
    N = wq.shape[1]
    sharded = jnp.ndim(ber) == 1
    inject = key is not None or seed is not None
    shard_mapped = False
    if sharded:
        S = int(ber.shape[0])
        shard_mapped = (use_kernel and fused and inject and mesh is not None
                        and shard_axis is not None
                        and shard_axis in mesh.axis_names
                        and int(mesh.shape[shard_axis]) == S
                        and N % S == 0)
        if not shard_mapped:
            # documented downgrade: same streams, kernel-free executor
            use_kernel = fused = False
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    xq, xs = quantize_int8(x2, axis=-1)
    if sharded and inject:
        if seed is None:
            seed = seed_from_key(key)
        if shard_mapped:
            acc = _fused_aged_matmul_sharded(xq, wq, ber, seed,
                                             mesh, shard_axis, interpret)
        else:
            acc = ref.systolic_matmul_ref(xq, wq)
            acc = inject_bitflips_sharded(acc, ber, seed=seed)
        # one dequant epilogue for BOTH routes — identical jnp expression
        # => identical XLA rewrites => cross-route bit-exactness survives
        # the simplifier's broadcast-multiply reassociation freedom
        out = acc.astype(jnp.float32) * xs * ws
        return out.reshape(*lead, N).astype(x.dtype)
    if use_kernel and fused and inject:
        if seed is None:
            seed = seed_from_key(key)
        out = fused_aged_matmul(xq, wq, xs, ws, ber=ber, seed=seed,
                                interpret=interpret)
        return out.reshape(*lead, N).astype(x.dtype)
    if use_kernel:
        acc = quantized_matmul(xq, wq, interpret=interpret)
    else:
        acc = ref.systolic_matmul_ref(xq, wq)
    if inject:
        if key is None:
            key = jax.random.PRNGKey(seed)
        # kernel-free route stays kernel-free: the jnp oracle injection
        # is bit-exact vs the Pallas kernel and vmap-friendly
        acc = (inject_bitflips(acc, ber, key, interpret=interpret)
               if use_kernel else inject_bitflips_ref(acc, ber, key))
    out = acc.astype(jnp.float32) * xs * ws
    return out.reshape(*lead, N).astype(x.dtype)
