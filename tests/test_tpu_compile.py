"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs here: each test lowers and compiles at the published
``deepseek_7b`` widths (d_model 4096, d_ff 11008) for a ``v5e:2x2``
topology that the installed TPU compiler describes without a chip, and
asserts the program holds a Mosaic kernel (``tpu_custom_call``).  The
interpret-mode parity tests cannot see what Mosaic refuses (int32
matmuls, unsupported casts, scalar memory spaces, block shapes under
``vmap``); these can.

The topology is described inside a fixture and never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  ``ops._default_interpret`` picks interpret mode from the CPU
backend this process runs on, so each test steers it to the compiled path.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import ModelConfig
from repro.distributed.sharding import make_mesh
from repro.kernels import ops as kops
from repro.models import transformer as tf
from repro.models.layers import FaultConfig
from repro.serve import steps
from repro.kernels.bitflip import bitflip_words
from repro.kernels.systolic_matmul import systolic_matmul

D_MODEL, D_FF, TOKENS = 4096, 11008, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled(monkeypatch):
    """Compile for the TPU: no interpret mode, and no persistent cache
    (a TPU executable written to it cannot be read back without a chip)."""
    monkeypatch.setattr(kops, "_default_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_systolic_matmul_compiles(compiled, one_chip):
    _assert_kernel(lambda a, b: systolic_matmul(a, b),
                   _sds((TOKENS, D_MODEL), jnp.int8, one_chip),
                   _sds((D_MODEL, D_FF), jnp.int8, one_chip))


@pytest.mark.parametrize("dequant", [False, True])
def test_fused_aged_matmul_compiles(compiled, one_chip, dequant):
    """The ops wrapper (padding, block choice) around the fused kernel:
    the int32 accumulator form and the fused-dequant form."""
    args = [_sds((TOKENS, D_MODEL), jnp.int8, one_chip),
            _sds((D_MODEL, D_FF), jnp.int8, one_chip)]
    if dequant:
        args += [_sds((TOKENS, 1), jnp.float32, one_chip),
                 _sds((1, D_FF), jnp.float32, one_chip)]
    args += [_sds((), jnp.float32, one_chip), _sds((), jnp.int32, one_chip)]

    def f(a, b, *rest):
        *scales, ber, seed = rest
        return kops.fused_aged_matmul(a, b, *scales, ber=ber, seed=seed)
    _assert_kernel(f, *args)


def test_bitflip_words_compiles(compiled, one_chip):
    rows = TOKENS * D_FF // 128
    _assert_kernel(lambda x, u, pos, q: bitflip_words(x, u, pos, q),
                   _sds((rows, 128), jnp.int32, one_chip),
                   _sds((rows, 128), jnp.float32, one_chip),
                   _sds((rows, 128), jnp.int32, one_chip),
                   _sds((1,), jnp.float32, one_chip))


def test_kernels_compile_under_fleet_vmap(compiled, one_chip):
    """The fleet engines vmap whole generation over lanes: each lane's
    BER and seed become a batched SMEM scalar of both kernels."""
    lanes = 2

    def lane(x, w, ber, seed, key):
        fused = kops.aged_linear(x, w, ber=ber, seed=seed)
        acc = kops.quantized_matmul(*[kops.quantize_int8(t, axis=a)[0]
                                      for t, a in ((x, -1), (w, 0))])
        return fused, kops.inject_bitflips(acc, ber, key)
    text = _assert_kernel(
        jax.vmap(lane, in_axes=(0, None, 0, 0, 0)),
        _sds((lanes, TOKENS, D_MODEL), jnp.bfloat16, one_chip),
        _sds((D_MODEL, D_FF), jnp.bfloat16, one_chip),
        _sds((lanes,), jnp.float32, one_chip),
        _sds((lanes,), jnp.int32, one_chip),
        _sds((lanes, 2), jnp.uint32, one_chip))
    # fused kernel, systolic matmul, bit-flip injection
    assert text.count("tpu_custom_call") >= 3


def test_shard_map_fused_route_compiles(compiled, topo):
    """The mesh serving route: each "model" shard of a 2x2 mesh runs the
    fused kernel on its own d_ff column block at its own BER."""
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    repl = NamedSharding(mesh, P())

    def f(x, w, bers, seed):
        return kops.aged_linear(x, w, ber=bers, seed=seed, mesh=mesh,
                                shard_axis="model")
    text = _assert_kernel(
        f, _sds((TOKENS, D_MODEL), jnp.bfloat16, repl),
        _sds((D_MODEL, D_FF), jnp.bfloat16,
             NamedSharding(mesh, P(None, "model"))),
        _sds((2,), jnp.float32, repl), _sds((), jnp.int32, repl))
    assert np.prod(mesh.devices.shape) == 4 and "all-gather" not in text


def test_faulted_decode_attention_dots_stay_on_the_mxu(compiled, one_chip):
    """The faulted decode step's int8 QK^T and SV matmuls, each a matvec
    per (row, head) over a layer's ring read from the stacked cache, compile
    to MXU dots (``kOutput`` fusions), not to multiply-reduce loops on the
    vector unit.  Two MHA layers at the published widths over the chat
    cells' 1,153-slot ring at batch 8; a one-layer scan compiles otherwise.
    """
    cfg = ModelConfig(name="deepseek_7b", family="dense", n_layers=2,
                      d_model=D_MODEL, n_heads=32, n_kv_heads=32, d_ff=D_FF,
                      vocab=1024, head_dim=128, mlp="gated", norm="rms",
                      pos="rope")
    batch, kv_len = 8, 1153
    shapes = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), jax.eval_shape(tree))
    params = shapes(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: tf.init_cache(cfg, batch, kv_len))
    fi = shapes(lambda: FaultConfig(
        bers={op: jnp.float32(1e-5) for op in ("q", "k", "v", "qkt", "sv")},
        key=jax.random.PRNGKey(0), step=jnp.int32(0)))
    text = jax.jit(steps.make_decode_fn(cfg), donate_argnums=(2,)).lower(
        params, _sds((batch, 1), jnp.int32, one_chip), cache,
        _sds((), jnp.int32, one_chip), fi).compile().as_text()
    roots = dict(re.findall(r"^%([\w.\-]+) .*\{\n(?:.*\n)*?\s*ROOT %\S+ = "
                            r"\S+ ([\w-]+)\(", text, re.M))
    dots = re.findall(r"^\s*%\S+ = \S+ fusion\(.*kind=(\w+), "
                      r"calls=%([\w.\-]+).*attention/\.\.\.ik,\.\.\.kj->"
                      r"\.\.\.ij/dot_general", text, re.M)
    assert [kind for kind, _ in dots].count("kOutput") == 2, dots
    assert not [c for _, c in dots if roots.get(c) == "reduce"], dots
