"""shard_map fused-kernel mesh serving: stream independence and parity.

The route under test (PR 8): with a serve mesh in scope and ``(S,)``
per-shard BER vectors, every divisible weight matmul runs the fused Pallas
kernel (int8 matmul + in-flush accumulator upsets + fused dequant) *per
shard* under ``shard_map``, with shard ``s`` drawing the counter stream
``fold_seed(seed, s)``.  The kernel-free GSPMD route draws the same
streams (``inject_bitflips_sharded``), so it is the oracle: routing must
never change a sampled token.

In-process tests cover the stream/kernel contracts on one device (a tp=1
mesh exercises the real shard_map machinery); the tp in {2, 4, 8} x
{deepseek, paligemma, whisper} generation parity grid runs on 8 faked host
devices in a subprocess, like the rest of the multi-device coverage.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed.sharding import make_mesh
from repro.kernels import ops as kops
from repro.kernels import ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------- #
# fold_seed stream independence (hypothesis property)
# --------------------------------------------------------------------------- #
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(min_value=-(2 ** 31), max_value=2 ** 31 - 1),
       n_shards=st.integers(min_value=2, max_value=16))
def test_fold_seed_shard_streams_never_alias(seed, n_shards):
    """(seed, shard) -> stream is injective across the shard axis, and
    nearby base seeds never collide shard-wise: ``fold_seed(seed, s)`` must
    differ from every ``fold_seed(seed', s')`` with ``seed' in {seed,
    seed + 1}`` except itself — additive mixing (``seed + s``) would alias
    shard s of seed k with shard s-1 of seed k+1."""
    folds = {}
    for base in (seed, seed + 1 if seed < 2 ** 31 - 1 else seed - 1):
        for s in range(n_shards):
            folds[(base, s)] = int(kops.fold_seed(jnp.int32(base), s))
    assert len(set(folds.values())) == len(folds)


def test_fold_seed_matches_shard_map_axis_index():
    """The python-int fold the oracle uses equals the traced
    ``axis_index`` fold the shard_map body uses."""
    seed = jnp.int32(0x5EED)
    traced = jax.jit(lambda s: kops.fold_seed(seed, s))(jnp.uint32(3))
    assert int(traced) == int(kops.fold_seed(seed, 3))


# --------------------------------------------------------------------------- #
# counter-stream contracts: oracle block == fused kernel block
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("m,k,n", [(32, 64, 48), (8, 32, 130), (16, 96, 32)])
def test_upset_counter_block_matches_fused_kernel(m, k, n):
    """``upset_counter_block`` resolves the same tile grid as the kernel
    wrapper and draws the same counter bits: faulted int32 accumulators
    agree exactly (integer compare — no dequant float in the loop)."""
    ka, kb = jax.random.split(jax.random.PRNGKey(m + n))
    a = jax.random.randint(ka, (m, k), -128, 128, jnp.int8)
    b = jax.random.randint(kb, (k, n), -128, 128, jnp.int8)
    seed, ber = jnp.int32(77), jnp.float32(0.03)
    got = kops.fused_aged_matmul(a, b, ber=ber, seed=seed, interpret=True)
    acc = ref.systolic_matmul_ref(a, b)
    want = kops.upset_counter_block(acc, ber, seed)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got) != np.asarray(acc)).any()


def test_shard_map_route_single_device_parity():
    """A tp=1 mesh runs the real shard_map + Pallas route in-process: the
    lowering must contain the pallas_call and the jitted output must be
    bit-exact vs the jitted kernel-free oracle (clean and faulted)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 96), jnp.float32)
    seed = jnp.int32(9)

    f_sm = jax.jit(lambda x, w, b, s: kops.aged_linear(
        x, w, ber=b, seed=s, mesh=mesh, shard_axis="model", interpret=True))
    f_or = jax.jit(lambda x, w, b, s: kops.aged_linear(x, w, ber=b, seed=s))
    jaxpr = str(jax.make_jaxpr(f_sm)(x, w, jnp.ones(1), seed))
    assert "pallas_call" in jaxpr and "shard_map" in jaxpr
    assert "pallas_call" not in str(jax.make_jaxpr(f_or)(
        x, w, jnp.ones(1), seed))
    for ber in (jnp.zeros(1), jnp.float32([0.02])):
        a, b = f_sm(x, w, ber, seed), f_or(x, w, ber, seed)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(f_sm(x, w, jnp.float32([0.02]), seed))
            != np.asarray(f_sm(x, w, jnp.zeros(1), seed))).any()


def test_aged_linear_downgrades_without_mesh():
    """No mesh — or a BER vector whose length does not match the mesh axis
    — silently downgrades the fused flags to the kernel-free route
    (documented in the docstring), and the downgrade is output-invisible
    because the streams match."""
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (32, 64), jnp.float32)
    mesh = make_mesh((1, 1), ("data", "model"))
    seed = jnp.int32(3)
    cases = [
        (jnp.float32([0.05]), {}),                      # fused flags, no mesh
        (jnp.float32([0.05, 0.1]),                      # S=2 != axis size 1
         {"mesh": mesh, "shard_axis": "model"}),
    ]
    for ber, kwargs in cases:
        jaxpr = str(jax.make_jaxpr(lambda b: kops.aged_linear(
            x, w, ber=b, seed=seed, **kwargs))(ber))
        assert "pallas_call" not in jaxpr, kwargs
        down = kops.aged_linear(x, w, ber=ber, seed=seed, **kwargs)
        free = kops.aged_linear(x, w, ber=ber, seed=seed,
                                use_kernel=False, fused=False)
        np.testing.assert_array_equal(np.asarray(down), np.asarray(free))


def test_serve_shard_map_info_gating():
    from repro.distributed import sharding as shrules
    assert shrules.serve_shard_map_info(64) is None       # no scope
    mesh = make_mesh((1, 1), ("data", "model"))
    with shrules.serve_mesh_scope(mesh):
        assert shrules.serve_shard_map_info(64) is None   # tp == 1


# --------------------------------------------------------------------------- #
# multi-device generation parity grid (8 faked devices, subprocess)
# --------------------------------------------------------------------------- #
PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.fleet import FleetRuntime
    from repro.distributed import sharding as shrules
    from repro.models.layers import FaultConfig, op_linear
    from repro.serve import steps
    from repro.serve.sharded import MeshServeEngine, default_serve_mesh
    from repro.train.steps import init_train_state
    mark = lambda m: (print(m, file=sys.stderr), sys.stderr.flush())

    GRID = {"deepseek_7b": (2, 4, 8), "paligemma_3b": (4,),
            "whisper_large_v3": (2, 8)}
    out = {"combos": {}}

    # the fused flavour must actually lower the kernel inside shard_map
    mesh8 = default_serve_mesh(8)
    fi = FaultConfig(bers={"q": jnp.zeros(8)}, key=jax.random.PRNGKey(0),
                     step=jnp.int32(0))
    with shrules.serve_mesh_scope(mesh8):
        jaxpr = str(jax.make_jaxpr(lambda x, w: op_linear(x, w, "q", fi))(
            jnp.ones((2, 32), jnp.bfloat16), jnp.ones((32, 64),
                                                      jnp.bfloat16)))
    out["fused_lowering"] = ("pallas_call" in jaxpr
                            and "shard_map" in jaxpr)

    for arch, tps in GRID.items():
        cfg = get_config(arch).reduced()
        params = init_train_state(cfg, jax.random.PRNGKey(0)).params
        # batch 4 x 6 steps: enough upsets at ~1e-5 BER that every combo's
        # faults reach a sampled token
        prompts = (np.arange(4 * 4).reshape(4, 4) * 31 % cfg.vocab
                   ).astype(np.int32)
        rng = np.random.RandomState(0)
        extras = {}
        if cfg.prefix_tokens:
            extras["prefix_embeds"] = rng.randn(
                4, cfg.prefix_tokens, cfg.d_model).astype(np.float32)
        if cfg.n_encoder_layers:
            extras["frames"] = rng.randn(
                4, cfg.encoder_seq, cfg.d_model).astype(np.float32)
        for tp in tps:
            fl = FleetRuntime(n_devices=1, n_shards=tp)
            engs = {
                route: MeshServeEngine(cfg, params, fleet=fl, tp=tp,
                                       max_len=24, seed=3,
                                       use_fused_kernel=(route == "fused"))
                for route in ("fused", "free")}
            combo = {}
            steps.TRACE_COUNTS.clear()
            mark(f"[parity] {arch} tp={tp} compiling clean (age 0)")
            clean = {r: e.generate(prompts, 6, **extras)
                     for r, e in engs.items()}
            combo["clean_exact"] = bool(np.array_equal(
                clean["fused"].tokens, clean["free"].tokens))
            n1 = dict(steps.TRACE_COUNTS)
            for s in range(tp):              # heterogeneous shard ages
                fl.set_age(years=2.0 + 7.0 * s / max(tp - 1, 1), shard=s)
            mark(f"[parity] {arch} tp={tp} faulted pass")
            faulted = {r: e.generate(prompts, 6, **extras)
                       for r, e in engs.items()}
            combo["faulted_exact"] = bool(np.array_equal(
                faulted["fused"].tokens, faulted["free"].tokens))
            combo["faulted_differs_from_clean"] = bool(
                not np.array_equal(faulted["fused"].tokens,
                                   clean["fused"].tokens))
            combo["ber_live"] = float(faulted["fused"].bers.max()) > 0
            combo["zero_retrace"] = dict(steps.TRACE_COUNTS) == n1
            out["combos"][f"{arch}:tp{tp}"] = combo
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_shard_map_fused_generation_parity_grid():
    """Fused shard_map route vs kernel-free GSPMD route, clean AND
    faulted, across the three zoo families at tp in {2, 4, 8}: sampled
    tokens bit-identical, faults live, zero retrace across the shard
    age/BER update between the two passes."""
    proc = subprocess.run([sys.executable, "-c", PARITY_SCRIPT],
                          capture_output=True, text=True, timeout=1500,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["fused_lowering"] is True
    assert len(out["combos"]) == 6
    for combo, res in out["combos"].items():
        assert res["clean_exact"] is True, combo
        assert res["faulted_exact"] is True, combo
        assert res["faulted_differs_from_clean"] is True, combo
        assert res["ber_live"] is True, combo
        assert res["zero_retrace"] is True, combo
