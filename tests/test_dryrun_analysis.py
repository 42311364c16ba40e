"""Roofline extraction utilities + the scan-trip-blindness evidence that
motivates the probe methodology (launch/dryrun.py docstring)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.shapes import SHAPES, applicable
from repro.launch import analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------- #
# HLO collective parser
# --------------------------------------------------------------------------- #
HLO_SAMPLE = """
HloModule test
  %p = bf16[1024,512]{1,0} parameter(0)
  %ag = bf16[4096,512]{1,0} all-gather(%p), replica_groups={}
  %ar = f32[128]{0} all-reduce(%x), to_apply=%sum
  %t = (f32[64,32]{1,0}, f32[64,32]{1,0}) all-reduce(%a, %b), to_apply=%sum
  %rs = bf16[256,512]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = s8[32,128]{1,0} all-to-all(%z), dimensions={0}
  %cp = bf16[16,16]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %ags = bf16[2048,128]{1,0} all-gather-start(%q)
  %agd = bf16[2048,128]{1,0} all-gather-done(%ags)
  %not = bf16[9,9]{1,0} add(%p, %p)
"""


def test_collective_bytes_parser():
    got = analysis.collective_bytes(HLO_SAMPLE)
    assert got["all-gather"] == 4096 * 512 * 2 + 2048 * 128 * 2  # start once
    assert got["all-reduce"] == 128 * 4 + 2 * 64 * 32 * 4        # tuple sum
    assert got["reduce-scatter"] == 256 * 512 * 2
    assert got["all-to-all"] == 32 * 128
    assert got["collective-permute"] == 16 * 16 * 2
    assert got["total"] == sum(got[k] for k in analysis.COLLECTIVE_OPS)


def test_collective_parser_on_real_lowering():
    """Parse a real partitioned module: fully-sharded matmul -> the known
    all-reduce of the (M, N) f32 output."""
    import subprocess, sys, textwrap, json
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import make_mesh
        from repro.launch import analysis
        mesh = make_mesh((4,), ("k",))
        f = jax.jit(lambda a, b: a @ b,
                    in_shardings=(NamedSharding(mesh, P(None, "k")),
                                  NamedSharding(mesh, P("k", None))),
                    out_shardings=NamedSharding(mesh, P()))
        sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c = f.lower(sds, sds).compile()
        print("RESULT " + json.dumps(analysis.collective_bytes(c.as_text())))
    """)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    got = json.loads(line[len("RESULT "):])
    assert got["all-reduce"] == 64 * 64 * 4
    assert got["total"] == got["all-reduce"]


def test_scan_trip_blindness_documented():
    """XLA cost_analysis counts a scan body ONCE — the undercount the probe
    extrapolation in launch/dryrun.py corrects.  If this test ever fails,
    XLA fixed trip-count accounting and the probes can be retired."""
    def f(ws, x):
        return jax.lax.scan(lambda c, w: (c @ w, ()), x, ws)[0]

    w8 = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    w2 = jax.ShapeDtypeStruct((2, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def flops(wsds):
        c = jax.jit(f).lower(wsds, x).compile()
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca["flops"])

    assert flops(w8) == pytest.approx(flops(w2), rel=0.01)


# --------------------------------------------------------------------------- #
# roofline terms
# --------------------------------------------------------------------------- #
def test_roofline_terms_math():
    t = analysis.RooflineTerms(flops=197e12 * 256, hbm_bytes=819e9 * 256,
                               coll_bytes_per_dev=50e9, n_devices=256,
                               model_flops=197e12 * 128)
    assert t.t_compute == pytest.approx(1.0)
    assert t.t_memory == pytest.approx(1.0)
    assert t.t_collective == pytest.approx(1.0)
    assert t.useful_flops_frac == pytest.approx(0.5)
    assert t.roofline_frac == pytest.approx(0.5)
    assert t.dominant in ("compute", "memory", "collective")


def test_model_flops_active_params_for_moe():
    cfg = get_config("qwen3_moe_235b")
    cell = SHAPES["train_4k"]
    mf = analysis.model_flops_for(cfg, cell, 10_000)
    dense_equiv = 6 * cfg.param_count() * 10_000
    active = 6 * cfg.active_param_count() * 10_000
    assert mf == active < dense_equiv / 5      # top-8 of 128 experts


def test_analytic_hbm_model_sane():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
        size = 256
    cfg = get_config("deepseek_7b")
    hbm = analysis.analytic_hbm_bytes(cfg, SHAPES["train_4k"], FakeMesh(),
                                      microbatches=16, fsdp=True)
    assert hbm["total"] == sum(v for k, v in hbm.items() if k != "total")
    # training reads weights once per microbatch
    assert hbm["weights"] == pytest.approx(
        2 * cfg.param_count() / 16 * 16)
    dec = analysis.analytic_hbm_bytes(cfg, SHAPES["decode_32k"], FakeMesh())
    # decode is dominated by weights + cache, no optimizer traffic
    assert dec["opt"] == 0.0 and dec["cache"] > 0
    assert dec["total"] < hbm["total"]


# --------------------------------------------------------------------------- #
# shape-cell applicability (the documented skips)
# --------------------------------------------------------------------------- #
def test_long_context_applicability():
    runs, skips = [], []
    for arch in ("rwkv6_3b", "recurrentgemma_2b", "deepseek_7b",
                 "command_r_plus_104b", "whisper_large_v3"):
        ok, why = applicable(get_config(arch), SHAPES["long_500k"])
        (runs if ok else skips).append(arch)
    assert runs == ["rwkv6_3b", "recurrentgemma_2b"]
    assert len(skips) == 3


def test_input_specs_cover_all_inputs():
    from repro.launch import dryrun
    for arch in ("paligemma_3b", "whisper_large_v3", "deepseek_7b"):
        cfg = get_config(arch)
        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            spec = dryrun.input_specs(cfg, SHAPES[shape])
            assert "tokens" in spec
            if cfg.prefix_tokens:
                assert "prefix_embeds" in spec
            if cfg.n_encoder_layers:
                assert "frames" in spec
            for s in jax.tree.leaves(spec):
                assert isinstance(s, jax.ShapeDtypeStruct)
