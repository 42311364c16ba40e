"""Benchmark: mesh-sharded serving — one sharded dispatch, per-shard aging.

On a one-device host it must own its process: it fakes 8 host devices via
``XLA_FLAGS`` *before* jax initialises (run ``PYTHONPATH=src python -m
benchmarks.mesh_bench``; ``benchmarks.run --only mesh`` shells out here
for the same reason, and runs it in-process where the devices exist).

Measures, on a reduced decoder-only config over a ``("data", "model")``
mesh with tp=8:

* **sharded vs single-device generation**: compile time, warm whole-call
  wall, decode tokens/sec for the SAME cast params — plus the bit-exactness
  check the serve layout guarantees (clean graphs; the full parity matrix
  lives in ``tests/test_serve_sharded.py``).
* **per-shard aging inside one dispatch**: a shard-granular
  :class:`~repro.core.fleet.FleetRuntime` (``n_shards=8``) with staggered
  shard ages served by :class:`~repro.serve.sharded.MeshServeEngine`;
  structural guards assert the served per-shard BERs differ across shards
  and that advancing shard ages re-jits nothing
  (``serve.steps.TRACE_COUNTS``).

On the CPU container the 8 "devices" share one physical core, so sharded
wall-clock carries partitioning overhead rather than speedup — the numbers
to read are compile cost, the zero-retrace property and the parity flag.
Results are recorded to ``BENCH_mesh.json`` at the repo root.
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config
from repro.core.fleet import FleetRuntime
from repro.data import SyntheticLM
from repro.serve import steps as serve_steps
from repro.serve.engine import ServeEngine
from repro.serve.sharded import MeshServeEngine

from .common import check, table

ARCH = "deepseek_7b"


def _timed(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _setup(batch: int, prompt_len: int):
    from repro.train.steps import init_train_state
    cfg = get_config(ARCH).reduced()
    params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    data = SyntheticLM(vocab=cfg.vocab, seq_len=prompt_len,
                      global_batch=batch)
    return cfg, params, data.batch_at(0).tokens


def bench_sharded_dispatch(quick: bool):
    B, S = 2, 8
    n_steps = 4 if quick else 12
    reps = 2
    cfg, params, prompts = _setup(B, S)
    max_len = S + n_steps + 1

    eng = MeshServeEngine(cfg, params, max_len=max_len, seed=0)
    tp = eng.tp
    t0 = time.perf_counter()
    a = eng.generate(prompts, n_steps)
    compile_sharded = time.perf_counter() - t0
    t_sharded = _timed(lambda: eng.generate(prompts, n_steps), reps)

    host_params = jax.device_get(eng.params)
    single = ServeEngine(cfg, host_params, max_len=max_len, seed=0)
    t0 = time.perf_counter()
    b = single.generate(prompts, n_steps)
    compile_single = time.perf_counter() - t0
    t_single = _timed(lambda: single.generate(prompts, n_steps), reps)

    exact = bool(np.array_equal(a.tokens, b.tokens))
    total = B * n_steps
    rows = [["single-device scanned", f"{compile_single:.1f}s",
             f"{t_single * 1e3:.0f}ms", f"{total / t_single:.0f}"],
            [f"mesh-sharded tp={tp}", f"{compile_sharded:.1f}s",
             f"{t_sharded * 1e3:.0f}ms", f"{total / t_sharded:.0f}"]]
    txt = table(f"Mesh-sharded serving (clean graph, B={B}, {n_steps} "
                "steps, 8 faked host devices)",
                ["path", "compile", "wall", "tok/s"], rows)
    txt += "\n" + check("sharded generation bit-exact vs single device",
                        exact)
    return txt, {"tp": tp, "compile_sharded_s": compile_sharded,
                 "compile_single_s": compile_single,
                 "sharded_tok_s": total / t_sharded,
                 "single_tok_s": total / t_single, "bit_exact": exact}


def _decode_weight_matmul_shapes(cfg, B: int) -> list:
    """(M, K, N) of every weight matmul one faulted decode token executes
    (the ``op_linear`` domains — q/k/v/o, the gated MLP, the unembed)."""
    d, hd = cfg.d_model, cfg.head_dim
    per_layer = [(B, d, cfg.n_heads * hd),            # q
                 (B, d, cfg.n_kv_heads * hd),         # k
                 (B, d, cfg.n_kv_heads * hd),         # v
                 (B, cfg.n_heads * hd, d),            # o
                 (B, d, cfg.d_ff), (B, d, cfg.d_ff),  # gate, up
                 (B, cfg.d_ff, d)]                    # down
    return per_layer * cfg.n_layers + [(B, d, cfg.vocab)]    # + unembed


def _route_bytes_per_token(cfg, B: int, tp: int) -> dict:
    """Analytic HBM bytes/decode-token of the two vector-BER routes.

    Reuses ``kernel_bench._hbm_bytes`` (the model the fused-vs-three-pass
    kernel bench validated).  The fused shard_map route runs the kernel on
    each shard's (M, N/tp) column block and, unlike the single-device fused
    kernel, returns the int32 accumulator for the shared external dequant
    epilogue (cross-route bit-exactness — see ``_fused_aged_matmul_sharded``),
    so it pays one extra int32 round-trip per output word on top of the
    fully-fused count.  Non-divisible output dims stay on the kernel-free
    route in both columns (same downgrade the real graph takes).  Shapes are
    padded to their resolved blocks exactly as the wrappers pad."""
    from repro.kernels.ops import _ceil_mult
    from .kernel_bench import _hbm_bytes

    def one(M, K, N, fused):
        bm, bn = _ceil_mult(M, 256), _ceil_mult(N, 256)
        bk = _ceil_mult(K, 256)
        Mp, Np = -(-M // bm) * bm, -(-N // bn) * bn
        b = _hbm_bytes(Mp, -(-K // bk) * bk, Np, bm, bn, fused=fused)
        if fused:
            b += 8 * Mp * Np        # int32 acc write + dequant re-read
        return b

    three_pass = fused = 0
    for M, K, N in _decode_weight_matmul_shapes(cfg, B):
        three_pass += one(M, K, N, False)
        if N % tp == 0:
            fused += tp * one(M, K, N // tp, True)
        else:                        # divisibility fallback: both routes
            fused += one(M, K, N, False)   # stay three-pass kernel-free
    return {"bytes_per_token_three_pass": three_pass,
            "bytes_per_token_fused": fused,
            "bytes_saved_ratio": three_pass / max(fused, 1)}


def bench_per_shard_aging(quick: bool):
    B, S = 2, 8
    n_steps = 3 if quick else 8
    cfg, params, prompts = _setup(B, S)
    max_len = S + n_steps + 1
    tp = len(jax.devices())

    fleet = FleetRuntime(n_devices=1, n_shards=tp)
    for s in range(tp):
        fleet.set_age(years=9.0 * (s + 1) / tp, shard=s)
    engines = {route: MeshServeEngine(cfg, params, fleet=fleet,
                                      max_len=max_len, seed=0,
                                      use_fused_kernel=(route == "fused"))
               for route in ("fused", "kernel_free")}

    res, r1, r2 = {}, {}, {}
    rows = []
    for route, eng in engines.items():
        t0 = time.perf_counter()
        r1[route] = eng.generate(prompts, n_steps)
        compile_s = time.perf_counter() - t0
        before = dict(serve_steps.TRACE_COUNTS)
        fleet.advance(3.15e7, shard=1)           # one shard ages a year
        r2[route] = eng.generate(prompts, n_steps)
        fleet.advance(-3.15e7, shard=1)          # rewind: same ages for both
        zero_retrace = dict(serve_steps.TRACE_COUNTS) == before
        t_warm = _timed(lambda: eng.generate(prompts, n_steps), 2)
        res[route] = {"compile_s": compile_s,
                      "warm_tok_s": B * n_steps / t_warm,
                      "zero_retrace": zero_retrace}
        rows.append([f"{route} tp={tp}", f"{compile_s:.1f}s",
                     f"{t_warm * 1e3:.0f}ms", f"{B * n_steps / t_warm:.0f}"])

    parity = bool(np.array_equal(r1["fused"].tokens,
                                 r1["kernel_free"].tokens)
                  and np.array_equal(r2["fused"].tokens,
                                     r2["kernel_free"].tokens))
    shard_bers_differ = bool(len(np.unique(r1["fused"].bers[:, 0])) > 1)
    zero_retrace = all(r["zero_retrace"] for r in res.values())
    bytes_ = _route_bytes_per_token(cfg, B, tp)

    txt = table("Per-shard aging inside ONE sharded dispatch "
                "(fused shard_map kernel vs kernel-free GSPMD)",
                ["route", "compile", "wall", "tok/s"], rows)
    txt += "\n" + check("fused and kernel-free routes sample identical "
                        "tokens (before AND after aging)", parity)
    txt += "\n" + check("served per-shard BERs differ across mesh shards",
                        shard_bers_differ,
                        f"BER(q) spread {r1['fused'].bers[:, 0].min():.1e} "
                        f"-> {r1['fused'].bers[:, 0].max():.1e}")
    txt += "\n" + check("shard age advance + BER update re-jits nothing "
                        "(both routes)", zero_retrace)
    txt += "\n" + check(
        "fused route saves analytic HBM bytes per decode token",
        bytes_["bytes_saved_ratio"] > 1.0,
        f"{bytes_['bytes_per_token_three_pass'] / 2**20:.2f} MiB -> "
        f"{bytes_['bytes_per_token_fused'] / 2**20:.2f} MiB "
        f"({bytes_['bytes_saved_ratio']:.2f}x)")
    return txt, {"compile_s": res["fused"]["compile_s"],
                 "warm_tok_s": res["fused"]["warm_tok_s"],
                 "kernel_free_compile_s": res["kernel_free"]["compile_s"],
                 "kernel_free_warm_tok_s": res["kernel_free"]["warm_tok_s"],
                 "routes_bit_exact": parity,
                 **bytes_,
                 "shard_bers_differ": shard_bers_differ,
                 "zero_retrace": zero_retrace,
                 "ber_q_per_shard": r1["fused"].bers[:, 0].tolist(),
                 "tokens_changed_after_aging":
                     bool(not np.array_equal(r1["fused"].tokens,
                                             r2["fused"].tokens))}


def run(quick: bool = False) -> str:
    assert len(jax.devices()) >= 2, \
        "mesh_bench needs faked host devices; run it as its own process"
    txt1, disp = bench_sharded_dispatch(quick)
    txt2, aging = bench_per_shard_aging(quick)
    out = "\n".join([txt1, txt2])

    record = {"arch": ARCH, "mode": "quick" if quick else "full",
              "backend": jax.default_backend(),
              "n_devices": len(jax.devices()),
              "dispatch": disp, "per_shard_aging": aging}
    path = Path(__file__).resolve().parent.parent / "BENCH_mesh.json"
    path.write_text(json.dumps(record, indent=2) + "\n")
    out += f"\n[recorded] {path.name}"
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI")
    args = ap.parse_args()
    out = run(quick=args.quick)
    print(out)
    if "[FAIL]" in out:
        raise SystemExit(1)
