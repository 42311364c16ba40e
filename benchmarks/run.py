"""Benchmark driver: one section per paper table/figure + kernels + roofline.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig1b,...]
    PYTHONPATH=src python -m benchmarks.run --summary   # merge BENCH_*.json

``--summary`` folds every per-section ``BENCH_*.json`` record at the repo
root into one ``BENCH_summary.json`` keyed by section, so perf PRs have a
single before/after anchor instead of a dozen scattered files.
"""
from __future__ import annotations

import argparse
import sys
import time

SECTIONS = ("table1", "table2", "fig5", "scenarios", "sched",
            "disruption", "kernels", "serve", "online", "obs", "mesh",
            "resilience", "fig1b", "roofline")


def write_summary() -> str:
    """Merge all BENCH_*.json records into BENCH_summary.json."""
    import json
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    merged = {}
    for path in sorted(root.glob("BENCH_*.json")):
        if path.name == "BENCH_summary.json":
            continue
        section = path.stem[len("BENCH_"):]
        try:
            merged[section] = json.loads(path.read_text())
        except json.JSONDecodeError as e:
            merged[section] = {"error": f"unreadable: {e}"}
    out = root / "BENCH_summary.json"
    out.write_text(json.dumps({"sections": sorted(merged),
                               "records": merged}, indent=2) + "\n")
    return f"[recorded] {out.name} ({len(merged)} sections: " \
           f"{', '.join(sorted(merged))})"


def _run_mesh() -> str:
    """mesh_bench needs several devices.  Where this process already sees
    them (a multi-chip host) it runs here: this process holds the chips,
    so a child could not reach them.  On a one-device host it runs in a
    CPU-only child that fakes 8 host devices via XLA_FLAGS, which jax only
    reads at init."""
    import os
    import subprocess

    import jax
    if len(jax.devices()) >= 2:
        from . import mesh_bench
        return mesh_bench.run(quick=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.mesh_bench", "--quick"],
        capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return proc.stdout.rstrip()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {SECTIONS}")
    ap.add_argument("--summary", action="store_true",
                    help="merge all BENCH_*.json into BENCH_summary.json "
                         "(no benchmarks are run)")
    args = ap.parse_args()
    if args.summary:
        print(write_summary())
        return
    want = args.only.split(",") if args.only else list(SECTIONS)

    runners = {}
    if "table1" in want:
        from . import table1_aging
        runners["table1"] = table1_aging.run
    if "table2" in want:
        from . import table2_policy
        runners["table2"] = table2_policy.run
    if "fig5" in want:
        from . import fig5_curves
        runners["fig5"] = fig5_curves.run
    if "scenarios" in want:
        from . import scenario_bench
        runners["scenarios"] = scenario_bench.run
    if "sched" in want:
        from . import sched_bench
        runners["sched"] = sched_bench.run
    if "disruption" in want:
        from . import disruption_bench
        runners["disruption"] = disruption_bench.run
    if "kernels" in want:
        from . import kernel_bench
        runners["kernels"] = kernel_bench.run
    if "serve" in want:
        from . import serve_bench
        runners["serve"] = serve_bench.run
    if "online" in want:
        from . import online_bench
        runners["online"] = online_bench.run
    if "obs" in want:
        from . import obs_bench
        runners["obs"] = obs_bench.run
    if "mesh" in want:
        runners["mesh"] = _run_mesh
    if "resilience" in want:
        from . import resilience_bench
        runners["resilience"] = resilience_bench.run
    if "fig1b" in want:
        from . import fig1b_ber
        runners["fig1b"] = fig1b_ber.run
    if "roofline" in want:
        from . import roofline
        runners["roofline"] = roofline.run

    failed = []
    for name in want:
        if name not in runners:
            continue
        t0 = time.time()
        print(f"\n{'#' * 72}\n# benchmark: {name}\n{'#' * 72}")
        try:
            out = runners[name]()
            print(out)
        except Exception as e:                      # pragma: no cover
            failed.append(name)
            print(f"[ERROR] {name}: {type(e).__name__}: {e}")
        print(f"# ({name} took {time.time() - t0:.1f}s)")
    if failed:
        print(f"\nFAILED sections: {failed}")
        sys.exit(1)
    print("\n" + write_summary())
    print("All benchmark sections completed.")


if __name__ == "__main__":
    main()
