"""Run one benchmark cell on the chip and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics, the device's busy and window seconds, and a
breakdown.  Either way the served tokens are compared with the plain
reference once the window has closed, and each number compared is printed
beside its limit, last on standard error and under ``checks`` in the
result.  The last line of standard output is the result object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402

WORKDIR = os.path.join(cells.ROOT, ".bench")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.load_cell(args.workload)
    import jax
    devices = jax.devices()
    want = int(cell["workload"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: {args.workload} needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    import harness
    os.makedirs(WORKDIR, exist_ok=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, WORKDIR,
                              log=lambda s: print(s, file=sys.stderr,
                                                  flush=True))
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
