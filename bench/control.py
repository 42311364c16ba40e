"""The control for a cell's comparison with the reference, and the
program's own readings beside it, over several seeds in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell as ``run.py`` does (set-up, a window at the
cell's own load, the reference over the same sampled requests) and also
runs the control: the reference with every matmul domain one precision
below what the cell's traffic states (bfloat16 -> int8, int8 -> int4).
At each served position the control's gap is the reference's best logit
less the reference's logit of the token the control puts first, and the
control's numbers go through the harness's own comparison with the cell's
limits.  One JSON line per seed: the program's numbers and verdict, and
the control's; the control has to come out not correct on every seed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402

STEP_DOWN = {"float32": None, "bf16": 8, "int8": 4}


def control_precision(traffic: dict) -> dict:
    return {k: STEP_DOWN[v] for k, v in traffic["device"]["precision"].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    import harness
    workdir = os.path.join(cells.ROOT, ".bench")
    os.makedirs(workdir, exist_ok=True)
    prec = control_precision(cell["traffic"])
    control_passed = 0      # seeds on which the control came out correct
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(cell, seed, args.seconds, False,
                             time.perf_counter(), workdir, controls=[prec],
                             log=lambda s: print(s, file=sys.stderr,
                                                 flush=True))
        ctrl = r["controls"][0]
        print(json.dumps({"seed": seed, "control": prec,
                          "correct": r["correct"],
                          "control_correct": ctrl["correct"],
                          "memory_peak_bytes":
                              r["device"]["memory_peak_bytes"],
                          "checks": {k: c["value"]
                                     for k, c in r["checks"].items()},
                          "control_checks": {k: c["value"] for k, c
                                             in ctrl["checks"].items()},
                          "readings": r["readings"]}),
              flush=True)
        control_passed += ctrl["correct"]
    return 1 if control_passed else 0


if __name__ == "__main__":
    sys.exit(main())
