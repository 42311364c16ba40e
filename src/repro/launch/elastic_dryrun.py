import os
import sys

_QUICK = "--quick" in sys.argv
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + ("16" if _QUICK else "512"))

"""Elastic-scaling dry-run: prove the job re-lowers after losing capacity.

Scenario: a serving fleet retires a device mid-horizon (maintenance or
failure).  The retirement is driven end to end through
:func:`repro.sched.disruption.run_retirement` — the fleet co-simulation
ages every lane under routed traffic, the retired lane leaves the
rotation with the survivors resuming *bit-exactly* from their
accumulated trap state, and the matching serving-mesh change comes back
as a :class:`repro.distributed.elastic.RemeshPlan`.  This script then
lowers+compiles the SAME train step on the degraded mesh, demonstrating
checkpoint-boundary elasticity without real hardware: the model (TP)
axis is pinned, data parallelism absorbs the delta, and microbatches
rescale so the global batch (and the training curves) are unchanged.

    PYTHONPATH=src python -m repro.launch.elastic_dryrun [--arch deepseek_7b]

``--quick`` shrinks everything (16 fake chips, reduced arch, tiny shape
cell, short co-sim) for a CI subprocess smoke test.
"""
import argparse
import dataclasses
import json

import jax

from repro.configs import get_config
from repro.configs.shapes import SHAPES, ShapeCell
from repro.distributed.sharding import make_mesh
from repro.launch import dryrun as dr
from repro.sched.disruption import run_retirement

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek_7b")
    ap.add_argument("--retire-lanes", type=int, default=1,
                    help="fleet lanes (TP groups) retired mid-horizon")
    ap.add_argument("--hot-swap", type=int, default=0,
                    help="fresh lanes taking the retired rack slots")
    ap.add_argument("--quick", action="store_true",
                    help="reduced arch + tiny mesh/cell for CI smoke")
    ap.add_argument("--out-dir", default=RESULTS,
                    help="directory the JSON report is written to")
    args = ap.parse_args()

    if args.quick:
        n_lanes, tp, epochs = 4, 2, 16
        cell = ShapeCell("train_quick", 128, 16, "train")
    else:
        n_lanes, tp, epochs = 16, 16, 48
        cell = SHAPES["train_4k"]

    # Fleet side: retire the worst rack slots, survivors keep trap state.
    out = run_retirement(n_devices=n_lanes,
                         retire=tuple(range(args.retire_lanes)),
                         hot_swap=args.hot_swap, epochs=epochs,
                         tp=tp, global_batch=cell.global_batch)
    plan = out["plan_degraded"]
    s = out["stats"]
    old_chips, new_chips = n_lanes * tp, len(out["keep"]) * tp
    print(f"[elastic] {old_chips} chips -> {new_chips} (retired lanes "
          f"{s['retired']} at epoch {s['retire_epoch']}): new mesh "
          f"{dict(zip(plan.axis_names, plan.new_shape))}, "
          f"microbatches {plan.microbatches} (global batch preserved); "
          f"survivors resumed at {s['survivor_pre_max_dvp_mv']:.1f}mV")

    # Serving side: the SAME train step compiles on the degraded mesh.
    mesh = make_mesh(plan.new_shape, plan.axis_names)
    cfg = get_config(args.arch)
    if args.quick:
        cfg = cfg.reduced()
    lowered, info = dr.build_lowered(cfg, cell, mesh,
                                     microbatches=plan.microbatches,
                                     fsdp=True, remat=True)
    compiled = lowered.compile()
    report = {"arch": args.arch, "quick": args.quick,
              "mesh": list(plan.new_shape),
              "microbatches": plan.microbatches,
              "retired": list(s["retired"]),
              "retire_epoch": int(s["retire_epoch"]),
              "survivor_pre_max_dvp_mv": float(
                  s["survivor_pre_max_dvp_mv"]),
              "fleet_max_dvp_mv": float(s["fleet_max_dvp_mv"]),
              "plan_restored": (dataclasses.asdict(out["plan_restored"])
                                if out["plan_restored"] else None),
              **info}
    mem = compiled.memory_analysis()
    if mem is not None:
        report["temp_size_in_bytes"] = int(
            getattr(mem, "temp_size_in_bytes", 0))
    os.makedirs(args.out_dir, exist_ok=True)
    out_path = os.path.join(
        args.out_dir, f"elastic__{args.arch}__{cell.name}__{new_chips}chips"
                 f"{'__quick' if args.quick else ''}.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"[elastic] degraded-mesh train step compiles: state "
          f"{report['state_bytes_per_dev'] / 2**30:.2f} GiB/dev -> "
          f"{out_path}")


if __name__ == "__main__":
    main()
