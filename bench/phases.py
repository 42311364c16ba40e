"""Split a cell's generate calls into the program's phases, on the chip.

    python bench/phases.py --workload <cell> --seed <n> --seconds <s>
                           [--slice <file.json.gz>]

Runs the cell as ``run.py`` does up to the window (set-up, one warm
call), then two windows of the same calls on the same prompts: one with
the profiler off and one traced.  From the traced window it prints, as
one JSON line, the readings of ``scopes.READINGS`` (``prefill_ms``,
``decode_step_ms``, ``attention_share``, ``quantize_share``,
``taps_share``, ``engine_host_ms``), the share of the programs' busy time
that prefill and decode cover and the largest operations outside both,
the longest idle gaps named by the program's ``serve.*`` spans, and the
median call seconds of each window: the cost of tracing.  The scope of
each operation comes from the compiled generate program's HLO metadata,
joined on the instruction name; ``joined`` is the share of the programs'
operations whose instruction the HLO text holds, and has to be 1.

``--slice`` writes one whole call of the traced window (its operations,
programs, host spans and the scope paths of its instructions) to a
gzipped JSON file, for ``bench/tests/test_scopes.py``.  The reference is not
run and nothing is compared: the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cells  # noqa: E402


def generate_hlo(engine, batch: int, prompt_len: int, n_steps: int) -> str:
    """The optimised HLO text of the program ``engine.generate`` runs for
    this shape: the same jitted function, lowered on the same arguments,
    so the compile cache hands back the same executable."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import _generate_fn
    gen = _generate_fn(engine.cfg, engine.max_len, n_steps, None)
    prompts = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    return gen.lower(engine.params, prompts, engine._fault_config(),
                     jax.random.PRNGKey(0),
                     jnp.float32(0.0)).compile().as_text()


def pack_ops(rows: list) -> dict:
    """[name, start, end] rows, sorted by start, as columns that compress
    well: an index into the sorted names, the start's step from the row
    before, and the duration."""
    names = sorted({r[0] for r in rows})
    at = {n: i for i, n in enumerate(names)}
    starts = [r[1] for r in rows]
    return {"names": names, "name": [at[r[0]] for r in rows],
            "step": [b - a for a, b in zip([0] + starts, starts)],
            "dur": [r[2] - r[1] for r in rows]}


def call_slice(s, traffic: dict) -> dict:
    """One whole call of the traced window: the harness's ``generate``
    span around the second generate program (the first if only one),
    with every event in it, times in ns from the span's start and the
    operations packed by ``pack_ops``."""
    progs = s.programs()
    prog = progs[min(1, len(progs) - 1)]
    span = next(e for e in s.trace.spans if e.name == "generate"
                and e.start <= prog.start and prog.end <= e.end)
    lo, hi = span.start, span.end
    rows = lambda evs: [[e.name, round(e.start - lo), round(e.end - lo)]
                        for e in evs if e.end > lo and e.start < hi]
    within = lambda evs: [e for e in evs if lo <= e.start and e.end <= hi]
    ops = rows(s.trace.ops)
    return {"new_tokens": traffic["new_tokens"],
            "window": [0, round(hi - lo)],
            "modules": rows(s.trace.modules),
            "spans": rows(within(s.trace.spans) + within(s.serve)),
            "ops": pack_ops(ops),
            "paths": {n: s.paths.get(n, "") for n in {r[0] for r in ops}}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("phases: needs a TPU", file=sys.stderr)
        return 3
    if cell["workload"]["chips"] != 1:
        # generate_hlo lowers the one-chip engine's program
        print("phases: reads one-chip cells only", file=sys.stderr)
        return 3

    import harness
    import program
    import scopes
    import tracefile

    traffic = cell["traffic"]
    program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    B, S, N = (traffic["batch"], traffic["prompt_tokens"],
               traffic["new_tokens"])
    sv = harness.set_up(cell, args.seed)
    engine, fault_free, vocab = sv.engine, sv.fault_free, sv.dims.vocab

    def median_call_s(calls):
        return statistics.median(c.t_done - c.t_due for c in calls)

    untraced, _, _ = harness.window(engine, fault_free, traffic, vocab,
                                    args.seed, args.seconds)
    trace_dir = os.path.join(cells.ROOT, ".bench", "phases_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    traced, _, _ = harness.window(engine, fault_free, traffic, vocab,
                                  args.seed, args.seconds)
    jax.profiler.stop_trace()
    paths = scopes.op_paths(generate_hlo(engine, B, S, N))
    try:
        tr = tracefile.load(trace_dir)
        serve = scopes.load_serve_spans(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    s = scopes.Scoped(tr, paths, serve, N)
    in_progs = [e for p in s.programs() for e in s.program_ops(p)]
    out = {"workload": args.workload, "seed": args.seed,
           "programs": len(s.programs()),
           "joined": (sum(e.name in paths for e in in_progs)
                      / max(len(in_progs), 1)),
           "readings": {k: f(s) for k, f in scopes.READINGS.items()},
           "coverage": scopes.coverage(s),
           "busy_s": sum(s.scope_ns(p, None) for p in s.programs()) * 1e-9,
           "unscoped": scopes.unscoped(s),
           "idle_gaps": scopes.idle_gaps(s),
           "call_s": {"untraced": median_call_s(untraced),
                      "traced": median_call_s(traced),
                      "calls": [len(untraced), len(traced)]}}
    print(json.dumps(out), flush=True)
    if args.slice:
        os.makedirs(os.path.dirname(os.path.abspath(args.slice)),
                    exist_ok=True)
        with gzip.open(args.slice, "wt") as f:
            json.dump(call_slice(s, traffic), f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
