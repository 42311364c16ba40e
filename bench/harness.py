"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the metrics.

The window is a closed loop of the engine's ``generate`` calls (a
``ServeEngine``, or a ``MeshServeEngine`` on several chips), one after
another, each on fresh uniform-token prompts drawn on the host from the
seed.  A request is due when its call starts and done when the call
returns its tokens.  In an aged cell every ``fault_free_every``-th call
(the first among them) is served at BER 0 through the same compiled
program: those calls are compared with the reference token by token,
and the others are checked to be upset at all.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import math
import os
import shutil
import sys
import time
from typing import List

import jax
import numpy as np

import cells
import program
import reference
import tracefile
import weights

TRACE_DIR = "trace"             # under the run's work directory


@dataclasses.dataclass
class Call:
    prompts: np.ndarray
    tokens: np.ndarray
    t_due: float
    t_done: float
    fault_free: bool
    bers: object            # {op: BER}, or a mesh engine's (S, O) array
    operators: tuple = None  # the columns of a mesh engine's ``bers``


def served_bers(call: Call) -> dict:
    """{op: (BER of each shard, ...)} that a call was served at."""
    if call.operators is None:
        return {op: (float(b),) for op, b in call.bers.items()}
    return {op: tuple(float(b) for b in call.bers[:, i])
            for i, op in enumerate(call.operators)}


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, stream])


class CompileWatch:
    """Counts JAX traces and compiles, and the program's own retraces."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.events += 1

    def snapshot(self) -> dict:
        out = program.compile_counts()
        out["jax:trace_or_compile"] = self.events
        return out

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in set(before) | set(after)
                if after.get(k, 0) != before.get(k, 0)}


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _sample(rng, calls: List[Call], want: int):
    """Up to ``want`` requests of ``calls``, drawn from the seed: (prompts,
    served tokens)."""
    rows = [(c, i) for c in calls for i in range(c.tokens.shape[0])]
    pick = rng.permutation(len(rows))[:want]
    pick.sort()
    return (np.stack([rows[j][0].prompts[rows[j][1]] for j in pick]),
            np.stack([rows[j][0].tokens[rows[j][1]] for j in pick]))


def verdict(checks: dict) -> bool:
    """Correct when every compared number is within its limit."""
    return all(v <= lim for v, lim in checks.values())


def check(cell: dict, fam, dims, seed: int, calls: List[Call],
          compiles: dict, controls=()) -> tuple:
    """The numbers compared with their limits: ({name: (value, limit)},
    one such dict per control, further readings that set limits).  A
    control is the reference at a lower precision (``controls``) put in the
    program's place: its numbers are the ones its own served tokens would
    give, read at the same positions and held to the same limits, and it
    has to come out not correct."""
    traffic, limits = cell["traffic"], cell["limits"]
    rng = seed_rng(seed, 2)
    aged = traffic["device"]["route"] != "clean"
    exact = [c for c in calls if c.fault_free or not aged]
    faulted = [c for c in calls if aged and not c.fault_free]
    p_exact, s_exact = _sample(rng, exact, traffic["check_requests"])
    n_exact = len(p_exact)
    prompts, served = p_exact, s_exact
    if faulted:
        p_f, s_f = _sample(rng, faulted, traffic["check_faulted_requests"])
        prompts = np.concatenate([p_exact, p_f])
        served = np.concatenate([s_exact, s_f])
    r = reference.readings(fam, dims, seed, prompts, served,
                           controls=controls,
                           chips=cell["workload"].get("chips", 1))
    best = r["best"][:n_exact]
    # the widest shortfall, and the mean one: a token chosen against a
    # logit error e falls short by about e where the race was within e,
    # so the mean grows as e squared and parts precisions that the widest,
    # growing as e, barely parts
    def gaps(logit):
        short = best - logit[:n_exact]
        return {"gap": (float(np.max(short)), limits["gap"]),
                "mean_gap": (float(np.mean(short)), limits["mean_gap"])}

    out = gaps(r["at_served"])
    control_checks = [gaps(c) for c in r["at_control"]]
    if faulted:
        top1 = float(np.mean(served[n_exact:] == r["argmax"][n_exact:]))
        out["faulted_top1"] = (top1, limits["faulted_top1"])
        # the stated BER of each shard, or one for every shard
        stated = traffic["device"]["ber"]
        worst = 0.0
        for c in faulted:
            got_bers = served_bers(c)
            for op, ber in stated.items():
                got = got_bers.get(op, (0.0,))
                for g, b in zip(got, np.broadcast_to(ber, len(got))):
                    # a BER served as 0 reads as some 300 decades off
                    worst = max(worst, abs(math.log10(max(g, 1e-300) / b)))
        out["ber_decades"] = (worst, traffic["device"]["ber_decades"])
    out["window_compiles"] = (float(sum(compiles.values())), 0.0)
    # what faulted_top1 would read with the upsets left out: the share of
    # the fault-free calls' tokens that are the reference's argmax
    readings = {"fault_free_top1": float(np.mean(
        s_exact == r["argmax"][:n_exact]))}
    return out, control_checks, readings


def window(engine, fault_free, traffic: dict, vocab: int, seed: int,
           seconds: float) -> tuple:
    """The closed loop.  Returns (calls, t_start, t_end)."""
    from jax.profiler import TraceAnnotation
    B, S, N = (traffic["batch"], traffic["prompt_tokens"],
               traffic["new_tokens"])
    every = traffic.get("fault_free_every") if fault_free is not None else 0
    rng = seed_rng(seed, 1)
    calls: List[Call] = []
    with TraceAnnotation("window"):
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            with TraceAnnotation("prep"):
                prompts = rng.integers(0, vocab, (B, S), dtype=np.int32)
                free = bool(every) and i % every == 0
                eng = fault_free if free else engine
            t_due = time.perf_counter()
            with TraceAnnotation("generate"):
                res = eng.generate(prompts, N)
            t_done = time.perf_counter()
            with TraceAnnotation("harvest"):
                calls.append(Call(prompts, res.tokens, t_due, t_done, free,
                                  res.bers, getattr(res, "operators", None)))
            i += 1
        t1 = time.perf_counter()
    return calls, t0, t1


def _malformed(call: Call, n: int, vocab: int) -> int:
    t = call.tokens
    if t.shape != (call.prompts.shape[0], n):
        return call.prompts.shape[0]
    return int(np.sum((t < 0).any(1) | (t >= vocab).any(1)))


@dataclasses.dataclass
class Served:
    """A cell's model and engines, built from the seed and warmed."""
    fam: object
    dims: object
    devices: list
    engine: object
    fault_free: object       # the BER-0 twin, or None for a clean cell


def set_up(cell: dict, seed: int) -> Served:
    """The cell's family, sizes and weights, its engines on its chips, and
    one warm call of the window's shape.  On several chips every weight is
    made in place, each chip's share on that chip."""
    traffic = cell["traffic"]
    fam = cells.family(cell["config"])
    dims = fam.read_dims(cell["config_name"], cell["config"])
    devices = jax.devices()[:cell["workload"].get("chips", 1)]
    cfg = fam.model_config(dims)
    mesh = place = None
    if len(devices) > 1:
        mesh = program.serve_mesh(devices)
        place = functools.partial(program.param_shardings, cfg, mesh)
    params = weights.build_params(fam, dims, seed, place)
    B, S, N = (traffic["batch"], traffic["prompt_tokens"],
               traffic["new_tokens"])
    engine, fault_free = program.engines(cfg, params, traffic["device"],
                                         max_len=S + N + 1,
                                         seed=seed % 2 ** 31, mesh=mesh)
    warm = seed_rng(seed, 0).integers(0, dims.vocab, (B, S), dtype=np.int32)
    engine.generate(warm, N)
    if fault_free is not None:
        fault_free._fault_config()
    return Served(fam, dims, devices, engine, fault_free)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, workdir: str, controls=(),
             log=print) -> dict:
    """Everything after the look for a chip.  Returns the result object,
    with the compared numbers under ``checks``."""
    traffic = cell["traffic"]
    program.enable_compile_cache()
    # every program, however quick to compile, comes from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch()
    sv = set_up(cell, seed)
    fam, dims, devices = sv.fam, sv.dims, sv.devices
    peak_table = peaks(devices[0].device_kind) if trace else None
    B, S, N = (traffic["batch"], traffic["prompt_tokens"],
               traffic["new_tokens"])
    setup_s = time.perf_counter() - t_start
    log(f"[bench] set-up {setup_s:.3f} s: {dims.name} {dims.n_layers} "
        f"layers, batch {B} x prompt {S} + {N} new, "
        f"route {traffic['device']['route']}, {len(devices)} chip(s)")

    trace_dir = None
    if trace:
        trace_dir = os.path.join(workdir, TRACE_DIR)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    before = watch.snapshot()
    calls, t0, t1 = window(sv.engine, sv.fault_free, traffic, dims.vocab,
                           seed, seconds)
    compiles = CompileWatch.delta(before, watch.snapshot())
    if trace:
        jax.profiler.stop_trace()
    log(f"[bench] window {t1 - t0:.3f} s, {len(calls)} calls; compiles and "
        f"traces inside it: {compiles or 0}")
    took = [c.t_done - c.t_due for c in calls]
    log(f"[bench] call seconds: first {took[0]:.4f}, median "
        f"{float(np.median(took)):.4f}, slowest {max(took):.4f}")
    peak = _peak_bytes(devices)
    del sv
    gc.collect()

    lat = np.asarray([c.t_done - c.t_due for c in calls
                      for _ in range(c.tokens.shape[0])])
    failed = sum(_malformed(c, N, dims.vocab) for c in calls)
    ok_calls = [c for c in calls if _malformed(c, N, dims.vocab) == 0]
    checks, control_checks, readings = check(cell, fam, dims, seed,
                                             ok_calls, compiles, controls)
    dev = devices[0]
    result = {
        "correct": failed == 0 and verdict(checks),
        "attempted": int(lat.size),
        "failed": int(failed),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if not trace:
        e2e = {
            "tok_s": sum(c.tokens.size for c in calls) / (t1 - t0),
            "req_p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        try:
            tr = tracefile.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(tr, dims, traffic, peak_table, len(devices))
        for m in cell["per_layer"]:
            v = cells.metric_reader(m["name"])(ctx)
            if v is None:
                # the cell is listed for this metric, so the trace has to
                # hold what it reads
                raise RuntimeError(f"per-layer metric {m['name']} found "
                                   f"nothing to read in the trace; "
                                   f"{tracefile.describe(tr)}")
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = tracefile.busy_ns(
            tr.ops, *tr.window) * 1e-9
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = tracefile.breakdown(tr)
    as_json = lambda cs: {k: {"value": v, "limit": lim}
                          for k, (v, lim) in cs.items()}
    if controls:
        result["controls"] = [{"correct": verdict(cs), "checks": as_json(cs)}
                              for cs in control_checks]
        result["readings"] = readings
    result["checks"] = as_json(checks)
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    trace: object
    dims: object
    traffic: dict
    peak: dict
    chips: int = 1           # the trace is the first chip's

    # one chip's generate program, or the one over a mesh
    GENERATE = r"jit_generate|jit_sharded_gen"

    @property
    def aged(self) -> bool:
        return self.traffic["device"]["route"] != "clean"

    def generate_programs(self):
        return tracefile.programs(self.trace, self.GENERATE)

    def call_shape(self):
        t = self.traffic
        return t["batch"], t["prompt_tokens"], t["new_tokens"]


def peaks(device_kind: str) -> dict:
    with open(os.path.join(cells.HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, and the result object as the last line of standard
    output."""
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
