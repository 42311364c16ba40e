"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote, with nothing but
``jax.profiler.ProfileData``, into plain ``Event`` lists: the device's
operations ("XLA Ops") and programs ("XLA Modules") of the first TPU, and
the harness's own host spans.  Everything after that is arithmetic on
intervals, kept apart so a test can feed it events by hand.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns on the profiler's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[Event]          # device operations
    modules: List[Event]      # device programs
    spans: List[Event]        # the harness's host spans
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


HOST_SPANS = ("window", "prep", "generate", "harvest")


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops, modules, spans = [], [], []
    device = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            device = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Event(op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend(Event(e.name, e.start_ns, e.end_ns)
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in HOST_SPANS)
    if device is None:
        raise RuntimeError("the trace holds no TPU device plane")
    windows = [s for s in spans if s.name == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one 'window' span, found {len(windows)}")
    window = (windows[0].start, windows[0].end)
    check_aligned(ops, window)
    return make_trace(ops, modules, spans, window)


_HLO_TEXT = re.compile(r"^%?([^\s=]+) = ")


def op_name(text: str) -> str:
    """The instruction name of a device operation.  A TPU trace names its
    operations by the whole HLO text, '%fusion.565 = bf16[4096]{...}
    fusion(...), kind=kLoop, ...'; this keeps 'fusion.565'."""
    m = _HLO_TEXT.match(text)
    return m.group(1) if m else text


def check_aligned(ops: List[Event], window: Tuple[float, float]) -> None:
    """The host's window span has to hold device operations: where it holds
    none, the host and device clocks do not line up, or the device ran
    nothing, and no number read from the trace would mean anything."""
    if not any(e.end > window[0] and e.start < window[1] for e in ops):
        extent = ((min(e.start for e in ops), max(e.end for e in ops))
                  if ops else None)
        raise RuntimeError(f"no device operation lies in the host's 'window' "
                           f"span {window}; {len(ops)} device operations "
                           f"span {extent}")


def describe(trace: Trace, top: int = 12) -> str:
    """What the window holds, for an error message: the most frequent
    program and operation names."""
    def common(evs):
        c = collections.Counter(base_name(e.name) for e in evs)
        return c.most_common(top)
    return (f"window {trace.window}, programs {common(trace.modules)}, "
            f"operations {common(trace.ops)}")


def make_trace(ops, modules, spans, window) -> Trace:
    """Keep what lies in the window, sorted by start."""
    lo, hi = window
    inside = lambda evs: sorted((e for e in evs
                                 if e.end > lo and e.start < hi),
                                key=lambda e: e.start)
    return Trace(inside(ops), inside(modules),
                 sorted(spans, key=lambda e: e.start), window)


def union(events: List[Event], lo: float, hi: float) -> List[Tuple]:
    """Merged (start, end) intervals that the events cover in [lo, hi]."""
    out: List[list] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(x) for x in out]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    return sum(t - s for s, t in union(events, lo, hi))


def idle_gaps(trace: Trace) -> List[Tuple[float, float]]:
    """(start, end) of every stretch of the window with no device op."""
    lo, hi = trace.window
    gaps, at = [], lo
    for s, t in union(trace.ops, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def programs(trace: Trace, pattern: str) -> List[Event]:
    """Device programs whose name matches ``pattern``, wholly in the
    window."""
    lo, hi = trace.window
    rx = re.compile(pattern)
    return [m for m in trace.modules
            if rx.search(m.name) and m.start >= lo and m.end <= hi]


def ops_within(trace: Trace, spans: List[Event], pattern: str) -> float:
    """Summed device time (ns) of ops matching ``pattern`` that start
    inside one of ``spans``."""
    rx = re.compile(pattern)
    total = 0.0
    for e in trace.ops:
        if rx.search(e.name) and any(s.start <= e.start < s.end
                                     for s in spans):
            total += e.dur
    return total


def host_label(trace: Trace, at: float) -> str:
    """The innermost harness span around time ``at`` ('window' if only
    the loop itself)."""
    best: Optional[Event] = None
    for s in trace.spans:
        if s.start <= at < s.end and s.name != "window" and (
                best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else "window"


def base_name(op: str) -> str:
    """An op's name without its instruction number: 'fusion.12' -> 'fusion'."""
    return re.sub(r"\.\d+$", "", op)


# control flow whose event spans the operations it runs: ranked beside
# them, a decode loop's 'while' would count their time again
ENVELOPES = ("while", "conditional", "call")


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time, and the longest idle
    gaps named by what the host was doing in them, in seconds."""
    per_op: Dict[str, float] = collections.defaultdict(float)
    lo, hi = trace.window
    for e in trace.ops:
        if base_name(e.name) in ENVELOPES:
            continue
        per_op[base_name(e.name)] += min(e.end, hi) - max(e.start, lo)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[host_label(trace, (s + t) / 2), (t - s) * 1e-9]
                          for s, t in gaps]}
