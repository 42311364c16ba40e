"""Which phase of the serving program each device operation belongs to,
and what the host did inside each call, from a traced window.

A TPU trace names each operation by its HLO instruction ('fusion.565')
and carries no framework path.  The path lives in the compiled program's
metadata: ``%fusion.565 = ... metadata={op_name="jit(generate)/while/
body/closed_call/decode/attention/dot_general"}``.  ``op_paths`` reads it
from the program's HLO text (``Compiled.as_text()``), and the readings
below join it to the trace's operations by instruction name, inside the
generate programs only: an instruction name is unique within one program,
not across programs.  A scope matches a whole component of the path, so
'decode' is not found in 'decode_attention'.

The program's scopes (``jax.named_scope``): ``prefill`` and ``decode``
(``serve/steps.make_generate_fn``), ``attention``
(``models/transformer._attn_block``), ``quantize``
(``kernels/ops.quantize_int8``) and ``taps`` (``obs/taps.logit_taps``).
Its host spans (``repro.obs.spans``): ``serve.generate`` around each
``ServeEngine.generate`` call, and inside it ``serve.prepare``,
``serve.dispatch``, ``serve.wait`` and ``serve.finish``.

Every reading returns None where the window holds nothing to read: no
whole generate program, no operation in the scope, no ``serve.generate``
span.  A program built without the scopes reads None throughout.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional

import tracefile
from tracefile import Event

SERVE = "serve."                      # prefix of the program's host spans
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def op_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` path of its metadata, for every
    instruction of an HLO module's text ('' where XLA inserted the
    instruction without metadata, as it does copies)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            path = _OP_NAME.search(m.group(2))
            out[m.group(1)] = path.group(1) if path else ""
    return out


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def load_serve_spans(trace_dir: str) -> List[Event]:
    """The program's ``serve.*`` host spans in the one ``.xplane.pb``
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(Event(e.name, e.start_ns, e.end_ns)
                           for e in line.events
                           if e.name.startswith(SERVE))
    return sorted(out, key=lambda e: e.start)


@dataclasses.dataclass
class Scoped:
    """A traced window with what the scope readings need beside it."""
    trace: tracefile.Trace
    paths: Dict[str, str]         # of the generate program (``op_paths``)
    serve: List[Event]            # the program's ``serve.*`` host spans
    new_tokens: int               # tokens per request of a call

    GENERATE = r"jit_generate"

    def __post_init__(self):
        self._starts = [e.start for e in self.trace.ops]   # ops are sorted

    def programs(self) -> List[Event]:
        return tracefile.programs(self.trace, self.GENERATE)

    def program_ops(self, prog: Event) -> List[Event]:
        """The operations that start inside the program, without the
        control-flow envelopes that span the operations they run."""
        ops = self.trace.ops[bisect.bisect_left(self._starts, prog.start):
                             bisect.bisect_left(self._starts, prog.end)]
        return [e for e in ops
                if tracefile.base_name(e.name) not in tracefile.ENVELOPES]

    def scope_ns(self, prog: Event, scope: Optional[str]) -> float:
        """Device time (union, ns) of the program's operations in
        ``scope``; all of them for None."""
        ops = [e for e in self.program_ops(prog)
               if scope is None or in_scope(self.paths.get(e.name, ""),
                                            scope)]
        return tracefile.busy_ns(ops, prog.start, prog.end)


def _mean_ms(s: Scoped, scope: str, per: int = 1) -> Optional[float]:
    progs = s.programs()
    took = [s.scope_ns(p, scope) for p in progs]
    if not progs or sum(took) <= 0:
        return None
    return sum(took) / len(took) / per * 1e-6


def _share(s: Scoped, scope: str) -> Optional[float]:
    progs = s.programs()
    scoped = sum(s.scope_ns(p, scope) for p in progs)
    if scoped <= 0:
        return None
    return 100.0 * scoped / sum(s.scope_ns(p, None) for p in progs)


def prefill_ms(s: Scoped) -> Optional[float]:
    """Device time of the prompt pass, mean per program, in ms."""
    return _mean_ms(s, "prefill")


def decode_step_ms(s: Scoped) -> Optional[float]:
    """Device time of one decode step (``new_tokens`` - 1 per program),
    mean, in ms."""
    return _mean_ms(s, "decode", max(s.new_tokens - 1, 1))


def attention_share(s: Scoped) -> Optional[float]:
    """Device time in attention over the programs' busy time, in %."""
    return _share(s, "attention")


def quantize_share(s: Scoped) -> Optional[float]:
    """Device time in int8 quantisation over the programs' busy time,
    in %."""
    return _share(s, "quantize")


def taps_share(s: Scoped) -> Optional[float]:
    """Device time in the per-step telemetry taps over the programs' busy
    time, in %."""
    return _share(s, "taps")


def engine_host_ms(s: Scoped) -> Optional[float]:
    """Host time of a call outside its wait for the tokens:
    ``serve.generate`` minus the ``serve.wait`` inside it, mean over the
    calls wholly in the window, in ms."""
    lo, hi = s.trace.window
    calls = [c for c in s.serve
             if c.name == SERVE + "generate" and c.start >= lo
             and c.end <= hi]
    if not calls:
        return None
    host = [c.dur - sum(w.dur for w in s.serve
                        if w.name == SERVE + "wait" and c.start <= w.start
                        and w.end <= c.end)
            for c in calls]
    return sum(host) / len(host) * 1e-6


READINGS: Dict[str, Callable[[Scoped], Optional[float]]] = {
    f.__name__: f for f in (prefill_ms, decode_step_ms, attention_share,
                            quantize_share, taps_share, engine_host_ms)}


def coverage(s: Scoped) -> Optional[float]:
    """The share of the programs' busy time that prefill plus every decode
    step covers, in %."""
    progs = s.programs()
    busy = sum(s.scope_ns(p, None) for p in progs)
    if busy <= 0:
        return None
    phases = sum(s.scope_ns(p, "prefill") + s.scope_ns(p, "decode")
                 for p in progs)
    return 100.0 * phases / busy


def unscoped(s: Scoped, top: int = 8) -> List[list]:
    """The programs' operations outside both ``prefill`` and ``decode``,
    by base name, with their summed device seconds, longest first."""
    per: Dict[str, float] = {}
    for p in s.programs():
        for e in s.program_ops(p):
            path = s.paths.get(e.name, "")
            if not (in_scope(path, "prefill") or in_scope(path, "decode")):
                n = tracefile.base_name(e.name)
                per[n] = per.get(n, 0.0) + e.dur * 1e-9
    return [[n, t] for n, t in sorted(per.items(), key=lambda kv: -kv[1])
            ][:top]


def idle_gaps(s: Scoped, top: int = 10) -> List[list]:
    """The longest idle gaps of the window, each named by the innermost
    host span around it, the program's ``serve.*`` spans included."""
    tr = s.trace
    both = tracefile.make_trace(tr.ops, tr.modules, tr.spans + s.serve,
                                tr.window)
    return tracefile.breakdown(both, top)["idle_gaps"]
