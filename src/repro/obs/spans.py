"""Host spans on the profiler's clock.

:class:`span` is a :class:`jax.profiler.TraceAnnotation` that also keeps
its own wall-clock seconds.  Under a running profiler the span lands in
the same trace as the device's programs and operations, on the same
clock, so a reduction of the trace can say what the host was doing while
the device sat idle.  With the profiler off an annotation costs well
under a microsecond, so the serving path keeps its spans on always.

Span names used by the serving path (``repro.serve.engine``):

* ``serve.generate`` — one whole ``generate`` call, and inside it, in
  order:
* ``serve.prepare`` — fault config, key splits, temperature, prompt
  upload;
* ``serve.dispatch`` — the jitted call, up to its return (asynchronous
  on an accelerator: the enqueue);
* ``serve.wait`` — the blocking fetch of the generated tokens;
* ``serve.finish`` — telemetry, served BERs and power, the result.

and by the mesh engine (``repro.serve.sharded``): ``mesh.prepare`` — the
fault config and the device puts — then ``mesh.generate`` — the dispatch
and the tokens' return.
"""
from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["span"]


class span:
    """``with span("serve.wait") as s: ...`` then ``s.seconds``."""

    __slots__ = ("name", "seconds", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds: Optional[float] = None

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
