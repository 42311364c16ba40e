"""Scopes and host spans of the serving path: the device scopes survive
into the compiled program's ``op_name`` metadata, and
``ServeEngine.generate`` opens its ``serve.*`` spans in order, nested."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.fleet import FleetRuntime
from repro.obs import spans as obs_spans
from repro.serve import steps
from repro.serve.engine import ServeEngine
from repro.train.steps import init_train_state

SCOPES = {"prefill", "decode", "attention", "quantize", "taps"}
SERVE_SPANS = ["serve.generate", "serve.prepare", "serve.dispatch",
               "serve.wait", "serve.finish"]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("deepseek_7b").reduced()
    params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    return cfg, params, jnp.zeros((2, 8), jnp.int32)


def _aged_runtime():
    rt = FleetRuntime(n_devices=1)
    rt.set_age(years=9.0)
    return rt


def _scope_components(hlo_text: str) -> set:
    """Every path component of every ``op_name`` in the HLO text."""
    return {c for path in re.findall(r'op_name="([^"]*)"', hlo_text)
            for c in path.split("/")}


@pytest.mark.parametrize("aged", [True, False], ids=["aged", "clean"])
def test_generate_scopes_in_compiled_metadata(tiny, aged):
    """The compiled generate program names its phases in the ops'
    metadata; only a faulted program quantises to int8."""
    cfg, params, prompts = tiny
    runtime = _aged_runtime() if aged else None
    fi = ServeEngine(cfg, params, runtime=runtime, max_len=32,
                     use_systolic_kernel=True)._fault_config()
    gen = jax.jit(steps.make_generate_fn(cfg, 32, 3))
    text = gen.lower(params, prompts, fi, jax.random.PRNGKey(1),
                     jnp.float32(0)).compile().as_text()
    found = _scope_components(text) & SCOPES
    if aged:
        assert found == SCOPES
    else:
        assert found == SCOPES - {"quantize"}
    # the decode step is the scan body: its ops sit inside the loop
    assert re.search(r'op_name="[^"]*while/body/[^"]*decode/', text)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_generate_opens_serve_spans_in_order(tiny, monkeypatch):
    cfg, params, prompts = tiny
    engine = ServeEngine(cfg, params, runtime=_aged_runtime(), max_len=32,
                         use_systolic_kernel=True)
    engine.generate(prompts, 3)                       # compile outside
    monkeypatch.setattr(obs_spans, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    engine.generate(prompts, 3)
    outer, *inner = SERVE_SPANS
    want = [("enter", outer)]
    for name in inner:
        want += [("enter", name), ("exit", name)]
    want.append(("exit", outer))
    assert _Recorder.log == want


def test_serve_spans_land_in_profiler_trace(tiny, tmp_path):
    """Under the real profiler the spans are host events on the trace's
    clock, each phase inside ``serve.generate`` and after the one before."""
    from jax.profiler import ProfileData
    cfg, params, prompts = tiny
    engine = ServeEngine(cfg, params, max_len=32)
    engine.generate(prompts, 3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate(prompts, 3)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SERVE_SPANS:
                        events[e.name] = (e.start_ns, e.end_ns)
    assert sorted(events) == sorted(SERVE_SPANS)
    lo, hi = events["serve.generate"]
    at = lo
    for name in SERVE_SPANS[1:]:
        s, t = events[name]
        assert at <= s <= t <= hi
        at = t


def test_span_keeps_seconds_and_lets_errors_through(monkeypatch):
    monkeypatch.setattr(obs_spans, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    with obs_spans.span("outer") as s:
        pass
    assert s.seconds >= 0.0
    with pytest.raises(ValueError):
        with obs_spans.span("failing"):
            raise ValueError("inside")
    assert _Recorder.log == [("enter", "outer"), ("exit", "outer"),
                             ("enter", "failing"), ("exit", "failing")]
