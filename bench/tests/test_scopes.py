"""The scope and program-span readings: synthetic events by hand, and one
whole call of each cell recorded on a TPU v5e."""
import glob
import gzip
import json
import os

import pytest

import scopes
import tracefile
from tracefile import Event

HLO = """\
HloModule jit_generate, is_scheduled=true

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %abs.1 = f32[8]{0} abs(%param_0), metadata={op_name="jit(generate)/prefill/quantize/abs" stack_frame_id=4}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %abs_reduce_fusion = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(generate)/prefill/quantize/reduce_max" stack_frame_id=2}
  %copy.14 = f32[8]{0} copy(%abs_reduce_fusion), backend_config={"flag_configs":[]}
  ROOT %while.2 = f32[8]{0} while(%copy.14), condition=%cond, body=%body, metadata={op_name="jit(generate)/while" stack_frame_id=2}
}
"""


def test_op_paths_from_hlo_text():
    paths = scopes.op_paths(HLO)
    assert paths["abs_reduce_fusion"] == \
        "jit(generate)/prefill/quantize/reduce_max"
    assert paths["abs.1"] == "jit(generate)/prefill/quantize/abs"
    assert paths["while.2"] == "jit(generate)/while"
    assert paths["copy.14"] == ""            # inserted without metadata
    assert paths["x.1"] == "x"


def test_scope_matches_whole_components():
    path = "jit(generate)/while/body/closed_call/decode/attention/dot"
    assert scopes.in_scope(path, "decode")
    assert scopes.in_scope(path, "attention")
    assert not scopes.in_scope(path, "dec")
    assert not scopes.in_scope("jit(generate)/decode_attention/add",
                               "decode")
    assert not scopes.in_scope("", "prefill")


PATHS = {
    "q.1": "jit(generate)/prefill/quantize/reduce_max",
    "a.1": "jit(generate)/prefill/attention/dot_general",
    "f.1": "jit(generate)/prefill/dot_general",
    "t.1": "jit(generate)/prefill/taps/top_k",
    "a.2": "jit(generate)/while/body/closed_call/decode/attention/exp",
    "f.2": "jit(generate)/while/body/closed_call/decode/dot_general",
    "t.2": "jit(generate)/while/body/closed_call/decode/taps/top_k",
    "while.1": "jit(generate)/while",
    "copy.1": "",
}


def synthetic(new_tokens=3):
    """Two whole generate programs, one cut by the window's end, and a
    small program whose instruction shares a name with the generate
    program's but whose scope is not the generate program's."""
    def call(t0):
        return [Event("q.1", t0 + 0, t0 + 10), Event("a.1", t0 + 10, t0 + 30),
                Event("f.1", t0 + 30, t0 + 60), Event("t.1", t0 + 60, t0 + 65),
                Event("while.1", t0 + 70, t0 + 190),      # envelope
                Event("f.2", t0 + 70, t0 + 100), Event("a.2", t0 + 100,
                                                       t0 + 120),
                Event("t.2", t0 + 120, t0 + 125),
                Event("f.2", t0 + 130, t0 + 160), Event("a.2", t0 + 160,
                                                        t0 + 180),
                Event("t.2", t0 + 180, t0 + 185),
                Event("copy.1", t0 + 185, t0 + 190)]
    ops = call(100) + [Event("f.1", 320, 330)] + call(400) + call(900)
    modules = [Event("jit_generate(1)", 100, 290),
               Event("jit_convert_element_type", 320, 330),
               Event("jit_generate(1)", 400, 590),
               Event("jit_generate(1)", 900, 1090)]   # past the window
    spans = [Event("window", 50, 1000), Event("generate", 60, 300),
             Event("generate", 310, 600)]
    serve = [Event("serve.generate", 70, 298), Event("serve.prepare", 72, 90),
             Event("serve.dispatch", 92, 95), Event("serve.wait", 96, 292),
             Event("serve.finish", 293, 297),
             Event("serve.generate", 312, 597), Event("serve.wait", 395,
                                                      592),
             Event("serve.generate", 890, 1100)]      # past the window
    tr = tracefile.make_trace(ops, modules, spans, (50, 1000))
    return scopes.Scoped(tr, PATHS, serve, new_tokens)


def test_readings_on_synthetic_calls():
    s = synthetic()
    assert len(s.programs()) == 2
    # prefill: q, a, f, t back to back over 0..65 of each program
    assert scopes.prefill_ms(s) == pytest.approx(65e-6)
    # decode: 70..125 and 130..185 without the envelope, two steps
    assert scopes.decode_step_ms(s) == pytest.approx(110 / 2 * 1e-6)
    busy = 65 + 110 + 5                     # the copy has no scope
    assert scopes.attention_share(s) == pytest.approx(100 * 60 / busy)
    assert scopes.quantize_share(s) == pytest.approx(100 * 10 / busy)
    assert scopes.taps_share(s) == pytest.approx(100 * 15 / busy)
    # serve.generate less serve.wait: 228 - 196 and 285 - 197
    assert scopes.engine_host_ms(s) == pytest.approx((32 + 88) / 2 * 1e-6)
    assert scopes.coverage(s) == pytest.approx(100 * 175 / busy)
    assert scopes.unscoped(s) == [["copy", pytest.approx(10e-9)]]


def test_readings_leave_out_other_programs_and_envelopes():
    s = synthetic()
    # the small program's 'f.1' would add 10 ns of prefill if joined
    assert scopes.prefill_ms(s) == pytest.approx(65e-6)
    # the 'while' envelope spans 120 ns and would cover the 5 ns gap
    # between the decode steps
    assert s.scope_ns(s.programs()[0], "decode") == 110


def test_idle_gaps_carry_program_spans():
    s = synthetic()
    gaps = [(n, round(t * 1e9)) for n, t in scopes.idle_gaps(s)]
    assert gaps[:4] == [("window", 310),          # 590..900, between calls
                        ("serve.generate", 70),   # 330..400, in the call
                        ("serve.prepare", 50),    # 50..100, mid 75
                        ("window", 30)]           # 290..320, mid 305


def test_nothing_in_scope_reads_none():
    s = synthetic()
    bare = scopes.Scoped(s.trace, {}, [], 3)     # a program without scopes
    for name, read in scopes.READINGS.items():
        assert read(bare) is None, name
    assert scopes.coverage(bare) == 0.0
    empty = scopes.Scoped(tracefile.make_trace([], [], [], (0, 10)), PATHS,
                          [], 3)
    for name, read in scopes.READINGS.items():
        assert read(empty) is None, name
    assert scopes.coverage(empty) is None
    clean = dict(PATHS, **{"q.1": "jit(generate)/prefill/reduce_max"})
    assert scopes.quantize_share(scopes.Scoped(s.trace, clean, [], 3)) \
        is None


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "scoped_slice_*.json.gz")))


def unpack_ops(cols: dict) -> list:
    """The inverse of ``phases.pack_ops``."""
    out, at = [], 0
    for i, step, dur in zip(cols["name"], cols["step"], cols["dur"]):
        at += step
        out.append(Event(cols["names"][i], at, at + dur))
    return out


def test_pack_ops_round_trip():
    import phases
    rows = [["fusion.2", 5, 9], ["copy.1", 7, 8], ["fusion.2", 20, 31]]
    assert unpack_ops(phases.pack_ops(rows)) == [Event(*r) for r in rows]


def load_slice(path):
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    ev = lambda rows: [Event(n, s, e) for n, s, e in rows]
    spans = ev(d["spans"])
    tr = tracefile.make_trace(
        unpack_ops(d["ops"]), ev(d["modules"]),
        [e for e in spans if not e.name.startswith(scopes.SERVE)],
        tuple(d["window"]))
    return scopes.Scoped(tr, d["paths"],
                         [e for e in spans if e.name.startswith(
                             scopes.SERVE)], d["new_tokens"])


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_call_reads_every_phase(path):
    """One whole generate call recorded on a TPU v5e, with the op scopes
    of its compiled program and the engine's ``serve.*`` spans."""
    s = load_slice(path)
    assert len(s.programs()) == 1
    aged = ".aged" in os.path.basename(path)
    for name, read in scopes.READINGS.items():
        v = read(s)
        if name == "quantize_share" and not aged:
            assert v is None                 # the clean program has none
        else:
            assert v is not None and v > 0, name
    for name in ("attention_share", "quantize_share", "taps_share"):
        assert (scopes.READINGS[name](s) or 0) < 100
    assert 50 < scopes.coverage(s) <= 100
    gaps = scopes.idle_gaps(s)
    assert gaps[0][0].startswith(scopes.SERVE)


def test_recorded_slices_cover_every_cell():
    import cells
    names = {os.path.basename(p)[len("scoped_slice_"):-len(".json.gz")]
             for p in RECORDED}
    assert names == {w["name"] for w in cells.load_benchmark()["workloads"]}
