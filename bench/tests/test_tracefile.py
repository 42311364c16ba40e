"""The reduction from trace events to the per-layer numbers."""
import glob
import json
import os

import pytest

import tracefile
from tracefile import Event


def synthetic():
    ops = [Event("fusion.1", 100, 200), Event("fused_aged_matmul.3", 200, 300),
           Event("fusion.2", 250, 320),          # overlaps the kernel
           Event("fused_aged_matmul.7", 500, 650), Event("copy.4", 900, 950)]
    modules = [Event("jit_generate", 100, 320), Event("jit_split", 400, 410),
               Event("jit_generate", 500, 650), Event("jit_generate", 900,
                                                         1200)]
    spans = [Event("window", 50, 1000), Event("generate", 60, 330),
             Event("prep", 340, 490), Event("generate", 490, 660),
             Event("harvest", 660, 700)]
    return tracefile.make_trace(ops, modules, spans, (50, 1000))


def test_union_and_busy():
    tr = synthetic()
    assert tracefile.union(tr.ops, 50, 1000) == [(100, 320), (500, 650),
                                                 (900, 950)]
    assert tracefile.busy_ns(tr.ops, 50, 1000) == 220 + 150 + 50
    assert tracefile.busy_ns(tr.ops, 150, 520) == 170 + 20


def test_idle_gaps_and_labels():
    tr = synthetic()
    assert tracefile.idle_gaps(tr) == [(50, 100), (320, 500), (650, 900),
                                       (950, 1000)]
    b = tracefile.breakdown(tr)
    assert b["idle_gaps"][0] == ["window", pytest.approx(250e-9)]  # 650..900
    assert b["idle_gaps"][1] == ["prep", pytest.approx(180e-9)]  # mid 410
    assert b["device_ops"][0] == ["fused_aged_matmul", pytest.approx(250e-9)]
    assert dict(b["device_ops"])["fusion"] == pytest.approx(170e-9)


def test_programs_and_ops_within():
    tr = synthetic()
    progs = tracefile.programs(tr, "jit_generate")
    assert [(p.start, p.end) for p in progs] == [(100, 320), (500, 650)]
    assert tracefile.ops_within(tr, progs, r"^fused_aged_matmul") == 250


class Ctx:
    def __init__(self, tr, aged=True, chips=1):
        from families.decoder import Dims
        self.trace = tr
        self.chips = chips
        self.dims = Dims(name="t", n_layers=1, d_model=8, n_heads=2,
                         n_kv_heads=1, head_dim=4, d_ff=16, vocab=32,
                         mlp="gated", norm="rms", norm_eps=1e-6,
                         rope_theta=1e4, window=None)
        self.traffic = {"batch": 1, "prompt_tokens": 2, "new_tokens": 2,
                        "device": {"route": "fused_kernel" if aged
                                   else "clean"}}
        self.peak = {"int8_ops_per_s": 1e9, "bf16_flops_per_s": 5e8,
                     "hbm_bytes_per_s": 1e9}
        self.aged = aged

    def generate_programs(self):
        return tracefile.programs(self.trace, "jit_generate")

    def call_shape(self):
        t = self.traffic
        return t["batch"], t["prompt_tokens"], t["new_tokens"]


def test_metric_readers_on_synthetic_trace():
    import cells
    import costs
    tr = synthetic()
    ctx = Ctx(tr)
    # one gap between the two whole generate programs: 320..500 with the
    # 10 ns jit_split op-free (no op event inside it) -> 180 ns
    assert cells.metric_reader("host_gap_ms")(ctx) == pytest.approx(180e-6)
    assert cells.metric_reader("device_idle")(ctx) == pytest.approx(
        100 * (1 - 420 / 950))
    flops = 2 * costs.generate_flops(ctx.dims, 1, 2, 2)
    assert cells.metric_reader("mfu")(ctx) == pytest.approx(
        100 * flops / (950e-9 * 1e9))
    floor = 2 * costs.aged_matmul_floor_s(ctx.dims, 1, 2, 2, 1e9, 1e9)
    assert cells.metric_reader("aged_matmul_roofline")(ctx) == \
        pytest.approx(100 * floor / 250e-9)
    assert cells.metric_reader("aged_matmul_roofline")(Ctx(tr, False)) \
        is None


def test_call_p95_from_host_spans():
    import cells
    # generate spans of 270 and 170 ns, batch 1: the 95th percentile of
    # the two requests lies 95% of the way from 170 to 270
    assert cells.metric_reader("call_p95_ms")(Ctx(synthetic())) == \
        pytest.approx((170 + 0.95 * 100) * 1e-6)


def test_reader_finds_nothing_returns_none():
    import cells
    empty = tracefile.make_trace([], [], [], (0, 10))
    ctx = Ctx(empty)
    for name in ("host_gap_ms", "mfu", "aged_matmul_roofline",
                 "call_p95_ms"):
        assert cells.metric_reader(name)(ctx) is None


def test_window_without_device_ops_is_refused():
    ops = synthetic().ops
    tracefile.check_aligned(ops, (50, 1000))
    with pytest.raises(RuntimeError, match="window"):
        tracefile.check_aligned(ops, (5000, 6000))     # clocks apart


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "trace_slice_*.json")))


def test_op_name_from_hlo_text():
    text = ("%fused_aged_matmul.146 = f32[8,4096]{1,0:T(8,128)S(1)} "
            "custom-call(s32[1,1]{1,0:T(1,128)} %bitcast.1107), "
            "custom_call_target=\"tpu_custom_call\"")
    assert tracefile.op_name(text) == "fused_aged_matmul.146"
    assert tracefile.op_name("fusion.3") == "fusion.3"


def test_breakdown_leaves_out_loop_envelopes():
    tr = synthetic()
    loop = Event("while.9", 100, 650)       # spans the ops it runs
    tr = tracefile.make_trace(tr.ops + [loop], tr.modules, tr.spans,
                              tr.window)
    names = [n for n, _ in tracefile.breakdown(tr)["device_ops"]]
    assert "while" not in names and names[0] == "fused_aged_matmul"


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """A slice recorded on a TPU v5e: the last operations of one generate
    program, the gap, and the first of the next, named as the chip's trace
    names them (the whole HLO text)."""
    d = json.load(open(path))
    ops = [Event(tracefile.op_name(n), s, e) for n, s, e in d["ops"]]
    assert all(" " not in e.name and "=" not in e.name for e in ops)
    (na, a0, a1), (nb, b0, b1) = d["modules"]
    spans = [Event(n, s, e) for n, s, e in d["spans"]]
    tr = tracefile.make_trace(ops, [Event(na, a0, a1), Event(nb, b0, b1)],
                              spans, (a0, b1))
    progs = tracefile.programs(tr, "jit_generate")
    assert len(progs) == 2
    # every operation between the programs is in the slice, so the gap
    # reads as it does in the whole trace
    import cells
    aged = ".aged" in os.path.basename(path)
    gap_ms = cells.metric_reader("host_gap_ms")(Ctx(tr, aged))
    assert gap_ms == pytest.approx(
        ((b0 - a1) - tracefile.busy_ns(ops, a1, b0)) * 1e-6)
    assert 0 < gap_ms < 100
    kernel = tracefile.ops_within(tr, progs, r"^fused_aged_matmul")
    assert (kernel > 0) == aged
    b = tracefile.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] in tracefile.HOST_SPANS
