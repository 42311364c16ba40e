"""Continuous-batching online serving: chunked-vs-one-shot bit-exactness,
slot-refill schedules, zero retrace across queue churn, admission control,
and the measured-occupancy -> aging replay loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fleet import FleetRuntime
from repro.serve import steps as serve_steps
from repro.serve.engine import ServeEngine
from repro.serve.online import (OnlineFleetEngine, OnlineServeEngine,
                                Request, RequestQueue,
                                requests_from_workload)
from repro.train.steps import init_train_state

S = 8               # fixed prompt length for the run
MAX_LEN = 64


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek_7b").reduced()
    params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, S), 0, cfg.vocab), np.int32)
    return cfg, params, prompts


def _online(cfg, params, *, runtime=None, n_slots=3, chunk=4, seed=5,
            max_new_cap=16, max_queue=64):
    return OnlineServeEngine(cfg, params, runtime=runtime,
                             n_slots=n_slots, max_len=MAX_LEN,
                             max_new_cap=max_new_cap, chunk_steps=chunk,
                             max_queue=max_queue, seed=seed)


def _tokens_by_id(res):
    return [r.tokens for r in sorted(res.completed, key=lambda r: r.id)]


# --------------------------------------------------------------------------- #
# bit-exactness with the one-shot scanned path
# --------------------------------------------------------------------------- #
def test_no_arrival_bit_exact_clean(setup):
    """All slots filled once at step 0, no EOS: the chunked online path
    reproduces ServeEngine.generate token-for-token — including when the
    generation length is not a multiple of the chunk size."""
    cfg, params, prompts = setup
    K, n_steps = 3, 9                      # 9 = 1 + 2 chunks of 4
    ref = ServeEngine(cfg, params, max_len=MAX_LEN, seed=5).generate(
        prompts[:K], n_steps, temperature=0.7).tokens
    eng = _online(cfg, params, n_slots=K, chunk=4, seed=5)
    res = eng.serve([Request(id=i, prompt=prompts[i], max_new=n_steps)
                     for i in range(K)],
                    greedy=False, temperature=0.7, eos_id=-1)
    np.testing.assert_array_equal(ref, np.stack(_tokens_by_id(res)))


def test_no_arrival_bit_exact_faulted(setup):
    """Same contract on the faulted graph: the online path consumes the
    identical key and per-step fault-stream chains as generate()."""
    cfg, params, prompts = setup
    rt = FleetRuntime(n_devices=1)
    rt.set_age(years=9.0)
    K, n_steps = 3, 9
    ref = ServeEngine(cfg, params, runtime=rt, max_len=MAX_LEN,
                      seed=5).generate(prompts[:K], n_steps,
                                       temperature=0.7).tokens
    eng = _online(cfg, params, runtime=rt, n_slots=K, chunk=4, seed=5)
    res = eng.serve([Request(id=i, prompt=prompts[i], max_new=n_steps)
                     for i in range(K)],
                    greedy=False, temperature=0.7, eos_id=-1)
    np.testing.assert_array_equal(ref, np.stack(_tokens_by_id(res)))


def test_ragged_slots_bit_exact_past_the_window_wrap():
    """Per-row slot depths through the cache scatter, past a windowed
    ring's wrap: starcoder2's 16-slot window, slot 1 admitted a chunk after
    slot 0, both decoding 12 tokens after an 8-token prompt.  Each request
    greedy-generates what the one-shot static batch gives its row."""
    cfg = dataclasses.replace(get_config("starcoder2_7b").reduced(),
                              window=16)    # its sliding window, cut down
    params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, S), 0, cfg.vocab), np.int32)
    n_new = 12
    assert S + n_new - 1 > cfg.window
    ref = ServeEngine(cfg, params, max_len=MAX_LEN, seed=5).generate(
        prompts, n_new).tokens
    res = _online(cfg, params, n_slots=2, chunk=4).serve(
        [Request(id=0, prompt=prompts[0], max_new=n_new, arrival=0),
         Request(id=1, prompt=prompts[1], max_new=n_new, arrival=3)],
        greedy=True, eos_id=-1)
    by_id = {r.id: r for r in res.completed}
    assert by_id[1].t_start > by_id[0].t_start      # ragged depths
    np.testing.assert_array_equal(ref, np.stack(_tokens_by_id(res)))


# --------------------------------------------------------------------------- #
# slot-refill schedule
# --------------------------------------------------------------------------- #
def test_refill_schedule_3_requests_2_slots(setup):
    """Handcrafted 3-request/2-slot run: the third request waits for a
    freed slot, every budget is honored exactly, and greedy requests with
    the same prompt generate identical tokens regardless of which slot
    (or wall-clock window) served them."""
    cfg, params, prompts = setup
    eng = _online(cfg, params, n_slots=2, chunk=4, seed=7)
    reqs = [Request(id=0, prompt=prompts[0], max_new=5, arrival=0),
            Request(id=1, prompt=prompts[1], max_new=9, arrival=0),
            Request(id=2, prompt=prompts[0], max_new=5, arrival=1)]
    res = eng.serve(reqs, greedy=True)
    assert res.n_completed == 3 and res.n_dropped == 0
    by_id = {r.id: r for r in res.completed}
    assert [by_id[i].n_generated for i in range(3)] == [5, 9, 5]
    # request 2 could only start after request 0 freed its slot
    assert by_id[2].t_start >= by_id[0].t_done
    assert by_id[2].t_start > 0 and by_id[0].t_start == 0
    # same prompt + greedy -> same tokens, whichever slot served it
    np.testing.assert_array_equal(by_id[0].tokens, by_id[2].tokens)
    # occupancy trace covers the whole service interval, 2 slots wide
    assert res.occupancy.shape == (res.total_steps, 2)


def test_eos_completion_frees_slot(setup):
    """A request whose sampled token hits eos_id retires early; its slot
    serves the next request."""
    cfg, params, prompts = setup
    eng = _online(cfg, params, n_slots=1, chunk=4, seed=3)
    # greedy tokens are deterministic: find the first generated token and
    # use it as the EOS id so the first request stops after one token
    probe = eng.serve([Request(id=0, prompt=prompts[0], max_new=6)],
                      greedy=True)
    first = int(probe.completed[0].tokens[0])
    eng2 = _online(cfg, params, n_slots=1, chunk=4, seed=3)
    res = eng2.serve([Request(id=0, prompt=prompts[0], max_new=6),
                      Request(id=1, prompt=prompts[1], max_new=4)],
                     greedy=True, eos_id=first)
    by_id = {r.id: r for r in res.completed}
    assert by_id[0].n_generated == 1          # stopped at EOS, not budget
    assert by_id[1].n_generated >= 1


def test_admission_control_drops_when_full(setup):
    """More simultaneous arrivals than slots + queue can hold -> drops."""
    cfg, params, prompts = setup
    eng = _online(cfg, params, n_slots=1, chunk=4, max_queue=2)
    reqs = [Request(id=i, prompt=prompts[i % 4], max_new=4, arrival=0)
            for i in range(6)]
    res = eng.serve(reqs, greedy=True)
    assert res.n_arrived == 6
    # admission is queue-first: 2 fit the bounded queue, 4 are dropped
    assert res.n_dropped == 4
    assert res.n_completed == 2
    assert 0.0 < res.drop_rate < 1.0


def test_request_queue_bounds():
    q = RequestQueue(max_queue=2)
    rs = [Request(id=i, prompt=np.zeros(4, np.int32), max_new=2)
          for i in range(4)]
    assert [q.push(r) for r in rs] == [True, True, False, False]
    assert (q.n_arrived, q.n_admitted, q.n_dropped) == (4, 2, 2)
    assert [r.id for r in q.take(5)] == [0, 1] and len(q) == 0


# --------------------------------------------------------------------------- #
# zero retrace across refills / queue churn
# --------------------------------------------------------------------------- #
def test_zero_retrace_across_refills(setup):
    """Slot refills, different arrival patterns, different budgets, and an
    advanced device age all reuse the same two compiled functions."""
    cfg, params, prompts = setup
    rt = FleetRuntime(n_devices=1)
    rt.set_age(years=2.0)
    eng = _online(cfg, params, runtime=rt, n_slots=2, chunk=4, seed=1)
    eng.serve([Request(id=i, prompt=prompts[i % 4], max_new=5,
                       arrival=2 * i) for i in range(4)], greedy=True)
    before = dict(serve_steps.TRACE_COUNTS)
    rt.set_age(years=8.0)             # BERs change: traced leaves only
    eng.serve([Request(id=i, prompt=prompts[(i + 1) % 4], max_new=3 + i % 4,
                       arrival=3 * i) for i in range(6)], greedy=True)
    assert dict(serve_steps.TRACE_COUNTS) == before


# --------------------------------------------------------------------------- #
# occupancy -> apply_load round trip
# --------------------------------------------------------------------------- #
def test_occupancy_matches_hand_computed_duty(setup):
    """lane_utilization == hand-computed busy-slot fraction per window."""
    cfg, params, prompts = setup
    eng = _online(cfg, params, n_slots=2, chunk=4, seed=7)
    res = eng.serve([Request(id=0, prompt=prompts[0], max_new=5),
                     Request(id=1, prompt=prompts[1], max_new=9),
                     Request(id=2, prompt=prompts[2], max_new=5,
                             arrival=1)], greedy=True)
    occ = np.asarray(res.occupancy, np.float64)      # (T, 2)
    T = occ.shape[0]
    n_epochs = 4
    got = res.lane_utilization(n_epochs)
    edges = np.linspace(0, T, n_epochs + 1).astype(int)
    want = np.asarray([occ[edges[e]:edges[e + 1]].mean()
                       for e in range(n_epochs)])
    np.testing.assert_allclose(got, want, atol=1e-12)
    assert got.shape == (n_epochs,)
    assert 0.0 <= got.min() and got.max() <= 1.0


def test_occupancy_replay_drives_fleet_aging(setup):
    """Measured (E, N) occupancy feeds FleetRuntime.apply_load: the aging
    recursion runs on the served duty cycle, and replaying a routed
    co-sim's own util output is bit-identical to the routed run."""
    from repro.core.artifacts import load_calibration
    from repro.sched.lifetime import cosimulate
    cfg, params, prompts = setup
    N = 2
    fleet = FleetRuntime(n_devices=N)
    eng = OnlineFleetEngine(cfg, params, fleet, n_slots=2,
                            max_len=MAX_LEN, max_new_cap=8,
                            chunk_steps=4, seed=4)
    reqs = [Request(id=i, prompt=prompts[i % 4], max_new=6, arrival=i)
            for i in range(10)]
    res = eng.serve(reqs, greedy=True)
    assert res.occupancy.shape[1:] == (N, 2)
    util = res.lane_utilization(6)                    # (6, N) measured
    assert util.shape == (6, N)

    cos = fleet.apply_load(util_trace=util, horizon_s=3.15e7)
    np.testing.assert_allclose(np.asarray(cos.util), util, atol=1e-6)
    wear = cos.device_wear()[-1]
    assert np.isfinite(wear).all() and wear.max() > 0.0
    # the engine serves the traffic-aged BERs immediately afterwards
    assert fleet.age_years > 0.9

    # replay == routed, bit for bit, when the trace IS a routed output
    cal = load_calibration()
    dmax = fleet.policy.thresholds(fleet.scenario, fleet.operators)
    loads = np.linspace(0.2, 1.4, 12).astype(np.float32)
    routed = cosimulate(cal.aging, cal.delay_poly, fleet.scenario, dmax,
                        loads, router="wear_level", n_devices=N)
    replay = cosimulate(cal.aging, cal.delay_poly, fleet.scenario, dmax,
                        loads, util_trace=routed.util, n_devices=N)
    for f in ("util", "V", "delay", "dvp", "dvn", "dv"):
        np.testing.assert_array_equal(np.asarray(getattr(routed, f)),
                                      np.asarray(getattr(replay, f)))


# --------------------------------------------------------------------------- #
# fleet dispatch + workload arrivals
# --------------------------------------------------------------------------- #
def test_fleet_router_dispatch_serves_all(setup):
    """Router-dispatched lanes drain a workload-derived queue; per-request
    lane assignment is recorded and occupancy spans all lanes."""
    cfg, params, prompts = setup
    N = 2
    fleet = FleetRuntime(n_devices=N)
    fleet.set_age(years=8.0, device=0)     # aged lane: wear_level steers
    eng = OnlineFleetEngine(cfg, params, fleet, n_slots=2,
                            max_len=MAX_LEN, max_new_cap=8,
                            chunk_steps=4, router="wear_level", seed=2)
    reqs = requests_from_workload(
        "poisson", n_slots=2, steps_per_epoch=16, max_new=6,
        prompt_len=S, vocab=cfg.vocab, n_devices=N, seed=0, n_epochs=3)
    assert len(reqs) > 0
    res = eng.serve(reqs, greedy=True, max_steps=600)
    assert res.n_completed + res.n_dropped == res.n_arrived
    lanes = {r.lane for r in res.completed}
    assert lanes <= set(range(N)) and len(lanes) >= 1
    for r in res.completed:
        assert r.n_generated == min(6, r.max_new)
        assert r.t_done > r.t_start >= r.arrival


def test_requests_from_workload_sizing():
    """Little's-law sizing: request count tracks load * slots * steps /
    max_new, and arrivals land inside their epoch."""
    loads = np.asarray([1.0, 0.0, 2.0], np.float64)
    reqs = requests_from_workload(
        None, loads=loads, n_slots=4, steps_per_epoch=100, max_new=10,
        prompt_len=8, vocab=64, seed=0)
    # epoch 1 has zero load -> no arrivals inside [100, 200)
    assert not any(100 <= r.arrival < 200 for r in reqs)
    n = len(reqs)
    expect = (1.0 + 2.0) * 4 * 100 / 10
    assert 0.5 * expect < n < 1.5 * expect        # Poisson, loose bound
    assert all(0 <= r.arrival < 300 for r in reqs)
    assert all(len(r.prompt) == 8 and r.max_new == 10 for r in reqs)
