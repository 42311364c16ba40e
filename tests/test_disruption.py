"""Disruption scenarios: flash crowd + thermal feedback, retirement /
hot-swap with trap-state-preserving resize, and rest-to-recover routing.

The scenario regression layer for :mod:`repro.sched.disruption`: the
closed thermal loop reaches a *bounded* fixed point and is monotone in
routed power, mid-horizon retirement resumes the survivors bit-exactly
(replay-verified against the undisturbed run), the ``rest_to_recover``
router beats round-robin on effective fleet-max ΔVth (mirroring the
wear-leveling acceptance test), and the un-orphaned elastic dry-run
compiles the degraded mesh end to end in a subprocess.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.artifacts import load_calibration
from repro.core.fleet import FleetRuntime
from repro.core.policy import FaultTolerantPolicy
from repro.core.resilience import OPERATORS
from repro.core.scenario import Scenario
from repro.sched import cosimulate, get_workload
from repro.sched.disruption import (recovered_totals, run_flash_crowd,
                                    run_rest_to_recover, run_retirement)
from repro.sched.workload import WORKLOADS

YEAR_S = 365.25 * 24 * 3600.0
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cal():
    return load_calibration()


@pytest.fixture(scope="module")
def policy(cal):
    return FaultTolerantPolicy(ber_model=cal.ber)


# --------------------------------------------------------------------------- #
# flash_crowd workload
# --------------------------------------------------------------------------- #
def test_flash_crowd_workload_window():
    wl = get_workload("flash_crowd", n_devices=8, utilization=0.5,
                      n_epochs=240, surge_gain=4.0)
    loads = np.asarray(wl.loads(0))
    s0, sl = int(wl.surge_start), int(wl.surge_len)
    assert 0 < s0 and s0 + sl <= 240 and sl >= 1
    inside = loads[s0:s0 + sl].mean()
    outside = np.concatenate([loads[:s0], loads[s0 + sl:]]).mean()
    assert inside > 2.5 * outside            # the x4 surge is visible
    np.testing.assert_array_equal(loads, np.asarray(wl.loads(0)))


def test_flash_crowd_zero_length_surge_is_identity():
    base = get_workload("poisson", n_devices=4, utilization=0.5,
                        n_epochs=96)
    fc = get_workload("flash_crowd", n_devices=4, utilization=0.5,
                      n_epochs=96, surge_len=0)
    # no window -> the envelope degenerates to the base arrival model's
    np.testing.assert_array_equal(np.asarray(fc.envelope()),
                                  np.asarray(base.envelope()))
    # every legacy workload still defaults to a unit surge envelope
    for name in WORKLOADS:
        if name == "flash_crowd":
            continue
        wl = get_workload(name, n_devices=4, utilization=0.5, n_epochs=96)
        assert float(wl.surge_len) == 0.0
    del base


# --------------------------------------------------------------------------- #
# closed thermal loop
# --------------------------------------------------------------------------- #
def _thermal_replay(cal, policy, util, epochs=48, n=4):
    scn = Scenario.from_lifetime_config(cal.lifetime_cfg).replace(
        lifetime_s=1.0 * YEAR_S)
    dmax = policy.thresholds(scn, OPERATORS)
    U = np.full((epochs, n), util, np.float32)
    return cosimulate(cal.aging, cal.delay_poly, scn, dmax, None,
                      util_trace=jnp.asarray(U), thermal=True)


def test_thermal_node_bounded_fixed_point(cal, policy):
    cos = _thermal_replay(cal, policy, 1.0)
    tn = np.asarray(cos.t_node)
    assert np.isfinite(tn).all()
    t_amb = float(np.asarray(Scenario.from_lifetime_config(
        cal.lifetime_cfg).t_amb))
    assert (tn >= t_amb - 1e-3).all()        # dissipation only heats
    assert tn.max() < t_amb + 60.0           # bounded: util<=1, V<=v_max
    # a constant-power run settles: the last epochs stop moving
    assert abs(tn[-1].max() - tn[-2].max()) < 0.1


def test_thermal_node_monotone_in_routed_power(cal, policy):
    lo = np.asarray(_thermal_replay(cal, policy, 0.2).t_node)
    hi = np.asarray(_thermal_replay(cal, policy, 0.9).t_node)
    assert (hi >= lo - 1e-4).all()
    assert hi[-1].max() > lo[-1].max() + 1.0  # strictly hotter in steady


def test_flash_crowd_driver_heats_and_relaxes(cal):
    out = run_flash_crowd(cal, n_devices=4, epochs=96, surge_gain=4.0)
    s, tn = out["stats"], np.asarray(out["cos"].t_node)
    assert np.isfinite(tn).all()
    assert s["t_peak_k"] >= s["t_steady_k"] - 1e-3
    assert s["t_surge_rise_k"] > 1.0         # the fleet-mean spike shows
    assert 0.0 < s["surge_served_frac"] < 1.0   # x4 overload saturates
    # the node relaxes after the window (RC decay, not a ratchet)
    fm = tn.mean(axis=1)
    assert fm[-1] < fm[int(s["surge_start"]):int(s["surge_end"])].max()
    assert s["fleet_max_dvp_mv"] > 0.0
    assert s["recovered_mv_final"] >= 0.0


# --------------------------------------------------------------------------- #
# retirement / hot-swap: trap-state-preserving resize
# --------------------------------------------------------------------------- #
def _mk_fleet(cal, n):
    return FleetRuntime(cal, n_devices=n)


def test_retirement_survivors_bit_exact_vs_undisturbed(cal):
    """Replay the SAME measured duty with and without a mid-horizon
    resize: survivors' monotone state, recoverable pool and supplies
    must be bit-identical to the undisturbed run."""
    E, e, n, keep = 64, 32, 4, [0, 2, 3]
    rnd = np.random.default_rng(7)
    U = rnd.uniform(0.0, 1.0, (E, n)).astype(np.float32)
    H = 2.0 * YEAR_S

    full = _mk_fleet(cal, n).apply_load(util_trace=U, horizon_s=H,
                                        recovery=True)

    fleet = _mk_fleet(cal, n)
    fleet.apply_load(util_trace=U[:e], horizon_s=H * e / E, recovery=True)
    fleet2 = fleet.resize(keep)
    cos2 = fleet2.apply_load(util_trace=U[e:][:, keep],
                             horizon_s=H * (E - e) / E, recovery=True)

    ref = lambda x: np.asarray(x)[e:][:, keep]
    np.testing.assert_array_equal(np.asarray(cos2.dv), ref(full.dv))
    np.testing.assert_array_equal(np.asarray(cos2.rec), ref(full.rec))
    np.testing.assert_array_equal(np.asarray(cos2.V), ref(full.V))


def test_hot_swap_fresh_devices_start_clean(cal):
    n, keep = 4, [1, 2, 3]
    fleet = _mk_fleet(cal, n)
    fleet.apply_load(util_trace=np.ones((16, n), np.float32),
                     horizon_s=1.0 * YEAR_S, recovery=True)
    worn = fleet.trap_state()
    fleet2 = fleet.resize(keep, n_fresh=1)
    st = fleet2.trap_state()
    assert st["dv"].shape[0] == len(keep) + 1
    # survivors carry their exact state, the swap-in starts from zero
    np.testing.assert_array_equal(st["dv"][:3], worn["dv"][keep])
    np.testing.assert_array_equal(st["dv"][3], 0.0)
    np.testing.assert_array_equal(st["rec"][3], 0.0)
    assert st["ages_s"][3] == 0.0 and (st["ages_s"][:3] > 0).all()
    # the fresh device inherits the retired rack slot's thermal seat
    t_amb = np.asarray(fleet.scenario.t_amb)
    if t_amb.ndim:
        assert float(np.asarray(fleet2.scenario.t_amb)[3]) == \
            pytest.approx(float(t_amb[0]))


def test_run_retirement_driver_plans_and_stats(cal):
    out = run_retirement(cal, n_devices=8, retire=(0, 1), hot_swap=1,
                         epochs=48, tp=2, global_batch=64)
    pd, pr, s = out["plan_degraded"], out["plan_restored"], out["stats"]
    assert pd.old_shape == (8, 2) and pd.new_shape[0] < 8
    # global batch preserved: dp * microbatches never shrinks
    assert pd.new_shape[0] * pd.microbatches >= 8
    assert pr is not None and pr.new_shape[0] >= pd.new_shape[0]
    assert s["n_before"] == 8 and s["n_after"] == 7
    assert s["survivor_pre_max_dvp_mv"] <= s["pre_retire_max_dvp_mv"]
    assert s["fleet_max_dvp_mv"] >= s["survivor_pre_max_dvp_mv"]
    assert out["cos_after"].util.shape[1] == 7


# --------------------------------------------------------------------------- #
# rest_to_recover: deliberate idling harvests the recoverable pool
# --------------------------------------------------------------------------- #
def test_rest_to_recover_beats_round_robin(cal):
    """The acceptance criterion (mirrors the wear_level -13% test): on
    the 8-device heterogeneous fleet with recovery enabled, resting the
    most-worn devices reduces fleet-max effective ΔVth vs round-robin."""
    res = run_rest_to_recover(cal, n_devices=8, epochs=120)
    rr = res["round_robin"]["fleet_max_dvp_mv"]
    rest = res["rest_to_recover"]["fleet_max_dvp_mv"]
    assert rest < 0.95 * rr, (rest, rr)
    assert res["headline"]["rest_vs_round_robin_pct"] > 5.0
    assert res["headline"]["recovered_mv_final"] > 0.0
    # resting may not drop traffic at this utilization
    assert res["rest_to_recover"]["served_frac"] == \
        pytest.approx(1.0, abs=1e-3)


def test_recovered_totals_shape_and_positivity(cal):
    out = run_flash_crowd(cal, n_devices=4, epochs=48)
    rec = recovered_totals(out["cos"])
    assert rec.shape == (48, 4)
    assert (rec >= 0.0).all() and np.isfinite(rec).all()


# --------------------------------------------------------------------------- #
# CLI + un-orphaned elastic dry-run
# --------------------------------------------------------------------------- #
def test_schedule_cli_scenarios_inprocess(capsys):
    from repro.launch.schedule import main
    out = main(["--scenario", "flash_crowd", "--n-devices", "4",
                "--epochs", "48"])
    assert "stats" in out
    out = main(["--scenario", "rest_to_recover", "--n-devices", "8",
                "--epochs", "96"])
    assert out["headline"]["rest_vs_round_robin_pct"] > 0.0
    out = main(["--scenario", "retirement", "--n-devices", "4",
                "--epochs", "48", "--hot-swap", "1"])
    assert out["stats"]["n_after"] == 4
    text = capsys.readouterr().out
    assert "[disrupt]" in text


@pytest.mark.slow
def test_elastic_dryrun_quick_subprocess(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.elastic_dryrun", "--quick",
         "--out-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "degraded-mesh train step compiles" in proc.stdout
    assert list(tmp_path.glob("elastic__*.json"))
    assert "survivors resumed" in proc.stdout
