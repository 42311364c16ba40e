"""Direct tests for the distributed support layers: compressed collectives
(``repro.distributed.collectives``) and elastic re-meshing
(``repro.distributed.elastic``).

The collective math and the remesh *planning* are exercised in-process (a
1-device shard_map gives psum its axis context without faking devices);
actual cross-device behaviour — 8-shard compressed psum vs the plain mean,
and a value-preserving reshard across a device-count change on a 3-axis
("pod", "data", "model") mesh — runs on 8 faked host devices in a
subprocess.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.collectives import (psum_compressed_leaf,
                                           quantize_int8_global,
                                           tree_psum, tree_psum_compressed,
                                           zeros_residuals)
from repro.distributed.elastic import plan_remesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------- #
# collectives: quantisation + error feedback (1-device axis context)
# --------------------------------------------------------------------------- #
def test_quantize_int8_global_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 3.0
    q, scale = quantize_int8_global(x)
    assert q.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(q))) <= 127
    # symmetric absmax: dequant error is bounded by half a quantisation step
    err = jnp.max(jnp.abs(x - q.astype(jnp.float32) * scale))
    assert float(err) <= float(scale) * 0.5 + 1e-6


def test_quantize_int8_global_zero_tensor():
    q, scale = quantize_int8_global(jnp.zeros((8, 8)))
    assert int(jnp.abs(q).max()) == 0
    assert float(scale) > 0.0          # guarded against divide-by-zero


def _one_device_psum(fn, *args):
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    specs = tuple(P() for _ in args)
    return shard_map(fn, mesh=mesh, in_specs=specs,
                     out_specs=(P(), P()))(*args)


def test_psum_compressed_error_feedback_conservation():
    """With one shard the compressed psum is exactly conservative:
    out + new_residual == grad + old_residual, every step — the invariant
    that makes the quantisation bias vanish over steps."""
    g1 = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    g2 = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    r0 = jnp.zeros_like(g1)
    out1, r1 = _one_device_psum(
        lambda g, r: psum_compressed_leaf(g, r, "data", 1), g1, r0)
    np.testing.assert_allclose(np.asarray(out1 + r1), np.asarray(g1),
                               atol=1e-6)
    out2, r2 = _one_device_psum(
        lambda g, r: psum_compressed_leaf(g, r, "data", 1), g2, r1)
    np.testing.assert_allclose(np.asarray(out2 + r2), np.asarray(g2 + r1),
                               atol=1e-6)
    # and the transmitted value is the quantised gradient, not zero
    assert float(jnp.abs(out1).max()) > 0.0


def test_psum_compressed_close_to_plain():
    g = jax.random.normal(jax.random.PRNGKey(3), (64, 64))
    out, _ = _one_device_psum(
        lambda x, r: psum_compressed_leaf(x, r, "data", 1),
        g, jnp.zeros_like(g))
    # one shard: plain mean is g itself; int8 error ~ amax/127
    tol = float(jnp.abs(g).max()) / 127.0
    assert float(jnp.abs(out - g).max()) <= tol + 1e-6


def test_tree_helpers_structure():
    params = {"a": jnp.ones((4, 4), jnp.bfloat16), "b": jnp.ones((3,))}
    res = zeros_residuals(params)
    assert res["a"].dtype == jnp.float32 and res["a"].shape == (4, 4)

    def body(g, r):
        mean, new_r = tree_psum_compressed(g, r, "data", 1)
        plain = tree_psum(g, "data", 1)
        return (mean, new_r, plain)

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    spec = jax.tree.map(lambda _: P(), params)
    mean, new_r, plain = shard_map(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, spec, spec))(params, res)
    assert jax.tree.structure(mean) == jax.tree.structure(params)
    assert jax.tree.structure(new_r) == jax.tree.structure(params)
    assert mean["a"].dtype == jnp.bfloat16      # leaf dtype preserved
    np.testing.assert_allclose(np.asarray(plain["b"]), np.ones(3))


# --------------------------------------------------------------------------- #
# elastic: remesh planning (no devices needed)
# --------------------------------------------------------------------------- #
class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_plan_remesh_resizes_data_axis():
    plan = plan_remesh(FakeMesh({"data": 4, "model": 2}), 4, global_batch=8)
    assert plan.new_shape == (2, 2)
    assert plan.axis_names == ("data", "model")
    assert plan.microbatches == 2              # dp 4 -> 2 doubles accum


def test_plan_remesh_shrinks_dp_to_batch_divisor():
    plan = plan_remesh(FakeMesh({"data": 4, "model": 2}), 6, global_batch=8)
    assert plan.new_shape == (2, 2)            # dp 3 would not divide 8


def test_plan_remesh_rejects_non_tp_multiple():
    with pytest.raises(ValueError):
        plan_remesh(FakeMesh({"data": 4, "model": 2}), 5, global_batch=8)


def test_plan_remesh_preserves_pod_axis():
    """Steps compiled against a ("pod", "data", "model") mesh reference the
    pod axis by name — the plan must keep it even when resized."""
    old = FakeMesh({"pod": 2, "data": 4, "model": 2})
    # grow: 16 -> 32 devices keeps whole pods (dp 16 = 4 pods x 4)
    plan = plan_remesh(old, 32, global_batch=64)
    assert plan.axis_names == ("pod", "data", "model")
    assert plan.new_shape == (4, 4, 2)
    # shrink below one pod: collapses the pod axis to size 1, keeps the name
    plan = plan_remesh(old, 4, global_batch=64)
    assert plan.axis_names == ("pod", "data", "model")
    assert plan.new_shape == (1, 2, 2)
    assert plan.microbatches == 4              # dp 8 -> 2 preserves batch


def test_plan_remesh_pod_axis_microbatch_invariant():
    old = FakeMesh({"pod": 2, "data": 4, "model": 2})
    for n_dev, micro in ((32, 1), (16, 1), (8, 2), (4, 4)):
        plan = plan_remesh(old, n_dev, global_batch=64,
                           old_microbatches=1)
        dp = int(np.prod([s for s, a in zip(plan.new_shape,
                                            plan.axis_names)
                          if a != "model"]))
        assert dp * plan.microbatches >= 8 * 1  # global tokens preserved
        assert plan.microbatches == micro


# --------------------------------------------------------------------------- #
# 8 faked devices: compressed psum vs plain, reshard round-trip
# --------------------------------------------------------------------------- #
MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.distributed.collectives import (psum_compressed_leaf,
                                               tree_psum)
    from repro.distributed.elastic import (make_mesh_from_plan, plan_remesh,
                                           reshard_state)
    from repro.distributed.sharding import make_mesh
    from repro.models import transformer as tf

    out = {}
    # --- compressed psum across 8 real shards vs the plain mean ---------
    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32))

    def body(gs, rs):
        mean, new_r = psum_compressed_leaf(gs[0], rs[0], "data", 8)
        plain = tree_psum({"g": gs[0]}, "data", 8)["g"]
        return mean[None], new_r[None], plain[None]

    f = shard_map(body, mesh=mesh,
                  in_specs=(P("data"), P("data")),
                  out_specs=(P("data"), P("data"), P("data")))
    r = jnp.zeros_like(g)
    mean, r1, plain = f(g, r)
    # every shard returns the same reduced mean
    out["psum_err"] = float(jnp.abs(mean[0] - plain[0]).max())
    # analytic single-shot bound: per-shard rounding (scale_i / 2) plus the
    # shared-scale mismatch (|q| <= 127 times |smean - scale_i|), averaged
    scales = jnp.abs(g).max(axis=(1, 2)) / 127.0
    smean = scales.mean()
    out["psum_bound"] = float(jnp.mean(
        scales / 2.0 + 127.0 * jnp.abs(smean - scales)))

    # error feedback: repeated same gradient -> running average converges
    errs = []
    acc = jnp.zeros_like(plain[0])
    for t in range(40):
        mean, r, _ = f(g, r)
        acc = acc + mean[0]
        errs.append(float(jnp.abs(acc / (t + 1) - plain[0]).max()))
    out["ef_err_first"] = errs[0]
    out["ef_err_last"] = errs[-1]
    out["g_amax"] = float(jnp.abs(g).max())

    # --- reshard across a device-count change on a 3-axis mesh ----------
    cfg = get_config("deepseek_7b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    from repro.distributed.sharding import param_specs
    specs = param_specs(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        cfg, mesh3)
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh3, s)),
        params, specs)

    plan = plan_remesh(mesh3, 4, global_batch=8)
    out["plan_names"] = list(plan.axis_names)
    out["plan_shape"] = list(plan.new_shape)
    new_mesh = make_mesh_from_plan(plan)
    moved = reshard_state(placed, cfg, new_mesh)
    d = jax.tree.map(lambda a, b: float(np.max(np.abs(
        np.asarray(a) - np.asarray(b)))), params, moved)
    out["reshard_max_delta"] = max(jax.tree.leaves(d))
    one = jax.tree.leaves(moved)[0]
    out["moved_axis_names"] = list(one.sharding.mesh.axis_names)
    out["moved_n_devices"] = len(one.sharding.mesh.devices.flatten())

    # round-trip back up to 8 devices
    plan8 = plan_remesh(new_mesh, 8, global_batch=8)
    back = reshard_state(moved, cfg, make_mesh_from_plan(plan8))
    d2 = jax.tree.map(lambda a, b: float(np.max(np.abs(
        np.asarray(a) - np.asarray(b)))), params, back)
    out["roundtrip_max_delta"] = max(jax.tree.leaves(d2))
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_multidevice_collectives_and_reshard():
    proc = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    # int8 mean-psum tracks the exact mean within its analytic error bound
    assert out["psum_err"] <= out["psum_bound"] + 1e-6
    # error feedback: the running average converges at O(1/T) — the
    # quantisation bias vanishes over steps instead of accumulating
    assert out["ef_err_last"] < 0.5 * out["ef_err_first"]
    assert out["ef_err_last"] < 0.02 * out["g_amax"]
    # reshard across 8 -> 4 devices: values bit-identical, pod axis kept
    assert out["reshard_max_delta"] == 0.0
    assert out["roundtrip_max_delta"] == 0.0
    assert out["plan_names"] == ["pod", "data", "model"]
    assert out["plan_shape"] == [1, 2, 2]
    assert out["moved_axis_names"] == ["pod", "data", "model"]
    assert out["moved_n_devices"] == 4
