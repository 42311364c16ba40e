"""The reference against the serving program's own forward pass, faults
off, on the CPU at a small size; and the weight generator's layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import program  # noqa: F401  (puts the program's src on the path)
import reference
import weights
from conftest import TINY
from repro.models import transformer as tf


def tiny(family):
    fam = cells.family(TINY[family])
    return fam, fam.read_dims(family, TINY[family])


@pytest.mark.parametrize("family", sorted(TINY))
def test_reference_matches_program_forward(family):
    fam, dims = tiny(family)
    params = weights.build_params(fam, dims, 7)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = fam.model_config(dims)
    rng = np.random.default_rng(0)
    S, N = 10, 6
    prompts = rng.integers(0, dims.vocab, (3, S)).astype(np.int32)
    served = rng.integers(0, dims.vocab, (3, N)).astype(np.int32)
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(tf.forward_logits(params32, cfg,
                                              jnp.asarray(seq))[0],
                            np.float64)[:, S - 1:]
    r = reference.readings(fam, dims, 7, prompts, served, row_block=2)
    np.testing.assert_allclose(r["best"], logits.max(-1), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(
        r["at_served"],
        np.take_along_axis(logits, served[..., None], -1)[..., 0],
        rtol=2e-4, atol=2e-4)
    assert np.array_equal(r["argmax"], logits.argmax(-1))


def test_layer_weights_are_the_built_ones():
    fam, dims = tiny("llama")
    params = weights.build_params(fam, dims, 2 ** 31 + 3)
    key = weights.base_key(2 ** 31 + 3)
    for l in range(dims.n_layers):
        one = fam.layer_weights(key, l, dims)
        stacked = jax.tree.map(lambda a: a[l], params["groups"]["b0_attn"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.array_equal(a, b)), one, stacked))
    assert not jnp.array_equal(weights.build_params(fam, dims, 3)["embed"],
                               params["embed"])


def test_control_precision_reads_a_wider_gap():
    """int4 products put other tokens first than float32 does."""
    fam, dims = tiny("llama")
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, dims.vocab, (4, 12)).astype(np.int32)
    served = rng.integers(0, dims.vocab, (4, 8)).astype(np.int32)
    ctrl = {"linear": 4, "attn": 4, "head": 4}
    r = reference.readings(fam, dims, 5, prompts, served, controls=[ctrl])
    gap = r["best"] - r["at_control"][0]
    assert (gap >= 0).all() and gap.max() > 0
