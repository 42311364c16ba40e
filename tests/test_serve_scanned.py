"""Single-dispatch serving: scanned-vs-eager parity, in-graph sampling,
compile-cache (zero retrace), and the fleet-vmapped engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fleet import FleetRuntime
from repro.data import SyntheticLM
from repro.serve import steps
from repro.serve.engine import FleetServeEngine, ServeEngine, _generate_fn
from repro.train.steps import init_train_state

ARCHS = {
    "deepseek_7b": "plain",          # decoder-only
    "paligemma_3b": "prefix",        # VLM prefix-embedding family
    "whisper_large_v3": "encdec",    # encoder-decoder
}


@pytest.fixture(scope="module")
def setups():
    out = {}
    for arch, kind in ARCHS.items():
        cfg = get_config(arch).reduced()
        params = init_train_state(cfg, jax.random.PRNGKey(0)).params
        data = SyntheticLM(vocab=cfg.vocab, seq_len=16, global_batch=2)
        prompts = data.batch_at(0).tokens
        extras = {}
        rng = np.random.RandomState(0)
        if kind == "prefix":
            extras["prefix_embeds"] = rng.randn(
                2, cfg.prefix_tokens, cfg.d_model).astype(np.float32)
        elif kind == "encdec":
            extras["frames"] = rng.randn(
                2, cfg.encoder_seq, cfg.d_model).astype(np.float32)
        out[arch] = (cfg, params, prompts, extras)
    return out


def _aged_runtime():
    rt = FleetRuntime(n_devices=1)
    rt.set_age(years=9.0)
    return rt


# --------------------------------------------------------------------------- #
# scanned vs eager parity — all three families, clean and faulted
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", list(ARCHS))
def test_scanned_matches_eager_clean(setups, arch):
    cfg, params, prompts, extras = setups[arch]
    a = ServeEngine(cfg, params, max_len=64, seed=3) \
        .generate(prompts, 5, **extras)
    b = ServeEngine(cfg, params, max_len=64, seed=3) \
        .generate(prompts, 5, scan=False, **extras)
    assert a.tokens.shape == (2, 5)
    np.testing.assert_array_equal(a.tokens, b.tokens)


@pytest.mark.parametrize("arch", list(ARCHS))
def test_scanned_matches_eager_faulted_fused(setups, arch):
    """Bit-exact tokens with real BER > 0 through the fused Pallas kernel:
    the scanned loop derives the same per-(call, operator, step) upset
    streams in-trace that the eager oracle derives step by step."""
    cfg, params, prompts, extras = setups[arch]
    rt = _aged_runtime()
    assert max(rt.op_bers().values()) > 0      # end-of-life: errors admitted
    mk = lambda: ServeEngine(cfg, params, runtime=rt, max_len=64, seed=3,
                             use_systolic_kernel=True, use_fused_kernel=True)
    a = mk().generate(prompts, 4, **extras)
    b = mk().generate(prompts, 4, scan=False, **extras)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_scanned_matches_eager_three_pass_oracle(setups):
    """Parity also holds on the unfused (three-pass injection) route."""
    cfg, params, prompts, extras = setups["deepseek_7b"]
    rt = _aged_runtime()
    mk = lambda: ServeEngine(cfg, params, runtime=rt, max_len=64, seed=3,
                             use_systolic_kernel=False,
                             use_fused_kernel=False)
    a = mk().generate(prompts, 4, **extras)
    b = mk().generate(prompts, 4, scan=False, **extras)
    np.testing.assert_array_equal(a.tokens, b.tokens)


@pytest.mark.parametrize("route", ["clean", "faulted_fused"])
def test_scanned_matches_eager_windowed_ring_wraps(route):
    """A windowed ring past its wrap: starcoder2's 16-slot local window,
    an 8-token prompt and 14 new tokens, so decode writes slot
    ``(cache_len - 1) % 16`` of the carried stack for cache_len 17..21."""
    cfg = dataclasses.replace(get_config("starcoder2_7b").reduced(),
                              window=16)    # its sliding window, cut down
    params = init_train_state(cfg, jax.random.PRNGKey(0)).params
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab), np.int32)
    n_steps, max_len = 14, 24
    assert prompts.shape[1] + n_steps - 1 > cfg.window
    kw = {}
    if route == "faulted_fused":
        kw = dict(runtime=_aged_runtime(), use_systolic_kernel=True,
                  use_fused_kernel=True)
    mk = lambda: ServeEngine(cfg, params, max_len=max_len, seed=3, **kw)
    a = mk().generate(prompts, n_steps, temperature=0.7)
    b = mk().generate(prompts, n_steps, temperature=0.7, scan=False)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_encdec_cache_matches_teacher_forced_rollout(setups):
    """The enc-dec prefill stashes the prompt's decoder self-attention K/V
    in the cache (regression: it used to return an all-zero cache, so
    decode steps attended over zeroed prompt slots).  Greedy incremental
    decode must equal a from-scratch teacher-forced rollout."""
    from repro.models import encdec
    cfg, params, prompts, extras = setups["whisper_large_v3"]
    frames = jnp.asarray(extras["frames"])
    gen = ServeEngine(cfg, params, max_len=48, seed=3) \
        .generate(prompts, 5, **extras).tokens

    toks = jnp.asarray(prompts, jnp.int32)
    enc = encdec.encode(params, cfg, frames)
    ref = []
    for _ in range(5):
        logits, _ = encdec.decode(params, cfg, toks, enc_out=enc)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        ref.append(np.asarray(nxt))
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(gen, np.stack(ref, axis=1))


# --------------------------------------------------------------------------- #
# in-graph sampling
# --------------------------------------------------------------------------- #
def test_temperature_zero_is_greedy(setups):
    cfg, params, prompts, _ = setups["deepseek_7b"]
    g = ServeEngine(cfg, params, max_len=64, seed=7) \
        .generate(prompts, 6, greedy=True)
    t0 = ServeEngine(cfg, params, max_len=64, seed=7) \
        .generate(prompts, 6, temperature=0.0)
    k1 = ServeEngine(cfg, params, max_len=64, seed=7) \
        .generate(prompts, 6, temperature=0.9, top_k=1)
    np.testing.assert_array_equal(g.tokens, t0.tokens)   # T=0 is exact argmax
    np.testing.assert_array_equal(g.tokens, k1.tokens)   # top_k=1 too


def test_sampling_deterministic_and_scan_parity(setups):
    cfg, params, prompts, _ = setups["deepseek_7b"]
    mk = lambda: ServeEngine(cfg, params, max_len=64, seed=7)
    s1 = mk().generate(prompts, 6, temperature=0.8, top_k=8)
    s2 = mk().generate(prompts, 6, temperature=0.8, top_k=8)
    s3 = mk().generate(prompts, 6, temperature=0.8, top_k=8, scan=False)
    np.testing.assert_array_equal(s1.tokens, s2.tokens)  # seed-deterministic
    np.testing.assert_array_equal(s1.tokens, s3.tokens)  # same RNG chain
    assert (s1.tokens >= 0).all() and (s1.tokens < cfg.vocab).all()


def test_sample_token_top_k_support():
    """top_k masking really restricts the support."""
    logits = jnp.asarray(np.random.RandomState(1).randn(64, 32), jnp.float32)
    top2 = set(np.asarray(
        jax.lax.top_k(logits, 2)[1]).reshape(-1, 2).flatten().tolist())
    for s in range(3):
        tok = steps.sample_token(logits, jax.random.PRNGKey(s),
                                 jnp.float32(5.0), top_k=2)
        picked = np.asarray(tok)
        kidx = np.asarray(jax.lax.top_k(logits, 2)[1])
        for row, t in enumerate(picked):
            assert t in kidx[row]


# --------------------------------------------------------------------------- #
# compile-cache: repeated generate performs zero new traces
# --------------------------------------------------------------------------- #
def test_repeated_generate_zero_retrace(setups):
    """Advancing device age between calls re-jits NOTHING — the docstring
    claim, now enforced: BERs/keys enter as traced pytree leaves of a
    cached compiled function (scanned AND eager oracle paths)."""
    cfg, params, prompts, _ = setups["deepseek_7b"]
    rt = FleetRuntime(n_devices=1)
    rt.set_age(years=2.0)
    eng = ServeEngine(cfg, params, runtime=rt, max_len=64, seed=1,
                      use_systolic_kernel=True)
    eng.generate(prompts, 4)                      # compile scanned flavour
    eng.generate(prompts, 4, scan=False)          # compile eager flavour
    before = dict(steps.TRACE_COUNTS)
    rt.set_age(years=9.5)                         # new BER values, same avals
    eng.generate(prompts, 4)
    eng.generate(prompts, 4, scan=False)
    eng.generate(prompts, 4, temperature=0.8)     # sampling knob is traced
    assert dict(steps.TRACE_COUNTS) == before


def test_engines_share_compile_cache(setups):
    """A second engine instance with the same config reuses the module-level
    compiled functions — no per-engine jit wrappers."""
    cfg, params, prompts, _ = setups["deepseek_7b"]
    ServeEngine(cfg, params, max_len=64, seed=1).generate(prompts, 4)
    before = dict(steps.TRACE_COUNTS)
    ServeEngine(cfg, params, max_len=64, seed=99).generate(prompts, 4)
    assert dict(steps.TRACE_COUNTS) == before


# --------------------------------------------------------------------------- #
# fleet-batched serving
# --------------------------------------------------------------------------- #
def test_fleet_engine_matches_per_lane_dispatch(setups):
    """The vmapped fleet generation is exactly N independent per-lane calls
    of the same generation function: slicing the batched FaultConfig /
    keys per lane and dispatching the single-device function reproduces
    every lane's tokens bit-for-bit."""
    cfg, params, prompts, _ = setups["deepseek_7b"]
    N = 3
    fleet = FleetRuntime(n_devices=N)
    for i in range(N):
        fleet.set_age(years=3.0 * (i + 1), device=i)
    fe = FleetServeEngine(cfg, params, fleet, max_len=64, seed=5,
                          use_systolic_kernel=True)
    lane_prompts = np.stack([prompts, prompts + 1, prompts + 2]) % cfg.vocab
    res = fe.generate(lane_prompts, 4)
    assert res.tokens.shape == (N, 2, 4)
    assert res.bers.shape == (N, len(fleet.operators))

    # replay the engine's key schedule and dispatch lanes one by one
    key = jax.random.PRNGKey(5)
    _, call_key = jax.random.split(key)
    fi = fe._fleet_fault_config(call_key)
    keys = jax.random.split(jax.random.fold_in(call_key, 1), N)
    gen = _generate_fn(cfg, 64, 4, None)
    for i in range(N):
        fi_i = jax.tree.map(lambda x: x[i], fi)
        toks, _ = gen(params, jnp.asarray(lane_prompts[i], jnp.int32),
                      fi_i, keys[i], jnp.float32(0.0))
        np.testing.assert_array_equal(res.tokens[i], np.asarray(toks))


def test_fleet_engine_shards_flat_batch(setups):
    cfg, params, prompts, _ = setups["deepseek_7b"]
    fleet = FleetRuntime(n_devices=2)
    fleet.set_age(years=1.0)
    fe = FleetServeEngine(cfg, params, fleet, max_len=64, seed=5)
    flat = np.concatenate([prompts, prompts])      # (4, S) -> 2 lanes x 2
    res = fe.generate(flat, 3)
    assert res.tokens.shape == (2, 2, 3)
    assert res.ages_years.shape == (2,) and res.power_w.shape == (2,)
    # flat (N, S) means one prompt PER LANE (B=1), not a rank-1 lane batch
    res1 = fe.generate(prompts, 3)                 # (2, S) -> 2 lanes x 1
    assert res1.tokens.shape == (2, 1, 3)


def test_fleet_zero_retrace_on_aging(setups):
    cfg, params, prompts, _ = setups["deepseek_7b"]
    fleet = FleetRuntime(n_devices=2)
    fleet.set_age(years=2.0)
    fe = FleetServeEngine(cfg, params, fleet, max_len=64, seed=5,
                          use_systolic_kernel=True)
    lane_prompts = np.stack([prompts, prompts])
    fe.generate(lane_prompts, 3)
    before = dict(steps.TRACE_COUNTS)
    fleet.advance(3600 * 24 * 365, device=1)       # age one lane a year
    res = fe.generate(lane_prompts, 3)
    assert dict(steps.TRACE_COUNTS) == before
    assert res.ages_years[1] > res.ages_years[0]


def test_op_ber_array_matches_device_views():
    fleet = FleetRuntime(n_devices=3)
    for i in range(3):
        fleet.set_age(years=3.0 * (i + 1), device=i)
    mat = fleet.op_ber_array()
    assert mat.shape == (3, len(fleet.operators))
    for i in range(3):
        bers = fleet.op_bers(device=i)
        for j, op in enumerate(fleet.operators):
            assert mat[i, j] == bers[op]
    # heterogeneous ages -> older devices admit >= BER on tolerant domains
    q = fleet.operators.index("q")
    assert mat[2, q] >= mat[0, q]
