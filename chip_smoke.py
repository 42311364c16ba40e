"""Chip smoke test: aged-matmul serving on a TPU, end to end, in one process.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh     # four chips: MeshServeEngine at tp=4

The default run serves ``deepseek_7b`` at its published widths (d_model
4096, 32 heads, d_ff 11008, vocab 102400) cut to 4 layers, with random
bfloat16 parameters from ``--seed``, on a fleet device aged 9 years under
the fault-tolerant AVS policy.  It drives the normal engines:
``ServeEngine.generate`` through the fused Pallas kernel, through the
default (kernel-free) route and clean; ``OnlineServeEngine.serve`` on 16
requests over 8 slots; and ``FleetServeEngine`` over 2 lanes with the
kernel.  Every check below ends the run with a nonzero exit when it fails:

* the kernel phases compiled to ``tpu_custom_call`` (no interpret mode);
* tokens lie in the vocabulary and the logit taps are finite;
* at BER 0 the fused kernel's int32 accumulator equals
  ``kernels.ref.systolic_matmul_ref`` bitwise;
* at BER 1e-4 on a 4096x4096 product the flipped-word count lies within
  5 sigma of ``q * M * N``, every upset is one bit, and the bit positions
  are uniform within 5 sigma;
* the online engine's first wave of slots is bit-exact with the one-shot
  kernel generate;
* the fleet's AVS voltages, dVth and admitted BERs at the served age agree
  between the TPU and the host CPU: voltages exactly, dVth within
  ``DVTH_RTOL``, BERs within ``BER_DECADES``;
* the clean route's tokens agree with one plain forward pass, teacher
  forced, within ``REF_LOGIT_RTOL``.

``--mesh`` runs only the tensor-parallel path: ``MeshServeEngine`` at
tp=4 with per-shard aging, whose compiled program must hold the
``shard_map``-wrapped kernel, against the single-chip generate function on
device 0 fed the same per-shard BERs and keys.  It reports whether tokens
are bit-exact, aged and at BER 0, and checks the rms prefill-logit
difference at BER 0 against ``MESH_LOGIT_RMS``.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits nonzero and prints no result; ``--rehearse``
runs the same phases on the CPU at the reduced config (Pallas interpret
mode), for rehearsing the control flow without a chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.fleet import FleetRuntime  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve_model  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.obs.taps import enable_taps  # noqa: E402
from repro.serve import engine as serve_engine  # noqa: E402
from repro.serve.online import OnlineServeEngine, Request  # noqa: E402

ARCH = "deepseek_7b"
N_LAYERS = 4
AGE_YEARS = 9.0
FLIP_BER = 1e-4
# TPU vs host-CPU physics.  Both run float32 with the delay polynomial at
# HIGHEST precision, but the TPU's exp/log/pow are accurate only to about
# 1e-5 relative (4e-6 to 9e-6 measured on a v5e, against 1-2 ulp on the
# CPU), and the lifetime scan composes them in each of its 480 steps, so
# dVth may drift by up to 480 * 2e-5 ~ 1e-2.  At the served delay the
# steepest operator's BER moves 3.8 decades per tau (30 ps) of delay and
# its delay about 3 ps per mV of dVth: 1e-2 of ~93 mV gives 0.35 decade.
# The AVS voltage is a discrete decision and must match exactly.
DVTH_RTOL = 1e-2
BER_DECADES = 0.35
# clean KV-cache decoding vs a plain forward: the same bf16 arithmetic in
# another order (attention over one query against the cache, not the full
# causal matrix), so allow four bf16 ulps (4 * 2**-7) of the top logit
REF_LOGIT_RTOL = 2 ** -5
# mesh vs one chip at BER 0.  On a TPU v5e the two programs round bf16
# intermediates in different places: clean serving differs by one bf16
# ulp, but the faulted routes quantise to int8, where a one-ulp change
# moves a whole quantisation step that random weights carry on (max
# |diff| 0.351 against a top logit of 4.9).  So bit-exactness is
# reported, not asked.  A wrong layout, gather or shard order leaves the
# logits uncorrelated, an rms difference of sqrt(2) times their spread;
# ask for a sixth of that.
MESH_LOGIT_RMS = 0.25


@dataclasses.dataclass(frozen=True)
class Sizes:
    batch: int
    prompt: int
    new: int
    kernel_dim: int      # M = K = N of the kernel checks
    n_requests: int
    n_slots: int


CHIP = Sizes(batch=8, prompt=128, new=16, kernel_dim=4096, n_requests=16,
             n_slots=8)
REHEARSAL = Sizes(batch=8, prompt=16, new=4, kernel_dim=512, n_requests=16,
                  n_slots=8)


def check(ok, what: str) -> None:
    """One smoke check: print it, and end the run if it failed."""
    print(f"[check] {'PASS' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: check failed: {what}")


def expect_kernel(text: str, what: str, on_tpu: bool) -> None:
    """The compiled program holds a Mosaic kernel (TPU only: a CPU
    rehearsal runs the kernels in interpret mode, as plain HLO)."""
    if on_tpu:
        check("tpu_custom_call" in text, f"{what} compiled to tpu_custom_call")


def check_tokens(tokens: np.ndarray, shape: tuple, vocab: int,
                 what: str) -> None:
    check(tokens.shape == shape and tokens.min() >= 0
          and tokens.max() < vocab,
          f"{what}: tokens {tokens.shape} in [0, {vocab})")


def check_taps(telemetry, what: str) -> None:
    check(telemetry is not None
          and all(np.isfinite(np.asarray(v)).all()
                  for v in telemetry.values()),
          f"{what}: logit taps finite")


def prompts_for(cfg, sz: Sizes, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (sz.batch, sz.prompt), dtype=np.int32)


def aged_fleet(n_devices: int = 1, n_shards: int = 1) -> FleetRuntime:
    fleet = FleetRuntime(n_devices=n_devices, n_shards=n_shards,
                         policy="fault_tolerant")
    fleet.set_age(years=AGE_YEARS)
    return fleet


# --------------------------------------------------------------------------- #
# one-chip phases
# --------------------------------------------------------------------------- #
def phase_generate(cfg, params, prompts, sz, on_tpu, seed) -> dict:
    """ServeEngine.generate: fused kernel, default route, clean."""
    fleet = aged_fleet()
    max_len = sz.prompt + sz.new + 1
    shape = (sz.batch, sz.new)
    out = {}
    for route, kw in (("kernel", dict(runtime=fleet,
                                      use_systolic_kernel=True)),
                      ("default", dict(runtime=fleet)),
                      ("clean", dict(runtime=None))):
        eng = serve_engine.ServeEngine(cfg, params, max_len=max_len,
                                       seed=seed, **kw)
        t0 = time.perf_counter()
        first = eng.generate(prompts, sz.new)
        t1 = time.perf_counter()
        warm = eng.generate(prompts, sz.new)
        t2 = time.perf_counter()
        print(f"[smoke timing] generate {route}: first call {t1 - t0:.2f} s "
              f"(compile + run), warm call {t2 - t1:.4f} s = "
              f"{sz.batch * sz.new / (t2 - t1):.1f} tok/s "
              f"(prefill {sz.batch}x{sz.prompt} + {sz.new} steps)",
              flush=True)
        check_tokens(first.tokens, shape, cfg.vocab, f"generate {route}")
        check_taps(warm.telemetry, f"generate {route}")
        if route == "kernel":
            gen = serve_engine._generate_fn(cfg, max_len, sz.new, None)
            text = gen.lower(params, jnp.asarray(prompts), eng._fault_config(),
                             jax.random.PRNGKey(0), jnp.float32(0.0)) \
                .compile().as_text()
            expect_kernel(text, "generate kernel", on_tpu)
            print(f"[smoke] served BERs at {first.age_years:.1f} y: "
                  + ", ".join(f"{k}={v:.3g}"
                              for k, v in sorted(first.bers.items())))
        out[route] = first.tokens
    for route in ("kernel", "default"):
        agree = float(np.mean(out[route] == out["clean"]))
        print(f"[smoke] generate {route}: {agree:.3f} of tokens equal clean")
    return out


def check_reference(cfg, params, prompts, tokens) -> None:
    """Clean greedy decoding against one plain full-sequence forward pass.

    Teacher-forced on the generated tokens, the forward's logits at the
    last prompt position and after predict every generated token.  The
    engine prefills, then decodes through its KV cache one token at a
    time, so its bf16 arithmetic runs in another order: a token counts as
    agreeing when its reference logit is within REF_LOGIT_RTOL of the
    row's largest.  A wrong position, cache slot or mask misses by the
    logits' own spread.
    """
    n_prompt = prompts.shape[1]
    seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    forward = jax.jit(
        lambda p, t: tf.forward_logits(p, cfg, t)[0][:, n_prompt - 1:])
    ref = forward(params, jnp.asarray(seq))
    ref = np.asarray(ref, np.float64)                       # (B, new, V)
    top = ref.max(axis=-1)
    chosen = np.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    gap = float(np.max((top - chosen) / np.abs(top)))
    exact = float(np.mean(tokens == ref.argmax(axis=-1)))
    print(f"[smoke] clean generate vs plain forward: {exact:.3f} of tokens "
          f"are its argmax, largest shortfall {gap:.3e} x the top logit")
    check(gap <= REF_LOGIT_RTOL,
          f"clean generate agrees with a plain forward within "
          f"{REF_LOGIT_RTOL:g} x the top logit")


def phase_online(cfg, params, prompts, sz, kernel_tokens, seed) -> None:
    """OnlineServeEngine.serve: n_requests over n_slots, fused kernel."""
    rng = np.random.default_rng(seed + 1)
    extra = rng.integers(0, cfg.vocab,
                         (sz.n_requests - sz.batch, sz.prompt),
                         dtype=np.int32)
    all_prompts = np.concatenate([prompts, extra])
    reqs = [Request(id=i, prompt=p, max_new=sz.new)
            for i, p in enumerate(all_prompts)]
    eng = OnlineServeEngine(cfg, params, runtime=aged_fleet(),
                            n_slots=sz.n_slots,
                            max_len=sz.prompt + sz.new + 1,
                            max_new_cap=sz.new, chunk_steps=8,
                            use_systolic_kernel=True, seed=seed)
    t0 = time.perf_counter()
    res = eng.serve(reqs)
    wall = time.perf_counter() - t0
    print(f"[smoke timing] online serve: {res.n_tokens} tokens in "
          f"{wall:.2f} s including compiles", flush=True)
    done = sorted(res.completed, key=lambda r: r.id)
    check(len(done) == sz.n_requests and all(
        r.tokens is not None and len(r.tokens) == sz.new
        and r.tokens.min() >= 0 and r.tokens.max() < cfg.vocab
        for r in done),
        f"online: {sz.n_requests} requests completed with {sz.new} "
        f"in-vocabulary tokens each")
    check_taps(res.telemetry, "online")
    first_wave = np.stack([r.tokens for r in done[:sz.batch]])
    check(np.array_equal(first_wave, kernel_tokens),
          "online: first wave of slots bit-exact with generate kernel")


def phase_fleet(cfg, params, prompts, sz, on_tpu, seed) -> None:
    """FleetServeEngine: 2 lanes aged 3 and 9 years, fused kernel."""
    fleet = FleetRuntime(n_devices=2, policy="fault_tolerant")
    fleet.set_age(years=3.0, device=0)
    fleet.set_age(years=AGE_YEARS, device=1)
    max_len = sz.prompt + sz.new + 1
    eng = serve_engine.FleetServeEngine(cfg, params, fleet, max_len=max_len,
                                        use_systolic_kernel=True, seed=seed)
    lanes = np.stack([prompts, prompts])
    t0 = time.perf_counter()
    res = eng.generate(lanes, sz.new)
    print(f"[smoke timing] fleet generate (2 lanes): first call "
          f"{time.perf_counter() - t0:.2f} s (compile + run)", flush=True)
    check_tokens(res.tokens, (2, sz.batch, sz.new), cfg.vocab, "fleet")
    check_taps(res.telemetry, "fleet")
    gen = serve_engine._fleet_generate_fn(cfg, max_len, sz.new, None)
    call_key = jax.random.PRNGKey(0)
    text = gen.lower(params, jnp.asarray(lanes),
                     eng._fleet_fault_config(call_key),
                     jax.random.split(call_key, 2), jnp.float32(0.0)) \
        .compile().as_text()
    expect_kernel(text, "fleet generate (kernel under vmap)", on_tpu)
    print(f"[smoke] fleet lane BER(q): {res.bers[:, 0].tolist()}")


def phase_kernel(sz, on_tpu, seed) -> None:
    """The fused kernel against the int32 oracle, and its upset rate."""
    n = sz.kernel_dim
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.randint(ka, (n, n), -127, 128, jnp.int8)
    b = jax.random.randint(kb, (n, n), -127, 128, jnp.int8)
    ref = kref.systolic_matmul_ref(a, b)
    expect_kernel(kops.fused_aged_matmul.lower(a, b, ber=FLIP_BER, seed=7)
                  .compile().as_text(), "fused_aged_matmul", on_tpu)
    clean = kops.fused_aged_matmul(a, b, ber=0.0, seed=7)
    check(bool(jnp.array_equal(clean, ref)),
          "fused kernel at BER 0 equals systolic_matmul_ref bitwise")

    diff = np.asarray(kops.fused_aged_matmul(a, b, ber=FLIP_BER, seed=7)
                      ^ ref).view(np.uint32)
    flipped = diff[diff != 0]
    q = 1.0 - (1.0 - FLIP_BER) ** 32
    mean, sd = q * n * n, math.sqrt(n * n * q * (1.0 - q))
    print(f"[smoke] BER {FLIP_BER:g} on {n}x{n}: {flipped.size} flipped "
          f"words, expected {mean:.1f} +- {sd:.1f}")
    check(abs(flipped.size - mean) <= 5 * sd,
          "flipped-word count within 5 sigma of q*M*N")
    check(bool(np.all(flipped & (flipped - 1) == 0)),
          "every upset flips exactly one bit")
    counts = np.bincount(np.log2(flipped).astype(np.int64), minlength=32)
    p = 1.0 / 32
    bit_sd = math.sqrt(flipped.size * p * (1 - p))
    check(counts.size == 32 and np.all(
        np.abs(counts - flipped.size * p) <= 5 * bit_sd),
        "upset bit positions uniform within 5 sigma")


def phase_physics() -> None:
    """Admitted BERs and dVth at the served age: chip vs host CPU."""
    def state(device):
        with jax.default_device(device):
            snap = aged_fleet().snapshot()
            return {k: np.asarray(getattr(snap, k), np.float64)
                    for k in ("v_dd", "ber", "dvth_p_mv", "dvth_n_mv")}
    chip = state(jax.devices()[0])
    host = state(jax.devices("cpu")[0])
    platform = jax.devices()[0].platform
    check(np.array_equal(chip["v_dd"], host["v_dd"]),
          f"AVS voltages on {platform} equal the host CPU's")
    for key in ("dvth_p_mv", "dvth_n_mv"):
        rel = np.max(np.abs(chip[key] - host[key]) / np.abs(host[key]))
        print(f"[smoke] physics {key}: max relative diff chip vs CPU "
              f"{rel:.3e} (tolerance {DVTH_RTOL:g})")
        check(rel <= DVTH_RTOL,
              f"{key} on {platform} within {DVTH_RTOL:g} of the host CPU")
    dec = np.max(np.abs(np.log10(chip["ber"] / host["ber"])))
    print(f"[smoke] physics ber: max |log10 ratio| chip vs CPU {dec:.3e} "
          f"decades (tolerance {BER_DECADES:g})")
    check(dec <= BER_DECADES,
          f"admitted BERs on {platform} within {BER_DECADES:g} decade of "
          f"the host CPU")


# --------------------------------------------------------------------------- #
# four-chip phase
# --------------------------------------------------------------------------- #
def phase_mesh(cfg, params, prompts, sz, on_tpu, seed) -> None:
    """MeshServeEngine at tp=4 vs the one-chip generate on device 0.

    Both sides run one fault configuration: the mesh engine's per-shard
    BER vectors and key.  On one chip, with no mesh in scope, the vectors
    take the kernel-free route, which draws the counter streams that the
    shard_map kernel draws in interpret mode.  A compiled TPU kernel draws
    its upsets from the hardware PRNG instead, so on the chip the aged
    runs agree only statistically, and bit-exactness is asked of the same
    two compiled programs with every BER zeroed.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as shrules
    from repro.serve import steps
    from repro.serve.sharded import MeshServeEngine, _mesh_generate_fn

    tp = 4
    check(len(jax.devices()) >= tp, f"{tp} devices visible")
    fleet = FleetRuntime(n_devices=1, n_shards=tp, policy="fault_tolerant")
    for s in range(tp):
        fleet.set_age(years=AGE_YEARS * (s + 1) / tp, shard=s)
    max_len = sz.prompt + sz.new + 1
    eng = MeshServeEngine(cfg, params, tp=tp, fleet=fleet, max_len=max_len,
                          seed=seed, use_fused_kernel=True)
    t0 = time.perf_counter()
    res = eng.generate(prompts, sz.new)
    print(f"[smoke timing] mesh generate tp={tp}: first call "
          f"{time.perf_counter() - t0:.2f} s (compile + run)", flush=True)
    check_tokens(res.tokens, (sz.batch, sz.new), cfg.vocab, "mesh generate")
    check_taps(res.telemetry, "mesh generate")

    repl = NamedSharding(eng.mesh, P())
    dev0 = jax.devices()[0]
    on_mesh = lambda x: jax.device_put(x, repl)
    on_one = lambda x: jax.device_put(x, dev0)
    aged = eng._fault_config()
    zero = dataclasses.replace(aged, bers={
        op: on_mesh(jnp.zeros_like(b)) for op, b in aged.bers.items()})
    # host copies: the mesh program donates its prompts and key, and a
    # replicated put of a device-0 array may hand it that very buffer
    call_key = np.asarray(jax.random.PRNGKey(seed + 1))
    temp = np.float32(0.0)
    gen_mesh = _mesh_generate_fn(cfg, max_len, sz.new, None, eng.mesh)
    text = gen_mesh.lower(eng.params, on_mesh(prompts), aged,
                          on_mesh(call_key), on_mesh(temp)) \
        .compile().as_text()
    expect_kernel(text, "mesh generate (shard_map fused route)", on_tpu)

    # the one-chip side gets the mesh engine's own bf16 values
    one_params = on_one(jax.device_get(eng.params))
    gen_one = serve_engine._generate_fn(cfg, max_len, sz.new, None)
    tokens = {}
    for name, fi in (("aged", aged), ("BER 0", zero)):
        mesh_tokens, _ = gen_mesh(eng.params, on_mesh(prompts), fi,
                                  on_mesh(call_key), on_mesh(temp))
        one_tokens, _ = gen_one(one_params, on_one(prompts), on_one(fi),
                                on_one(call_key), temp)
        tokens[name] = (np.asarray(mesh_tokens), np.asarray(one_tokens))
        m, o = tokens[name]
        print(f"[smoke] mesh tp={tp} {name}: tokens bit-exact with one chip: "
              f"{bool(np.array_equal(m, o))}; share equal by step "
              f"{np.round(np.mean(m == o, axis=0), 3).tolist()}")
    check_tokens(tokens["BER 0"][0], (sz.batch, sz.new), cfg.vocab,
                 "mesh generate at BER 0")

    prefill = steps.make_prefill_fn(cfg, max_len)

    def mesh_prefill(p, t, fi):
        with shrules.serve_mesh_scope(eng.mesh):
            return prefill(p, t, fi.with_seeds())[0]

    lm = jax.jit(mesh_prefill)(eng.params, on_mesh(prompts), zero)
    lo = jax.jit(lambda p, t, fi: prefill(p, t, fi.with_seeds())[0])(
        one_params, on_one(prompts), on_one(zero))
    lm, lo = np.asarray(lm, np.float64), np.asarray(lo, np.float64)
    rms = float(np.sqrt(np.mean((lm - lo) ** 2)) / np.std(lo))
    print(f"[smoke] mesh tp={tp} prefill logits at BER 0: max |diff| "
          f"{np.max(np.abs(lm - lo)):.4g} of max |logit| "
          f"{np.max(np.abs(lo)):.4g}, rms diff {rms:.4g} x the logits' "
          f"spread, argmax equal in "
          f"{float(np.mean(lm.argmax(-1) == lo.argmax(-1))):.3f} of rows")
    check(np.isfinite(lm).all() and rms <= MESH_LOGIT_RMS,
          f"mesh tp={tp} prefill logits at BER 0 within {MESH_LOGIT_RMS:g} "
          f"rms of the one-chip logits' spread")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip MeshServeEngine path and "
                         "its one-chip comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU backend: reduced config, Pallas "
                         "interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
                 f"(--rehearse runs the reduced config on the CPU)")
    cache = enable_compile_cache()
    print(f"[smoke] {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"compile cache: {cache or 'off'}", flush=True)

    sz = REHEARSAL if args.rehearse else CHIP
    t0 = time.perf_counter()
    cfg, params = serve_model(ARCH, full=not args.rehearse,
                              n_layers=None if args.rehearse else N_LAYERS,
                              seed=args.seed)
    jax.block_until_ready(params)
    print(f"[smoke] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}; params "
          f"built in {time.perf_counter() - t0:.1f} s", flush=True)
    prompts = prompts_for(cfg, sz, args.seed)

    with enable_taps():
        if args.mesh:
            phase_mesh(cfg, params, prompts, sz, on_tpu, args.seed)
        else:
            tokens = phase_generate(cfg, params, prompts, sz, on_tpu,
                                    args.seed)
            phase_online(cfg, params, prompts, sz, tokens["kernel"],
                         args.seed)
            phase_fleet(cfg, params, prompts, sz, on_tpu, args.seed)
            phase_kernel(sz, on_tpu, args.seed)
            phase_physics()
            check_reference(cfg, params, prompts, tokens["clean"])
    print(f"[smoke] all checks passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
