"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the aging-aware engine end-to-end on a reduced config (``--full``:
the published widths in bfloat16, depth cut with ``--n-layers``):
initialises serving params (no optimizer state), builds a
:class:`repro.core.fleet.FleetRuntime` (``--n-devices`` simulated
accelerators of possibly different age), and generates batched tokens
under the per-operator BERs the policy admits at each device's age.

With ``--n-devices > 1`` the whole fleet serves in ONE dispatch: the
prompt batch is sharded across lanes and
:class:`~repro.serve.engine.FleetServeEngine` vmaps the compiled
prefill + scanned-decode generation over every device's BER vector.
``--device`` narrows to a single-lane :class:`ServeEngine`; ``--eager``
selects the per-token oracle loop (bit-exact, one dispatch per token).

``--router`` (default ``round_robin``) first ages the fleet under
*routed traffic*: the staggered deployment ages fold into the
:func:`repro.sched.lifetime.cosimulate` scan's initial state, the
``--workload`` arrival trace is routed each epoch, and the BERs actually
served reflect the traffic-dependent wear.  ``--router static`` keeps
the legacy fixed-profile aging; ``wear_level`` demonstrates the
scheduler actively slowing fleet aging (``python -m
repro.launch.schedule`` for the router comparison).

``--mesh`` serves ONE model sharded over a ``("data", "model")`` device
mesh instead of a fleet of replicas: tensor/expert parallelism over
``--tp`` devices (default: all visible — fake them on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* launch),
with a shard-granular fleet (``n_shards == tp``) giving every mesh shard
its own staggered age and per-operator BERs inside the single sharded
dispatch (:class:`repro.serve.sharded.MeshServeEngine`).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.fleet import FleetRuntime
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.sched.router import ROUTER_REGISTRY
from repro.sched.workload import WORKLOADS
from repro.serve.engine import FleetServeEngine, ServeEngine

YEAR_S = 365.25 * 24 * 3600.0


def serve_model(arch: str, *, full: bool = False, n_layers=None,
                seed: int = 0):
    """(cfg, params) for serving: the reduced CPU config in float32, or
    with ``full`` the published widths in bfloat16; ``n_layers`` cuts the
    depth.  Parameters only — serving needs no optimizer state."""
    from repro.models import encdec
    from repro.models import transformer as tf
    cfg = get_config(arch)
    if not full:
        cfg = cfg.reduced()
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=int(n_layers))
    init = encdec.init_params if cfg.n_encoder_layers else tf.init_params
    dtype = jnp.bfloat16 if full else jnp.float32
    return cfg, jax.jit(init, static_argnums=(0, 2))(
        cfg, jax.random.PRNGKey(seed), dtype)


def _print_cache_stats():
    """``--stats``: per-cache compiled-fn hit/miss/evict table."""
    from repro.serve.engine import cache_stats
    print("[serve] compiled-fn caches (hit/miss/evict, size):")
    for name, s in sorted(cache_stats().items()):
        print(f"    {name:<20} {s['hits']:>5} {s['misses']:>5} "
              f"{s['evictions']:>5}   {s['currsize']}/{s['maxsize']}")


def main(argv=None):
    import sys
    argv_list = list(sys.argv[1:] if argv is None else argv)
    if "--online" in argv_list:
        # continuous-batching mode: delegate to the online launcher
        # (live request queue, slot refills, occupancy-driven aging)
        from . import online
        argv_list.remove("--online")
        return online.main(argv_list)
    ap = argparse.ArgumentParser()
    ap.add_argument("--online", action="store_true",
                    help="serve a LIVE request queue with continuous "
                         "batching instead of a static prompt batch "
                         "(remaining args go to repro.launch.online)")
    ap.add_argument("--arch", default="deepseek_7b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published widths (bfloat16) instead "
                         "of the reduced CPU config")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the model to this many layers")
    ap.add_argument("--age-years", type=float, default=5.0)
    ap.add_argument("--n-devices", type=int, default=1,
                    help="fleet size; device i serves at age-years * "
                         "(i+1)/n (a staggered-deployment fleet)")
    ap.add_argument("--device", type=int, default=None,
                    help="serve ONE fleet device instead of the whole "
                         "fleet in one dispatch")
    ap.add_argument("--budget", type=float, default=0.5,
                    help="accuracy budget [%% loss] of the policy")
    ap.add_argument("--batch", type=int, default=4,
                    help="prompts per device")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples softmax(logits/T)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="restrict sampling to the k highest logits")
    ap.add_argument("--router", default="round_robin",
                    choices=tuple(sorted(ROUTER_REGISTRY)) + ("static",),
                    help="age the fleet under ROUTED traffic before "
                         "serving (repro.sched): served BERs then "
                         "reflect the staggered --age-years wear PLUS "
                         "--horizon-years of routed service; 'static' "
                         "keeps the legacy fixed-profile aging")
    ap.add_argument("--workload", default="diurnal",
                    choices=sorted(WORKLOADS),
                    help="request-arrival model fed to --router")
    ap.add_argument("--utilization", type=float, default=0.55,
                    help="mean offered load / fleet capacity for "
                         "--workload")
    ap.add_argument("--horizon-years", type=float, default=2.0,
                    help="service horizon the --router traffic spans "
                         "(on top of the staggered --age-years start)")
    ap.add_argument("--policy", default=None,
                    choices=("fault_tolerant", "baseline", "measured"),
                    help="AVS policy; 'measured' uses THIS arch's curves "
                         "from resilience_calibrated.json (regenerate with "
                         "repro.launch.calibrate_resilience)")
    ap.add_argument("--baseline-avs", action="store_true",
                    help="legacy alias for --policy baseline")
    ap.add_argument("--mesh", action="store_true",
                    help="serve ONE mesh-sharded model (tensor/expert "
                         "parallel over --tp devices) with per-shard "
                         "aging instead of a fleet of replicas")
    ap.add_argument("--tp", type=int, default=None,
                    help="--mesh model-axis size (default: all visible "
                         "devices)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="run weight matmuls through the int8 systolic "
                         "Pallas kernel (interpret mode on CPU: slow)")
    ap.add_argument("--fused", dest="fused", action="store_true",
                    default=None,
                    help="--mesh route: shard_map the fused aged-matmul "
                         "Pallas kernel per shard (default on TPU; "
                         "interpret mode on CPU: slow)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="--mesh route: force the kernel-free GSPMD "
                         "injection (same streams, same tokens)")
    ap.add_argument("--eager", action="store_true",
                    help="per-token oracle loop instead of the scanned "
                         "single-dispatch path (single-device only)")
    ap.add_argument("--stats", action="store_true",
                    help="print per-cache compiled-fn hit/miss/evict "
                         "stats after the run")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg, params = serve_model(args.arch, full=args.full,
                              n_layers=args.n_layers)
    pol = args.policy or ("baseline" if args.baseline_avs
                          else "fault_tolerant")
    if pol == "measured":
        # key the artifact lookup on the served arch — the closed loop:
        # measured curves -> tolerable BER -> delay_max -> admitted BERs
        from repro.core.artifacts import load_calibration
        from repro.core.policy import MeasuredResiliencePolicy
        pol = MeasuredResiliencePolicy(ber_model=load_calibration().ber,
                                       model=args.arch)
    if args.mesh:
        return _run_mesh(args, cfg, params, pol)
    fleet = FleetRuntime(
        n_devices=args.n_devices, policy=pol, max_loss_pct=args.budget)
    for i in range(args.n_devices):
        fleet.set_age(years=args.age_years * (i + 1) / args.n_devices,
                      device=i)
    if args.router != "static":
        # traffic-driven aging: fold the staggered ages into the co-sim's
        # initial state, route --horizon-years of the workload, and serve
        # at the BERs the traffic-dependent wear admits at end of horizon
        cos = fleet.apply_load(workload=args.workload, router=args.router,
                               utilization=args.utilization,
                               horizon_s=args.horizon_years * YEAR_S)
        wear = cos.device_wear()[-1]
        print(f"[serve] routed {args.horizon_years:g}y of "
              f"{args.workload} traffic ({cos.n_epochs} epochs) via "
              f"{args.router}: fleet-max ΔVth {wear.max():.1f} mV "
              f"(spread {wear.max() - wear.min():.1f} mV), mean util "
              f"{np.asarray(cos.util).mean():.2f}")

    fleet_mode = args.n_devices > 1 and args.device is None
    if args.eager and fleet_mode:
        ap.error("--eager is single-device only: pass --device <i> to "
                 "pick a lane (the fleet path has no per-token loop)")
    max_len = args.prompt_len + args.gen_len + 1
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.prompt_len,
                       global_batch=args.batch)
    prompts = data.batch_at(0).tokens
    extra = {}
    if cfg.prefix_tokens:
        extra["prefix_embeds"] = np.zeros(
            (args.batch, cfg.prefix_tokens, cfg.d_model), np.float32)
    if cfg.n_encoder_layers:
        extra["frames"] = np.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), np.float32)

    pol = getattr(fleet.policy, "name", "fault_tolerant")
    if fleet_mode:
        engine = FleetServeEngine(cfg, params, fleet, max_len=max_len,
                                  use_systolic_kernel=args.use_kernel)
        tile = lambda x: np.broadcast_to(
            x, (args.n_devices,) + x.shape).copy()
        res = engine.generate(tile(prompts), args.gen_len,
                              temperature=args.temperature,
                              top_k=args.top_k,
                              **{k: tile(v) for k, v in extra.items()})
        ages = ", ".join(f"{a:.1f}y" for a in res.ages_years)
        pw = ", ".join(f"{p:.2f}W" for p in res.power_w)
        print(f"[serve] arch={cfg.name} fleet={args.n_devices} "
              f"policy={pol} budget={args.budget}% — ONE dispatch for the "
              f"whole fleet")
        print(f"[serve] fleet ages: [{ages}]  power: [{pw}] "
              f"(total {res.power_w.sum():.2f} W)")
        q = res.operators.index("q")
        bq = ", ".join(f"{b:.1e}" for b in res.bers[:, q])
        print(f"[serve] per-lane BER(q): [{bq}]")
        print(f"[serve] generated {res.tokens.shape} tokens "
              "(lanes x batch x steps); lane rows: ")
        for i in range(args.n_devices):
            print(f"    dev{i} ({res.ages_years[i]:.1f}y): "
                  f"{res.tokens[i, 0][:12].tolist()}")
        if args.stats:
            _print_cache_stats()
        return res

    engine = ServeEngine(cfg, params, runtime=fleet,
                         device=args.device or 0, max_len=max_len,
                         use_systolic_kernel=args.use_kernel)
    res = engine.generate(prompts, args.gen_len,
                          temperature=args.temperature, top_k=args.top_k,
                          scan=not args.eager, **extra)
    print(f"[serve] arch={cfg.name} fleet={args.n_devices} "
          f"dev={args.device or 0} age={res.age_years:.1f}y policy={pol} "
          f"budget={args.budget}% path="
          f"{'eager-oracle' if args.eager else 'scanned'}")
    print(f"[serve] per-op BER: " + ", ".join(
        f"{k}={v:.1e}" for k, v in sorted(res.bers.items())))
    print(f"[serve] est. array power: {res.power_w:.2f} W "
          f"(x{len(res.bers)} domains)")
    print(f"[serve] generated {res.tokens.shape} tokens; "
          f"first row: {res.tokens[0][:12].tolist()}")
    if args.stats:
        _print_cache_stats()
    return res


def _run_mesh(args, cfg, params, pol):
    """One mesh-sharded model, per-shard aging, ONE sharded dispatch."""
    from repro.serve.sharded import MeshServeEngine, default_serve_mesh

    mesh = default_serve_mesh(args.tp)
    tp = mesh.shape["model"]
    fleet = FleetRuntime(n_devices=1, n_shards=tp, policy=pol,
                         max_loss_pct=args.budget)
    for s in range(tp):
        # staggered shard ages: a device rebuilt from spares of mixed age
        fleet.set_age(years=args.age_years * (s + 1) / tp, shard=s)
    if args.router != "static":
        cos = fleet.apply_load(workload=args.workload, router=args.router,
                               utilization=args.utilization,
                               horizon_s=args.horizon_years * YEAR_S)
        wear = cos.device_wear()[-1]
        print(f"[serve] routed {args.horizon_years:g}y of {args.workload} "
              f"traffic over the {tp} shards via {args.router}: max ΔVth "
              f"{wear.max():.1f} mV (spread "
              f"{wear.max() - wear.min():.1f} mV)")

    max_len = args.prompt_len + args.gen_len + 1
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.prompt_len,
                       global_batch=args.batch)
    prompts = data.batch_at(0).tokens
    extra = {}
    if cfg.prefix_tokens:
        extra["prefix_embeds"] = np.zeros(
            (args.batch, cfg.prefix_tokens, cfg.d_model), np.float32)
    if cfg.n_encoder_layers:
        extra["frames"] = np.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), np.float32)

    # --fused / --no-fused; unset defaults to the engine's fused route
    engine = MeshServeEngine(cfg, params, mesh=mesh, fleet=fleet,
                             max_len=max_len,
                             use_fused_kernel=(args.fused
                                               if args.fused is not None
                                               else True))
    res = engine.generate(prompts, args.gen_len,
                          temperature=args.temperature, top_k=args.top_k,
                          **extra)
    pol_name = getattr(fleet.policy, "name", "fault_tolerant")
    ages = ", ".join(f"{a:.1f}y" for a in res.ages_years)
    print(f"[serve] arch={cfg.name} mesh tp={tp} policy={pol_name} "
          f"budget={args.budget}% — ONE sharded dispatch, per-shard aging")
    print(f"[serve] shard ages: [{ages}]  device power: {res.power_w:.2f} W")
    print("[serve] per-shard BER table (rows=shards):")
    head = "         " + " ".join(f"{op:>8s}" for op in res.operators)
    print(head)
    for s in range(res.bers.shape[0]):
        row = " ".join(f"{b:8.1e}" for b in res.bers[s])
        print(f"  shard{s} {row}")
    print(f"[serve] generated {res.tokens.shape} tokens; "
          f"first row: {res.tokens[0][:12].tolist()}")
    if args.stats:
        _print_cache_stats()
    return res


if __name__ == "__main__":
    main()
