"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Composes the full stack on whatever devices exist: reduced or full config,
sharded via the production rules, fault-tolerant loop (auto-resume, async
checkpoints, straggler watchdog), deterministic synthetic data.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data import SyntheticLM
from repro.distributed.sharding import (batch_spec, input_shardings,
                                        state_specs)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim import AdamWConfig
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.steps import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek_7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_local_mesh(model=args.model_parallel)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(10, args.steps // 20))
    step_fn = make_train_step(cfg, opt_cfg, microbatches=args.microbatches,
                              remat=args.remat)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.batch)

    def init():
        return init_train_state(cfg, jax.random.PRNGKey(0))

    state_sds = jax.eval_shape(init)
    st_specs = state_specs(state_sds, cfg, mesh)
    ns = lambda s: NamedSharding(mesh, s)
    st_shard = jax.tree.map(ns, st_specs, is_leaf=lambda s: isinstance(s, P))
    in_shard = input_shardings(cfg, mesh, args.batch, "train")
    jitted = jax.jit(step_fn, in_shardings=(st_shard, in_shard),
                     out_shardings=(st_shard, None), donate_argnums=(0,))

    def make_batch(step):
        tb = data.batch_at(step)
        extra = {}
        if cfg.prefix_tokens:
            extra["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.prefix_tokens, cfg.d_model), jnp.float32)
        if cfg.n_encoder_layers:
            extra["frames"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        return {"tokens": jnp.asarray(tb.tokens),
                "labels": jnp.asarray(tb.labels), **extra}

    loop = TrainLoop(jitted, data, ckpt_dir=args.ckpt_dir,
                     cfg=LoopConfig(total_steps=args.steps),
                     make_batch=make_batch)
    with mesh:
        state = loop.run(init)
    final = loop.history[-1]["loss"] if loop.history else float("nan")
    print(f"[train] done: final loss {final:.4f} "
          f"(uniform {np.log(cfg.vocab):.3f})")
    return loop


if __name__ == "__main__":
    main()
