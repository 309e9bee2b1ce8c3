"""Training driver: --arch <id> [--reduced] with fault-tolerant supervision.

On a real cluster this runs under the production mesh with the HM-planned
shardings; on this CPU container it drives reduced configs end-to-end
(checkpoints, restarts, straggler detection and metrics all live).

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
        --steps 50 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import ARCH_NAMES, ShapeConfig, get_config
from repro.core import planner
from repro.data import pipeline as data_lib
from repro.launch import compile_cache, mesh as mesh_lib
from repro.launch.cell import mesh_desc
from repro.runtime.fault_tolerance import FaultToleranceConfig, Supervisor
from repro.sharding import autoshard, specs as sh
from repro.train import loop as train_loop, optimizer as opt_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch + ("-reduced" if args.reduced else ""))
    mesh = mesh_lib.make_local_mesh()
    mesh_axes = sh.mesh_axis_sizes(mesh)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    plan = planner.plan_model(cfg, shape, mesh_desc(mesh))
    hints = (autoshard.make_hints(plan, mesh, args.batch)
             if mesh.devices.size > 1 else None)

    opt_cfg = opt_lib.OptimizerConfig(peak_lr=args.lr,
                                      warmup_steps=min(20, args.steps // 5),
                                      total_steps=args.steps)
    step_fn = train_loop.make_train_step(cfg, opt_cfg,
                                         remat_policy=args.remat,
                                         microbatches=args.microbatches,
                                         hints=hints)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    dcfg = data_lib.DataConfig(
        seq_len=args.seq, global_batch=args.batch,
        vocab_size=cfg.vocab_size, seed=args.seed,
        num_codebooks=cfg.num_codebooks,
        num_patches=cfg.num_patches if cfg.frontend == "vision" else 0,
        d_model=cfg.d_model, cond_len=cfg.cross_attn_cond)

    def data_fn(step: int):
        return {k: jax.numpy.asarray(v)
                for k, v in data_lib.synth_batch(dcfg, step).items()}

    def wrapped_step(state, batch):
        params, opt_state = state
        params, opt_state, metrics = jitted(params, opt_state, batch)
        return (params, opt_state), metrics

    def init_state():
        return train_loop.init_train_state(jax.random.PRNGKey(args.seed), cfg)

    ckpt_dir = args.ckpt_dir or os.path.join(
        "results", "ckpt", cfg.name.replace("/", "_"))
    sup = Supervisor(
        FaultToleranceConfig(checkpoint_dir=ckpt_dir,
                             checkpoint_every=args.ckpt_every),
        step_fn=wrapped_step, data_fn=data_fn, init_state_fn=init_state)

    t0 = time.time()
    result = sup.run(args.steps)
    dt = time.time() - t0
    for m in result["metrics"]:
        if m["step"] % args.log_every == 0 or m["step"] == args.steps - 1:
            print(f"step {m['step']:5d} loss={m.get('loss', 0):.4f} "
                  f"acc={m.get('accuracy', 0):.4f} "
                  f"gnorm={m.get('grad_norm', 0):.2f}")
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps, {dt:.1f}s, {toks / dt:.0f} tok/s, "
          f"restarts={result['restarts']}")
    return result


if __name__ == "__main__":
    main()
