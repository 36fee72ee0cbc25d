"""Production training launcher.

Wires together every substrate: config registry, mesh + sharding, pjit'd
train step, deterministic data, ADMM schedule, async checkpointing with
SIGTERM preemption, resume, and (optional) gradient compression.

On the CPU container use a reduced config:
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 50 --seq-len 64 --global-batch 8 --admm --ckpt-dir /tmp/ckpt
On a real cluster the same entry point runs the full config on the
production mesh (--mesh-data/--mesh-model/--pods).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager, install_preemption_handler
from repro.configs import ARCH_NAMES, get_config, get_reduced
from repro.configs.base import MeshConfig, TrainConfig
from repro.core import admm as admm_mod
from repro.data.synthetic import LMStreamConfig, lm_batch
from repro.distributed import sharding as shd
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.registry import build
from repro.training import train_loop


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-9b", choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--admm", action="store_true")
    ap.add_argument("--admm-rho", type=float, default=1e-3)
    ap.add_argument("--admm-every", type=int, default=50)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16", "bf16_ef", "int8_ef"])
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build(cfg)
    tcfg = TrainConfig(
        learning_rate=args.lr, total_steps=args.steps,
        warmup_steps=max(1, args.steps // 10),
        microbatches=args.microbatches, admm_enabled=args.admm,
        admm_rho=args.admm_rho, admm_update_every=args.admm_every,
        grad_compression=args.grad_compression,
        moment_dtype=args.moment_dtype, remat=not args.reduced,
        checkpoint_every=args.ckpt_every)

    mesh_cfg = MeshConfig(pods=args.pods, data=args.mesh_data,
                          model=args.mesh_model)
    mesh = make_mesh(mesh_cfg)
    ctx = shd.ParallelContext.for_mesh(mesh)

    with shd.parallel_context(ctx), mesh:
        state, table = train_loop.init_train_state(
            model, tcfg, jax.random.PRNGKey(tcfg.seed))
        shardings = shd.params_shardings(state.params, ctx)
        state = dataclasses.replace(
            state, params=shd.reshard_state(state.params, shardings))
        step = jax.jit(train_loop.make_train_step(model, tcfg, table),
                       donate_argnums=0)

        start = 0
        mgr = None
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, keep=tcfg.keep_checkpoints)
            if args.resume and mgr.latest_step() is not None:
                state, start = mgr.restore_latest(state)
                print(f"resumed from step {start}")
            install_preemption_handler(
                lambda: (mgr.wait(), mgr.save_sync(state, int(state.step)),
                         print("preemption checkpoint written")))

        ds = LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                            global_batch=args.global_batch, seed=tcfg.seed)
        t0 = time.time()
        slow_steps = 0
        for i in range(start, args.steps):
            ts = time.time()
            state, metrics = step(state, lm_batch(ds, i))
            state = train_loop.maybe_admm_update(state, table, tcfg, i + 1)
            dt = time.time() - ts
            if i > start + 2 and dt > 5 * (time.time() - t0) / max(i - start, 1):
                slow_steps += 1  # straggler watchdog (logged, not fatal)
                print(f"[watchdog] slow step {i}: {dt:.2f}s")
            if (i + 1) % args.log_every == 0:
                extra = ""
                if state.admm is not None:
                    cm = admm_mod.constraint_metrics(state.params, state.admm,
                                                     table)
                    extra = (f"  viol {float(cm['polarization_violation']):.4f}")
                print(f"step {i+1:5d}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.2f}  "
                      f"{dt*1e3:.0f}ms{extra}", flush=True)
            if mgr and (i + 1) % args.ckpt_every == 0:
                mgr.save_async(state, i + 1)
        if mgr:
            mgr.save_sync(state, args.steps)
        tput = (args.steps - start) * args.global_batch * args.seq_len \
            / max(time.time() - t0, 1e-9)
        print(f"done: {args.steps - start} steps, {tput:.0f} tokens/s")


if __name__ == "__main__":
    main()
