"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --scale smoke --steps 50 --ckpt-dir /tmp/ckpt [--resume] \
      [--hfused-optimizer] [--compression int8_pod] [--zero]

``--scale smoke`` runs the reduced config on local devices (CPU-runnable
end-to-end driver); ``--scale full`` expects the production mesh.
Fault tolerance: async checkpoints every --ckpt-every steps, auto-resume,
straggler watchdog with data-pipeline skip-ahead, bounded restart loop.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig, Prefetcher, TokenPipeline
from repro.distributed import sharding as shd
from repro.models import lm
from repro.train import checkpoint, optimizer as opt_mod
from repro.train.fault_tolerance import StepWatchdog, run_with_restarts
from repro.train.train_loop import TrainConfig, make_train_step
from repro.train.optimizer import AdamWConfig


def build(cfg, tcfg: TrainConfig, mesh=None, update_program=None):
    params = lm.init(cfg, jax.random.PRNGKey(0))
    opt_state = opt_mod.init(params)
    step_fn = jax.jit(make_train_step(cfg, tcfg, mesh,
                                      update_program=update_program),
                      donate_argnums=(0, 1))
    return params, opt_state, step_fn


def main(argv=None):
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--hfused-optimizer", action="store_true")
    ap.add_argument("--plan-fusion", action="store_true",
                    help="plan optimizer/backward fusion bundles AND execute "
                         "the optimizer step through the plan->program "
                         "executor (core/executor)")
    ap.add_argument("--dry-steps", type=int, default=None,
                    help="run only N steps with checkpointing disabled "
                         "(CI executor smoke)")
    ap.add_argument("--measure", choices=["auto", "interpret", "tpu", "gpu"],
                    default=None,
                    help="pick planned schedules by measurement "
                         "(core/timing.make_measure backend)")
    ap.add_argument("--compression", choices=["int8_pod"], default=None)
    ap.add_argument("--zero", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--max-failures", type=int, default=3)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    if args.measure and not args.plan_fusion:
        ap.error("--measure only applies to --plan-fusion schedule selection")
    if args.dry_steps is not None:
        args.steps = args.dry_steps
        args.ckpt_dir = ""

    cfg = get_config(args.arch)
    if args.scale == "smoke":
        cfg = cfg.reduced()
    ocfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(1, args.steps // 10),
                      hfused=args.hfused_optimizer)
    tcfg = TrainConfig(optimizer=ocfg, grad_accum=args.grad_accum,
                       compression=args.compression, zero=args.zero,
                       remat=args.scale == "full")

    update_program = None
    if args.plan_fusion:
        from repro.core.schedule_cache import default_cache
        from repro.core.timing import make_measure
        from repro.train.train_loop import (build_update_program,
                                            plan_update_fusion)
        measure = make_measure(args.measure) if args.measure else None
        abstract_params = jax.eval_shape(
            lambda: lm.init(cfg, jax.random.PRNGKey(0)))
        fplan = plan_update_fusion(
            abstract_params, tokens=args.batch * args.seq, measure=measure,
            cache=default_cache())
        print("[plan-fusion] optimizer/backward bundles (planning view):")
        for row in fplan.summary():
            print(f"  {row}")
        # the executed hot path: every leaf's update, lowered plan->program
        update_program = build_update_program(
            abstract_params, ocfg, measure=measure, cache=default_cache())
        print("[plan-fusion] executed update program "
              f"({update_program.program.n_fused} fused launches):")
        for row in update_program.describe():
            print(f"  {row}")

    mesh = None
    if args.scale == "full":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()

    data = TokenPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch,
        num_codebooks=cfg.num_codebooks if cfg.frontend == "audio_stub" else 0,
        num_image_tokens=cfg.num_image_tokens
        if cfg.frontend == "vision_stub" else 0,
        d_model=cfg.d_model))

    ckpt = (checkpoint.AsyncCheckpointer(args.ckpt_dir)
            if args.ckpt_dir else None)
    watchdog = StepWatchdog()

    def make_state():
        params, opt_state, step_fn = build(cfg, tcfg, mesh, update_program)
        start = 0
        if ckpt and args.resume:
            got = checkpoint.restore_latest(
                args.ckpt_dir, {"params": params,
                                "m": opt_state.m, "v": opt_state.v})
            if got:
                start, tree, meta = got
                params = tree["params"]
                opt_state = opt_mod.OptState(
                    m=tree["m"], v=tree["v"],
                    count=jnp.asarray(start, jnp.int32))
                data.restore({"step": start, "shard": 0})
                print(f"[resume] from step {start}")
        return dict(params=params, opt=opt_state, step_fn=step_fn, start=start)

    def loop(state, _failures):
        params, opt_state, step_fn = state["params"], state["opt"], state["step_fn"]
        losses = []
        for step in range(state["start"], args.steps):
            t0 = time.time()
            batch = jax.tree.map(jnp.asarray, data.batch_at(step))
            params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                 jnp.asarray(step))
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            if watchdog.observe(step, dt):
                data.skip_ahead(0)   # single-host: log only
                print(f"[straggler] step {step} took {dt:.2f}s")
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} {dt * 1e3:.0f}ms",
                      flush=True)
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save_async(step, {"params": params, "m": opt_state.m,
                                       "v": opt_state.v},
                                {"loss": loss})
        if ckpt:
            ckpt.save_async(args.steps, {"params": params, "m": opt_state.m,
                                         "v": opt_state.v}, {})
            ckpt.wait()
        if losses:
            print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
        else:
            print(f"nothing to do: resumed at step {state['start']} "
                  f">= --steps {args.steps}")
        return losses

    return run_with_restarts(make_state, loop, max_failures=args.max_failures,
                             on_restart=lambda n: print(f"[restart #{n}]"))


if __name__ == "__main__":
    main()
