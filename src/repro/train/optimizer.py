"""AdamW, implemented directly over param pytrees (fp32 moments, bf16 params).

Two update paths:
  * ``adamw_update``       — pure-jnp pytree math (default; what the dry-run
                             lowers).
  * ``hfused`` flag        — routes the per-tensor updates through the
                             horizontally-fused Pallas Adam kernel
                             (repro/kernels/adam.py): all N independent,
                             memory-bound per-tensor update "kernels" become
                             one launch over a concatenated flat buffer —
                             the paper's fusion applied to the optimizer
                             (DESIGN.md §4.3).  Compiled on an accelerator,
                             interpreted on the CPU test backend.

Gradient compression (int8 + error feedback) lives in
repro/distributed/compression.py and wraps the gradient *before* the update.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    hfused: bool = False


class OptState(NamedTuple):
    m: Any
    v: Any
    count: jax.Array


def schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay."""
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(1.0, cfg.warmup_steps)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(1.0, cfg.total_steps - cfg.warmup_steps), 0, 1)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.minimum(warm, cos)


def init(params) -> OptState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return OptState(m=zeros,
                    v=jax.tree.map(jnp.copy, zeros),
                    count=jnp.zeros((), jnp.int32))


def abstract_init(abstract_params) -> OptState:
    zeros = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                         abstract_params)
    return OptState(m=zeros, v=zeros,
                    count=jax.ShapeDtypeStruct((), jnp.int32))


def update(ocfg: AdamWConfig, grads, state: OptState, params, *,
           program=None):
    """One AdamW step.  Returns (new_params, new_state).

    ``program`` (a ``train_loop.UpdateProgram``) routes the whole update
    through the plan->program executor — fused bundles via
    ``SearchResult.build()``, leftover tensors via ``run_single`` — instead
    of the hand-wired jnp / hfused-kernel paths below.
    """
    cnt = state.count + 1
    lr = schedule(ocfg, cnt)
    b1, b2 = ocfg.b1, ocfg.b2
    bc1 = 1 - b1 ** cnt.astype(jnp.float32)
    bc2 = 1 - b2 ** cnt.astype(jnp.float32)

    if program is not None:
        # b1/b2/eps/wd are baked into the program's op bodies at build time
        # (lr/bias corrections ride in the scalars operand) — a program built
        # for different hyperparameters must never silently apply them
        built = getattr(program, "hyper", None)
        want = dict(b1=ocfg.b1, b2=ocfg.b2, eps=ocfg.eps,
                    wd=ocfg.weight_decay)
        if built is not None and built != want:
            raise ValueError(
                f"update program was built for hyperparameters {built}, "
                f"but update() was called with {want} — rebuild it with "
                f"build_update_program(params, ocfg)")
        new_params, new_m, new_v = program(params, grads, state.m, state.v,
                                           lr=lr, bc1=bc1, bc2=bc2)
        return new_params, OptState(new_m, new_v, cnt)

    if ocfg.hfused:                 # compiled Pallas; interpreted on CPU
        from repro.kernels import ops as kops
        new_params, new_m, new_v = kops.hfused_adamw(
            params, grads, state.m, state.v,
            lr=lr, b1=b1, b2=b2, eps=ocfg.eps, wd=ocfg.weight_decay,
            bc1=bc1, bc2=bc2)
        return new_params, OptState(new_m, new_v, cnt)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        mh = m2 / bc1
        vh = v2 / bc2
        step = mh / (jnp.sqrt(vh) + ocfg.eps) + ocfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, OptState(new_m, new_v, cnt)
