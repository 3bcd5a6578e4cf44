"""Measurement harness — the profiler inside the paper's Main() loop (Fig. 6).

The paper picks schedules by *running* each candidate and keeping the
fastest; our autotuner accepts that as ``search(..., measure=)`` but until
now nothing ever provided the callable.  ``make_measure(backend=...)``
builds it:

  tpu / gpu   — wall clock: synthesize operands from the OpSpecs, one
                compile+warmup pass, then ``repeats`` timed runs with
                ``jax.block_until_ready`` and a trimmed mean (drop the
                ``trim`` fastest/slowest — interrupt noise).
  interpret   — deterministic step-count proxy so CI exercises the
                *identical* measured-search code path on CPU: the score is
                the fused grid length x the bundle's mean per-step roofline
                work.  Schedules that waste fused steps (phase windows past
                a member's grid) genuinely score worse, so the proxy ranks
                schedules, it doesn't just rubber-stamp the cost model.
                ``execute=True`` additionally runs each candidate kernel in
                interpret mode on tiny synthesized inputs (numerics-path
                exercise; only sane for reduced-size ops).

The returned callable has the ``measure(fused, *ops) -> seconds`` contract
``autotuner.search`` expects, where ``fused`` is a ``hfuse.generate`` (or
``run_native``) callable and ``ops`` are the bundle members.
"""
from __future__ import annotations

import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.cost_model import LAUNCH_S
from repro.core.op_spec import OpSpec


def resolve_backend(backend: str = "auto") -> str:
    """'auto' -> the JAX default backend; only the CPU (the test backend)
    maps to the 'interpret' proxy."""
    if backend != "auto":
        return backend
    be = jax.default_backend()
    return "interpret" if be == "cpu" else be


def synth_inputs(ops: Sequence[OpSpec], seed: int = 0) -> list[jax.Array]:
    """Synthesize one flat operand list for a bundle from its OpSpecs.

    Floats get small-magnitude normals (saturating bodies like tanh rounds
    stay in-range); everything else gets zeros.  Timing only — numerics are
    the tests' job.
    """
    key = jax.random.PRNGKey(seed)
    arrs: list[jax.Array] = []
    for op in ops:
        for o in op.inputs:
            key, sub = jax.random.split(key)
            if jnp.issubdtype(jnp.dtype(o.dtype), jnp.floating):
                arrs.append(jax.random.normal(sub, o.shape).astype(o.dtype)
                            * 0.1)
            else:
                arrs.append(jnp.zeros(o.shape, o.dtype))
    return arrs


def step_time_proxy(fused, ops: Sequence[OpSpec]) -> float:
    """Deterministic interpret-mode score: fused-grid length x mean step work.

    ``fused.n_steps`` (set by hfuse.generate) is the realized fused grid:
    ``period * max_i ceil(grid_i / r_i)``.  A schedule that keeps every
    member busy end-to-end has n_steps ~= sum(grid_i); imbalanced ratios
    leave idle phase slots and n_steps grows — the proxy charges for them.
    Callables without ``n_steps`` (e.g. ``run_native``) are charged the
    exact per-op work plus one launch per op.
    """
    total_work = sum(op.t_compute + op.t_memory for op in ops)
    total_steps = sum(op.grid for op in ops)
    n_steps = getattr(fused, "n_steps", None)
    if n_steps is None:                     # native baseline: N launches
        return total_work + len(ops) * LAUNCH_S
    return n_steps * (total_work / max(total_steps, 1)) + LAUNCH_S


def make_measure(backend: str = "auto", *, warmup: int = 2, repeats: int = 5,
                 trim: int = 1, execute: bool = False,
                 seed: int = 0) -> Callable:
    """Build the ``measure(fused, *ops) -> seconds`` callable for
    ``autotuner.search(measure=)`` / ``planner.plan(measure=)``."""
    backend = resolve_backend(backend)

    if backend == "interpret":
        def measure(fused, *ops):
            if execute and hasattr(fused, "schedule"):
                from repro.core import hfuse
                interp = hfuse.generate(ops, fused.schedule, interpret=True)
                jax.block_until_ready(interp(*synth_inputs(ops, seed)))
            return step_time_proxy(fused, ops)
        measure.backend = "interpret"
        # the proxy RANKS schedules; its native-vs-fused difference is only
        # launch amortization, so absolute gains are meaningless — consumers
        # (planner admission) must fall back to predicted gain
        measure.rank_only = True
        return measure

    def measure(fused, *ops):
        args = synth_inputs(ops, seed)
        for _ in range(max(1, warmup)):       # compile + cache warm
            jax.block_until_ready(fused(*args))
        ts = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(fused(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        k = trim if len(ts) > 2 * trim else 0
        kept = ts[k:len(ts) - k] if k else ts
        return sum(kept) / len(kept)

    measure.backend = backend
    return measure
