"""Train step assembly: autodiff, microbatched gradient accumulation,
optional int8 pod-axis gradient compression, AdamW update, metrics.

The returned ``train_step`` is pure — (params, opt_state, batch, step) ->
(params, opt_state, metrics) — and is jitted/lowered by the caller with
explicit shardings (see launch/dryrun.py, launch/train.py).

Distributed-optimization notes (DESIGN.md §7):
  * grad accumulation is a ``lax.scan`` over microbatches — XLA's
    latency-hiding scheduler overlaps microbatch i's gradient all-reduce
    with microbatch i+1's backward compute;
  * with ``compression='int8_pod'`` the inter-pod reduction goes through
    repro.distributed.compression (int8 on the slow links);
  * ``zero=True`` shards optimizer moments over the data axis (ZeRO-1):
    XLA turns the gradient all-reduce into reduce-scatter + the param
    update all-gather.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.train import optimizer as opt_mod
from repro.train.optimizer import AdamWConfig, OptState


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    grad_accum: int = 1
    remat: bool = True
    compression: Optional[str] = None       # None | 'int8_pod'
    zero: bool = False                      # ZeRO-1 optimizer-state sharding
    max_grad_norm: float = 1.0


def leaf_update_name(path) -> str:
    """Stable graph-op name stem for one param leaf — the ONE place leaf
    paths become op names (plan, bindings, and state keys all share it)."""
    return "".join(c if c.isalnum() else "_"
                   for c in jax.tree_util.keystr(path)).strip("_")


def _leaf_rows(leaf, bm: int):
    """(n, R, bm_i): flat element count, padded (R, 128) rows, block rows —
    the layout contract shared by kernels.adam._flatten_leaf and the
    adamw OpSpec grid."""
    import math

    from repro.kernels.adam import LANES

    n = math.prod(leaf.shape) if leaf.shape else 1
    rows = math.ceil(n / LANES)
    bm_i = min(bm, rows)
    R = math.ceil(rows / bm_i) * bm_i
    return n, R, bm_i


def update_graph(params, *, tokens: int = 4096, bm: int = 1024,
                 max_tensors: Optional[int] = 8, include_dW: bool = True,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 wd: float = 0.1):
    """The optimizer-step op graph: one AdamW-update OpSpec per param leaf
    (stable operand signature: scalars/p/g/m/v -> p/m/v) and, with
    ``include_dW``, the backward dW matmul ``x^T @ g`` each 2-D parameter's
    update *depends on* (an update can never fuse *horizontally* with the
    matmul producing its gradient, but rides another tensor's).  When the
    dW output's row-major layout lines up exactly with the update's padded
    (R, 128) gradient view, the dW op declares the update as its *epilogue*
    (core/stitch.py) — the planner contracts the pair into one
    ``dW_w→adamw_w`` member whose gradient never round-trips HBM, and that
    chain still fuses horizontally with other tensors' updates.

    Returns ``(graph, layout)``: the planner graph plus the per-leaf layout
    ``[(name, path, n, R, bm_i), ...]`` the executor's pack/unpack uses —
    names are derived once here, not re-derived ad hoc by callers.
    """
    import math

    from repro.core import planner
    from repro.kernels.adam import LANES, adamw_op
    from repro.kernels.matmul import matmul_1d_op

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    if max_tensors is not None:
        flat = sorted(flat, key=lambda kv: -math.prod(kv[1].shape or (1,)))
        flat = flat[:max_tensors]
    graph: list[planner.GraphOp] = []
    layout: list[tuple] = []
    for path, leaf in flat:
        pname = leaf_update_name(path)
        n, R, bm_i = _leaf_rows(leaf, bm)
        deps: frozenset[str] = frozenset()
        if include_dW and leaf.ndim == 2:
            d_in, d_out = leaf.shape
            bmm = min(256, d_in)
            if d_in % bmm == 0:
                dw = matmul_1d_op(M=d_in, K=tokens, N=d_out, dtype=leaf.dtype,
                                  bm=bmm)
                dw = dataclasses.replace(dw, name=f"dW_{pname}",
                                         tag="train:dW")
                if n % LANES == 0 and (bmm * d_out) % LANES == 0:
                    # exact row-major correspondence: (d_in, d_out) flattens
                    # to (n/128, 128) with no padding, and matching the
                    # update's block rows to dW's block (bmm rows of d_out)
                    # makes the two grids identical — can_stitch's
                    # row-stream case, so dW can hand the update its
                    # gradient block in-register
                    bm_i = bmm * d_out // LANES
                    R = n // LANES
                    dw = dataclasses.replace(
                        dw, epilogue=(f"adamw_{pname}", "g"))
                graph.append(planner.GraphOp(dw))
                deps = frozenset({dw.name})
        upd = adamw_op(R=R, dtype=leaf.dtype, bm=bm_i, name=f"adamw_{pname}",
                       b1=b1, b2=b2, eps=eps, wd=wd)
        graph.append(planner.GraphOp(upd, deps=deps))
        layout.append((f"adamw_{pname}", path, n, R, bm_i))
    return graph, layout


def plan_update_fusion(params, *, tokens: int = 4096, max_ways: int = 3,
                       bm: int = 1024, max_tensors: int = 8,
                       measure=None, cache=None):
    """Hand the optimizer's per-tensor update OpSpecs plus the backward dW
    matmuls to ``planner.plan(max_ways>=3)`` — optimizer/backward overlap is
    *planned*, not hand-wired (ROADMAP; docs/nway_fusion.md).

    ``measure``/``cache`` flow through to the autotuner, so schedules are
    profiled once (core/timing) and reused forever (core/schedule_cache).
    Largest ``max_tensors`` parameters only — the tail adds launches the
    multi-tensor Adam path already amortizes.
    """
    from repro.core import planner

    graph, _ = update_graph(params, tokens=tokens, bm=bm,
                            max_tensors=max_tensors, include_dW=True)
    return planner.plan(graph, max_ways=max_ways, measure=measure,
                        cache=cache)


class UpdateProgram:
    """The executed optimizer step: a ``FusionPlan`` over every param
    leaf's AdamW op, lowered by ``core/executor`` — fused bundles run via
    ``SearchResult.build()``, leftovers via ``run_single`` — with the
    binding registry routing each op's operands to the flattened (R, 128)
    views of its param/grad/moment leaves.  This is the planner-driven
    generalization of ``kernels.adam.multi_tensor_adamw`` (the parity
    baseline in tests/test_executor.py)."""

    def __init__(self, plan, program, layout, hyper: dict):
        self.plan = plan
        self.program = program
        self.layout = layout
        self.hyper = hyper

    def __call__(self, params, grads, m, v, *, lr, bc1, bc2):
        from repro.kernels.adam import LANES, _flatten_leaf, _unflatten_leaf

        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        leaves_g = treedef.flatten_up_to(grads)
        leaves_m = treedef.flatten_up_to(m)
        leaves_v = treedef.flatten_up_to(v)
        scalars = jnp.zeros((1, LANES), jnp.float32)
        scalars = scalars.at[0, 0].set(lr).at[0, 1].set(bc1).at[0, 2].set(bc2)

        state = {"scalars": scalars}
        for (name, _path, n, R, bm_i), lp, lg, lm, lv in zip(
                self.layout, leaves_p, leaves_g, leaves_m, leaves_v):
            state[f"{name}.p"], _ = _flatten_leaf(lp, row_multiple=bm_i)
            state[f"{name}.g"], _ = _flatten_leaf(lg.astype(lp.dtype),
                                                  row_multiple=bm_i)
            state[f"{name}.m"], _ = _flatten_leaf(lm.astype(jnp.float32),
                                                  row_multiple=bm_i)
            state[f"{name}.v"], _ = _flatten_leaf(lv.astype(jnp.float32),
                                                  row_multiple=bm_i)
        state = self.program(state)
        new_p, new_m, new_v = [], [], []
        for (name, _path, n, _R, _bm_i), lp, lm, lv in zip(
                self.layout, leaves_p, leaves_m, leaves_v):
            new_p.append(_unflatten_leaf(state[f"{name}.p"], n, lp))
            new_m.append(_unflatten_leaf(state[f"{name}.m"], n, lm))
            new_v.append(_unflatten_leaf(state[f"{name}.v"], n, lv))
        return (treedef.unflatten(new_p), treedef.unflatten(new_m),
                treedef.unflatten(new_v))

    def describe(self) -> list[dict]:
        return self.program.describe()


def build_update_program(params, ocfg: Optional[AdamWConfig] = None, *,
                         bm: int = 1024, max_ways: int = 4,
                         measure=None, cache=None,
                         interpret: Optional[bool] = None) -> UpdateProgram:
    """Plan + compile the executed optimizer step for ``params`` (live or
    abstract).  All leaves participate — the executed step must update the
    whole tree.  The dW matmuls are *planning-only* (their operands — the
    backward's activations — are autodiff internals the update step never
    sees live), so the executable graph holds the per-tensor update ops;
    they fuse with each other (``allow_same_bound``: all memory-bound, the
    gain is launch+ramp amortization — multi-tensor-apply rediscovered by
    the planner).
    """
    from repro.core import executor, planner
    from repro.core.binding import BindingRegistry

    ocfg = ocfg or AdamWConfig()
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    graph, layout = update_graph(
        params, bm=bm, max_tensors=None, include_dW=False,
        b1=ocfg.b1, b2=ocfg.b2, eps=ocfg.eps, wd=ocfg.weight_decay)
    plan = planner.plan(graph, max_ways=max_ways, allow_same_bound=True,
                        measure=measure, cache=cache)
    reg = BindingRegistry()
    for name, *_ in layout:
        reg.bind(name, scalars="scalars", p=f"{name}.p", g=f"{name}.g",
                 m=f"{name}.m", v=f"{name}.v")
    program = executor.compile_plan(plan, bindings=reg, interpret=interpret)
    return UpdateProgram(plan, program, layout,
                         hyper=dict(b1=ocfg.b1, b2=ocfg.b2, eps=ocfg.eps,
                                    wd=ocfg.weight_decay))


def _split_microbatches(batch: dict, n: int) -> dict:
    def r(x):
        return x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return jax.tree.map(r, batch)


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda l: (l.astype(jnp.float32) * scale).astype(l.dtype),
                        tree), norm


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh=None,
                    update_program: Optional[UpdateProgram] = None) -> Callable:
    """``update_program`` (train_loop.build_update_program) routes the
    optimizer step through the plan->program executor instead of the
    hand-wired update paths — the `--plan-fusion` hot path."""
    loss_fn = functools.partial(lm.loss_fn, cfg, remat=tcfg.remat)

    def loss_wrap(params, batch):
        return loss_fn(params, batch)

    if tcfg.compression == "int8_pod" and mesh is not None:
        from repro.distributed.compression import pod_compressed_grads
        grad_fn = pod_compressed_grads(lambda p, b: loss_wrap(p, b), mesh)
    else:
        def grad_fn(params, batch):
            (l, aux), g = jax.value_and_grad(loss_wrap, has_aux=True)(params, batch)
            return l, aux, g

    def compute_grads(params, batch):
        if tcfg.grad_accum <= 1:
            return grad_fn(params, batch)
        micro = _split_microbatches(batch, tcfg.grad_accum)

        def body(carry, mb):
            acc, lsum = carry
            l, aux, g = grad_fn(params, mb)
            acc = jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32), acc, g)
            return (acc, lsum + l), aux

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (acc, lsum), auxs = jax.lax.scan(body, (acc0, 0.0), micro)
        g = jax.tree.map(lambda a: a / tcfg.grad_accum, acc)
        aux = jax.tree.map(lambda a: a[-1], auxs)
        return lsum / tcfg.grad_accum, aux, g

    def train_step(params, opt_state: OptState, batch, step):
        loss, aux, grads = compute_grads(params, batch)
        grads, gnorm = clip_by_global_norm(grads, tcfg.max_grad_norm)
        new_params, new_opt = opt_mod.update(tcfg.optimizer, grads, opt_state,
                                             params, program=update_program)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "lr": opt_mod.schedule(tcfg.optimizer, opt_state.count + 1)}
        if isinstance(aux, dict):
            metrics.update({k: v for k, v in aux.items()})
        return new_params, new_opt, metrics

    return train_step
