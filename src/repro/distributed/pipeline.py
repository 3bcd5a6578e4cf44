"""GPipe-style pipeline parallelism over the 'pod' axis.

The multi-pod mesh's `pod` axis defaults to pure DP (one gradient reduction
per step over the slow inter-pod links).  For models whose layers do not fit
a single pod, this module instead maps *pipeline stages* onto pods:
microbatch activations flow stage→stage via `collective_permute` (one small
(B_micro, S, d) hop per tick over the inter-pod link instead of full-gradient
traffic), with the classic GPipe fill/drain bubble of (S−1)/(M+S−1).

Implementation: `jax.shard_map` manual over 'pod' only (auto over
(data, model): each stage's interior keeps its normal SPMD sharding).
Stage parameters are stacked on a leading axis sharded P('pod') — each pod
holds exactly its stage's weights.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def gpipe(stage_fn: Callable, n_stages: int, axis: str = "pod"):
    """Build a pipelined forward: (stage_params_local, xs) -> ys.

    stage_fn(params, x) -> y, same signature for every stage (homogeneous
    stages — layer runs are grouped upstream).  Used inside a shard_map that
    is manual over `axis`; xs: (M, ...) microbatches (replicated over
    `axis`); returns (M, ...) outputs valid on the LAST stage (other stages
    return the in-flight garbage — callers read stage n_stages-1 or
    ppermute the result back).
    """
    def pipelined(params_local, xs):
        M = xs.shape[0]
        stage = jax.lax.axis_index(axis)
        n_ticks = M + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry                      # buf: activation entering
            mb_idx = jnp.clip(t, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                    keepdims=False)
            inp = jnp.where(stage == 0, first_in, buf)
            out = stage_fn(params_local, inp)
            # collect on the last stage once the pipe is full
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            collect = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, out_idx, 0),
                lambda o: o, outs)
            nxt = jax.lax.ppermute(out, axis, perm)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(stage_fn(params_local,
                                       jax.tree.map(lambda a: a[0], xs)))
        outs0 = jnp.zeros((M,) + buf0.shape, buf0.dtype)
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(n_ticks))
        return outs

    return pipelined


def pipeline_over_pods(stage_fn: Callable, mesh: Mesh, n_stages: int):
    """shard_map wrapper: stage params stacked on dim0 (P('pod')), inputs
    microbatched on dim0 (replicated over pod), outputs broadcast from the
    last stage back to all pods."""
    inner = gpipe(stage_fn, n_stages)

    def run(stage_params_stacked, xs):
        def body(params_stk, xs_local):
            params_local = jax.tree.map(lambda a: a[0], params_stk)
            ys = inner(params_local, xs_local)
            # broadcast final outputs from the last stage to every pod
            # (masked psum: ppermute cannot fan out one source to many)
            stage = jax.lax.axis_index("pod")
            ys = jnp.where(stage == n_stages - 1, ys, jnp.zeros_like(ys))
            return jax.lax.psum(ys, "pod")

        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pod"), stage_params_stacked),
                      P()),
            out_specs=P(),
            axis_names={"pod"}, check_vma=False)
        return f(stage_params_stacked, xs)

    return run
