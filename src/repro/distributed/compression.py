"""Gradient compression for slow (inter-pod) links.

``pod_compressed_grads`` wraps the whole value-and-grad computation in a
``shard_map`` that is *manual* over the ``pod`` axis and *auto* over
(data, model): each pod computes gradients for its local batch half with the
normal SPMD partitioning inside, then gradients cross the slow inter-pod ICI
as **int8 + per-tensor scale** via all_gather (1 byte/elem on the wire vs 4),
and are dequantized+averaged locally.  Error feedback (the int8 residual is
carried in optimizer-adjacent state) keeps the compression unbiased over
time [1-bit Adam / EF-SGD lineage].

Off-mesh (no 'pod' axis) or compression=None, this degrades to plain
autodiff with the implicit psum.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def quantize_int8(x: jax.Array):
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_roundtrip(g: jax.Array, residual: Optional[jax.Array] = None):
    """Quantize→dequantize with error feedback.  Returns (g_hat, new_residual)."""
    gf = g.astype(jnp.float32)
    if residual is not None:
        gf = gf + residual
    q, scale = quantize_int8(gf)
    g_hat = dequantize_int8(q, scale)
    return g_hat.astype(g.dtype), (gf - g_hat)


def compressed_allgather_mean(g: jax.Array, axis_name: str) -> jax.Array:
    """int8 all_gather + local dequant/mean across ``axis_name`` (manual axis)."""
    q, scale = quantize_int8(g)
    qs = jax.lax.all_gather(q, axis_name)            # (n, ...) int8 on the wire
    ss = jax.lax.all_gather(scale, axis_name)        # (n,) fp32 (negligible)
    n = qs.shape[0]
    deq = qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * (qs.ndim - 1))
    return jnp.mean(deq, axis=0).astype(g.dtype)


def pod_compressed_grads(loss_fn: Callable, mesh: Mesh):
    """Returns grad_fn(params, batch) -> (loss, aux, grads) where the pod-axis
    gradient reduction crosses the inter-pod links as int8.

    loss_fn(params, batch) -> (loss, aux).  The shard_map is *manual* over
    'pod' only (``axis_names={'pod'}``); (data, model) stay auto —
    SPMD-partitioned as usual inside the body."""
    if "pod" not in mesh.axis_names:
        def plain(params, batch):
            (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return l, aux, g
        return plain

    def local_grads(params, batch):
        # inside the pod-manual region the model's sharding constraints must
        # not mention 'pod': re-enter the ambient context with pod stripped.
        from repro.distributed import sharding as shd
        rules = dict(shd._CTX.rules or shd.BASE_RULES)
        for k, v in list(rules.items()):
            if isinstance(v, tuple) and "pod" in v:
                rest = tuple(a for a in v if a != "pod")
                rules[k] = rest[0] if len(rest) == 1 else (rest or None)
            elif v == "pod":
                rules[k] = None
        with shd.use_sharding(shd._CTX.mesh, rules):
            (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        # cross the slow inter-pod links compressed
        g = jax.tree.map(lambda t: compressed_allgather_mean(t, "pod"), g)
        l = jax.lax.pmean(l, "pod")
        aux = jax.tree.map(lambda t: jax.lax.pmean(t, "pod"), aux)
        return l, aux, g

    def wrapped(params, batch):
        # params replicated over pod (P()); batch dim-0 manual over pod —
        # its data-axis sharding stays auto.
        batch_specs = jax.tree.map(lambda x: P("pod"), batch)
        f = jax.shard_map(
            local_grads, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), batch_specs),
            out_specs=(P(), P(), jax.tree.map(lambda _: P(), params)),
            axis_names={"pod"}, check_vma=False)
        return f(params, batch)

    return wrapped
