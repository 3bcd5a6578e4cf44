"""jit'd public wrappers for every kernel: compiled Pallas on an
accelerator, interpret-mode Pallas on the CPU (the test backend).

`_mode()` decides per platform; `force` overrides for tests ("ref" selects
the jnp oracle).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import adam as adam_k
from repro.kernels import decode_attention as dec_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import matmul as mm_k
from repro.kernels import moe_gmm as gmm_k
from repro.kernels import ref
from repro.kernels import rmsnorm as rn_k

_FORCE: Optional[str] = None      # None | "pallas" | "interpret" | "ref"


def force(mode: Optional[str]):
    global _FORCE
    _FORCE = mode


def _mode() -> str:
    if _FORCE:
        return _FORCE
    return "interpret" if jax.default_backend() == "cpu" else "pallas"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul(x, w, bm=512, bn=512, bk=512):
    m = _mode()
    if m == "ref":
        return ref.matmul(x, w)
    return mm_k.matmul(x, w, bm=bm, bn=bn, bk=bk, interpret=(m == "interpret"))


@jax.jit
def rmsnorm(x, scale):
    m = _mode()
    if m == "ref":
        return ref.rmsnorm(x, scale)
    return rn_k.rmsnorm(x, scale, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q, k, v, causal=True):
    """q,k,v: (B,S,H,D) — GQA handled by repeating KV heads to H."""
    m = _mode()
    if m == "ref":
        B, S, H, D = q.shape
        rep = H // k.shape[2]
        kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
        vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
        return ref.flash_attention(q, kr, vr, causal=causal)
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = kr.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = vr.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    o = fa_k.flash_attention(qt, kt, vt, causal=causal,
                             interpret=(m == "interpret"))
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("act", "bc"))
def moe_gmm(xe, w_in, w_out, act="silu", bc=128):
    m = _mode()
    if m == "ref":
        return ref.moe_gmm(xe, w_in, w_out, act=act)
    return gmm_k.moe_gmm(xe, w_in, w_out, act=act, bc=bc,
                         interpret=(m == "interpret"))


def hfused_adamw(params, grads, m, v, *, lr, b1, b2, eps, wd, bc1, bc2):
    """All per-tensor updates as ONE Pallas launch (paper §4.3 form).

    Pallas/interpret modes run the N-way multi-tensor bundle (one OpSpec
    per tensor, horizontally fused by core/hfuse); ref mode applies the
    oracle update leaf-wise.
    """
    mode = _mode()
    if mode == "ref":
        lp, treedef = jax.tree.flatten(params)
        outs = [ref.adamw(p, g, mm.astype(jnp.float32),
                          vv.astype(jnp.float32), lr=lr, b1=b1, b2=b2,
                          eps=eps, wd=wd, bc1=bc1, bc2=bc2)
                for p, g, mm, vv in zip(lp, treedef.flatten_up_to(grads),
                                        treedef.flatten_up_to(m),
                                        treedef.flatten_up_to(v))]
        return tuple(jax.tree.unflatten(treedef, [o[k] for o in outs])
                     for k in range(3))
    scal = jnp.zeros((1, adam_k.LANES), jnp.float32)
    scal = scal.at[0, 0].set(lr).at[0, 1].set(bc1).at[0, 2].set(bc2)
    return adam_k.multi_tensor_adamw(params, grads, m, v, scal,
                                     b1=b1, b2=b2, eps=eps, wd=wd,
                                     interpret=(mode == "interpret"))
