"""Row-streamed elementwise Pallas kernels — the epilogue consumers.

These are the tiny memory-bound ops a matmul's output classically flows
into (activation, residual add).  Standalone they are pure HBM round-trips;
their whole point is to be *stitched* onto their producer via
``core/stitch.py`` so the intermediate never leaves registers.  Block
layout mirrors ``matmul_1d_op``'s output ((bm, F) row blocks, map
s -> (s, 0)) so ``can_stitch``'s identical-block case applies directly.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.op_spec import OpSpec, Operand


def activation_op(R: int, F_in: int, F_out: int, fn: Callable,
                  dtype=jnp.bfloat16, bm: int = 256,
                  name: str | None = None, bn: int | None = None) -> OpSpec:
    """out = fn(h) row-wise; h: (R, F_in) -> out: (R, F_out).

    ``fn`` maps a (bm, F_in) block to (bm, F_out) — gated activations
    (silu/gelu-and-multiply) halve F, plain ones keep it.  It must be
    shape-polymorphic in the row dim so the block-shrink variants stay
    valid.  ``bn`` tiles the output columns (grid over rows x tiles, like
    kernels/matmul.matmul_1d_op): step j reads input tile j of width
    ``bn * F_in / F_out`` — for a gated ``fn`` that is the tile-interleaved
    ``[gate_j | up_j]`` a gated column-tiled matmul writes.
    """
    bm = min(bm, R)
    assert R % bm == 0
    bn = bn or F_out
    assert F_out % bn == 0 and (F_in * bn) % F_out == 0
    nt = F_out // bn
    blk = lambda s: (s // nt, s % nt)

    def body(step, h_ref, o_ref):
        o_ref[...] = fn(h_ref[...]).astype(o_ref.dtype)

    itemsize = jnp.dtype(dtype).itemsize
    return OpSpec(
        name=name or f"act_{R}x{F_in}", grid=(R // bm) * nt, body=body,
        inputs=(Operand((R, F_in), dtype, (bm, F_in // nt), blk),),
        outputs=(Operand((R, F_out), dtype, (bm, bn), blk),),
        flops=8.0 * R * F_in,
        hbm_bytes=float(R * (F_in + F_out)) * itemsize,
        tag="framework:activation",
        in_names=("h",), out_names=("out",))


def silu_gate(h: jax.Array) -> jax.Array:
    """SwiGLU epilogue: h = [a | b] (gated halves) -> silu(a) * b."""
    f = h.shape[-1] // 2
    a, b = h[..., :f], h[..., f:]
    af = a.astype(jnp.float32)
    return (af * jax.nn.sigmoid(af)) * b.astype(jnp.float32)


def gelu_gate(h: jax.Array) -> jax.Array:
    f = h.shape[-1] // 2
    a, b = h[..., :f], h[..., f:]
    return jax.nn.gelu(a.astype(jnp.float32)) * b.astype(jnp.float32)


def gelu_plain(h: jax.Array) -> jax.Array:
    return jax.nn.gelu(h.astype(jnp.float32))


def relu2(h: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(h.astype(jnp.float32)))


def residual_add_op(R: int, F: int, dtype=jnp.bfloat16, bm: int = 256,
                    name: str | None = None) -> OpSpec:
    """out = h + res row-wise — the matmul→residual-add epilogue."""
    bm = min(bm, R)
    assert R % bm == 0
    blk = lambda s: (s, 0)

    def body(step, h_ref, r_ref, o_ref):
        o_ref[...] = (h_ref[...].astype(jnp.float32)
                      + r_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    itemsize = jnp.dtype(dtype).itemsize
    return OpSpec(
        name=name or f"resadd_{R}x{F}", grid=R // bm, body=body,
        inputs=(Operand((R, F), dtype, (bm, F), blk),
                Operand((R, F), dtype, (bm, F), blk)),
        outputs=(Operand((R, F), dtype, (bm, F), blk),),
        flops=1.0 * R * F,
        hbm_bytes=3.0 * R * F * itemsize,
        tag="framework:residual_add",
        in_names=("h", "res"), out_names=("out",))
