"""Tiled matmul Pallas kernel (MXU-aligned BlockSpecs, fp32 VMEM accumulator).

Two forms:
  * ``matmul``      — 3-D grid (m, n, k) with K-streaming and a VMEM
                      accumulator; the standalone high-performance form.
  * ``matmul_1d_op``— fusible OpSpec (1-D grid over M row-blocks x N
                      column tiles): the compute-bound partner the
                      horizontal-fusion planner pairs with memory-bound ops
                      (decode attention, optimizer updates, norms).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.op_spec import OpSpec, Operand


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x: jax.Array, w: jax.Array, *, bm: int = 512, bn: int = 512,
           bk: int = 512, interpret: bool = False) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N), tiled (bm, bn, bk)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
                  pl.BlockSpec((bk, bn), lambda m, n, k: (k, n))],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


TILE_BYTES = 4 * 2 ** 20


def weight_tile(K: int, N: int, dtype, *, views: int = 1) -> int:
    """Widest column tile of a (K, N) weight whose ``views`` blocks of
    (K, tile) fit ``TILE_BYTES`` together: N itself when the whole weight
    fits, else the widest 128-multiple dividing N that fits, else the
    narrowest tile N allows.  Keeps one step's weight blocks a few MiB so a
    fused bundle's members co-reside in VMEM double-buffered."""
    item = jnp.dtype(dtype).itemsize
    tiles = [bn for bn in range(128, N, 128) if N % bn == 0] + [N]
    fits = [bn for bn in tiles if views * K * bn * item <= TILE_BYTES]
    return max(fits) if fits else tiles[0]


def matmul_1d_op(M: int, K: int, N: int, dtype=jnp.bfloat16,
                 bm: int = 256, bn: int | None = None,
                 gated: bool = False) -> OpSpec:
    """Fusible form: grid over (M row-blocks x N column tiles), row-major;
    each step holds one (bm, K) row block and a (K, bn) weight tile.

    ``gated=True`` is the gate|up FFN in-projection: the (K, N) weight is
    ``[gate | up]`` with F = N / 2 columns each, and tile j multiplies the
    gate and up columns ``[j*bn, (j+1)*bn)`` together (the weight is bound
    twice, as "w" and "w_up").  Output tile j is ``[gate_j | up_j]``, so the
    (M, N) output is tile-interleaved — exactly what a column-tiled gated
    activation (elementwise.activation_op, same ``bn``) consumes.  With one
    tile the layout is the plain ``[gate | up]``."""
    assert M % bm == 0
    F = N // 2 if gated else N
    bn = bn or F
    assert F % bn == 0 and (not gated or N % 2 == 0), (M, K, N, bn)
    nt = F // bn
    ob = 2 * bn if gated else bn

    def body(step, x_ref, *refs):
        o_ref = refs[-1]
        x = x_ref[...]
        tiles = [jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
                 for w_ref in refs[:-1]]
        o = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=-1)
        o_ref[...] = o.astype(o_ref.dtype)

    w_views = (Operand((K, N), dtype, (K, bn), lambda s: (0, s % nt)),)
    if gated:
        w_views += (Operand((K, N), dtype, (K, bn),
                            lambda s: (0, s % nt + nt)),)
    itemsize = jnp.dtype(dtype).itemsize
    return OpSpec(
        name=f"matmul_{M}x{K}x{N}", grid=(M // bm) * nt, body=body,
        inputs=(Operand((M, K), dtype, (bm, K), lambda s: (s // nt, 0)),)
        + w_views,
        outputs=(Operand((M, N), dtype, (bm, ob),
                         lambda s: (s // nt, s % nt)),),
        flops=2.0 * M * K * N,
        hbm_bytes=(M * K + K * N + M * N) * itemsize,
        tag="framework:matmul",
        in_names=("x", "w", "w_up")[:1 + len(w_views)], out_names=("out",))
