"""RMSNorm Pallas kernel — memory-bound row normalization (one HBM round
trip), standalone and as a fusible OpSpec.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.op_spec import OpSpec, Operand


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * (1.0 + s_ref[...])).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            bm: int = 256, interpret: bool = False) -> jax.Array:
    """x: (R, d); scale: (d,) fp32."""
    R, d = x.shape
    bm = min(bm, R)
    assert R % bm == 0
    import functools
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(R // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda s: (s, 0)),
                  pl.BlockSpec((1, d), lambda s: (0, 0))],
        out_specs=pl.BlockSpec((bm, d), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        interpret=interpret,
    )(x, scale.reshape(1, d))


def rmsnorm_op(R: int, d: int, dtype=jnp.bfloat16, bm: int = 256,
               eps: float = 1e-6, repeat: int = 1) -> OpSpec:
    """Fusible form.  ``repeat`` re-emits each row block on that many
    consecutive steps (the block index holds, so nothing is re-fetched):
    the grid then matches a column-tiled consumer's, which is what lets
    norm -> matmul stitch when the matmul tiles its weight."""
    assert R % bm == 0

    def body(step, x_ref, s_ref, o_ref):
        _rmsnorm_kernel(x_ref, s_ref, o_ref, eps=eps)

    itemsize = jnp.dtype(dtype).itemsize
    return OpSpec(
        name=f"rmsnorm_{R}x{d}", grid=(R // bm) * repeat, body=body,
        inputs=(Operand((R, d), dtype, (bm, d), lambda s: (s // repeat, 0)),
                Operand((1, d), jnp.float32, (1, d), lambda s: (0, 0))),
        outputs=(Operand((R, d), dtype, (bm, d),
                         lambda s: (s // repeat, 0)),),
        flops=4.0 * R * d,
        hbm_bytes=2.0 * R * d * itemsize,
        tag="framework:rmsnorm",
        in_names=("x", "scale"), out_names=("out",))
