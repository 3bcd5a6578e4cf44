"""Vertical (epilogue) stitching — a producer→consumer chain as ONE OpSpec.

The paper fuses *independent* kernels horizontally; FusionStitching and the
BLAS kernel-fusion line (PAPERS.md) show the orthogonal win: a producer
whose output feeds exactly one consumer elementwise/row-wise (rmsnorm→matmul,
matmul→residual-add, matmul→activation, dW-matmul→adamw) can run as one
kernel with the intermediate living in registers/VMEM instead of
round-tripping HBM.  Both compose: a stitched chain is just an OpSpec, so it
becomes one *member* of a horizontal bundle — one ratio coordinate for the
autotuner, one node for the planner, one set of external operands for the
executor.

Mechanics.  Every kernel body in this repo follows the single-assignment
block contract (``o_ref[...] = value``; stitched inputs are read as
``ref[...]``), so composition needs no codegen: the chain body runs the
producer with a stub output ref that *captures* the block value, then runs
the consumer with a stub input ref that *returns* it.  The producer's HBM
write and the consumer's HBM read of the intermediate both vanish from the
chain's ``hbm_bytes``; the live block is charged to ``extra_vmem_bytes`` so
the cost model's VMEM cliff still sees it.

Safety is ``can_stitch``: per-step block correspondence (identical blocks,
or the row-major reshape case dW→adamw needs), equal grids, matching dtypes,
collision-free merged operand names.  Graph-level legality (single reader,
contraction stays acyclic) is the planner's job — see
``planner._contract_chains``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax.numpy as jnp

from repro.core.op_spec import Operand, OpSpec, shrink_blocks

CHAIN_SEP = "→"                       # "→" — also how plans render chains


def chain_label(*names: str) -> str:
    return CHAIN_SEP.join(names)


# ---------------------------------------------------------------------------
# Stub refs — the register-resident intermediate
# ---------------------------------------------------------------------------
class _CaptureRef:
    """Output stub handed to the producer body: ``o_ref[...] = v`` lands the
    block value here instead of a VMEM window.  Exposes ``shape``/``dtype``
    (bodies do ``.astype(o_ref.dtype)`` for their final rounding — capturing
    *after* that cast is what makes the chain bit-identical to the
    unstitched pair)."""

    __slots__ = ("shape", "dtype", "value")

    def __init__(self, block_shape, dtype):
        self.shape = tuple(block_shape)
        self.dtype = jnp.dtype(dtype)
        self.value = None

    def __setitem__(self, idx, v):
        if idx is not Ellipsis:
            raise NotImplementedError(
                "stitched producer must write its whole block (o_ref[...])")
        self.value = v


class _ValueRef:
    """Input stub handed to the consumer body for the stitched operand:
    ``ref[...]`` returns the captured block value."""

    __slots__ = ("shape", "dtype", "value")

    def __init__(self, value):
        self.value = value
        self.shape = tuple(value.shape)
        self.dtype = value.dtype

    def __getitem__(self, idx):
        if idx is not Ellipsis:
            raise NotImplementedError(
                "stitched consumer must read its whole block (ref[...])")
        return self.value


# ---------------------------------------------------------------------------
# The stitchability contract
# ---------------------------------------------------------------------------
_PROBE_FAILED = object()


def _probe(operand: Operand, grid: int):
    """Index-map values at sample steps (incl. late steps — see
    op_spec._index_pattern for why grid-aware probes matter)."""
    steps = sorted({0, 1, 2, grid // 2, max(grid - 1, 0)})
    try:
        return {s: tuple(int(c) for c in operand.index_map(s))
                for s in steps}
    except Exception:
        return _PROBE_FAILED


def _row_stream(operand: Operand, grid: int) -> bool:
    """Pure row-stream: block covers every trailing dim and the map is
    s -> (s, 0, ..., 0) — step s holds rows [s*b0, (s+1)*b0), contiguous in
    row-major order.  Two such operands with equal per-block element counts
    see the *same elements* at every step, which is what licenses the
    flatten/reshape correspondence (dW (bm, N) blocks → adamw (bm*N/128,
    128) blocks)."""
    if operand.block_shape[1:] != operand.shape[1:]:
        return False
    probes = _probe(operand, grid)
    if probes is _PROBE_FAILED:
        return False
    return all(p == (s,) + (0,) * (len(operand.block_shape) - 1)
               for s, p in probes.items())


def _blocks_identical(a: Operand, b: Operand, grid: int) -> bool:
    if a.shape != b.shape or a.block_shape != b.block_shape:
        return False
    pa, pb = _probe(a, grid), _probe(b, grid)
    return pa is not _PROBE_FAILED and pa == pb


def can_stitch(producer: OpSpec, consumer: OpSpec,
               operand: str) -> Optional[str]:
    """None iff ``producer``'s output can feed ``consumer.<operand>``
    in-register; otherwise the reason it can't.  Checks the *kernel-level*
    contract only — the graph-level single-reader/acyclicity checks live in
    the planner."""
    if not (producer.has_signature and consumer.has_signature):
        return "both ops need operand signatures"
    if producer.chain or consumer.chain:
        return "chains do not cascade (one stitch level)"
    if len(producer.outputs) != 1:
        return f"producer has {len(producer.outputs)} outputs, need 1"
    if producer.out_names[0] in producer.in_names:
        return "producer output is in-place (cannot be eliminated)"
    if operand not in consumer.in_names:
        return f"consumer has no input named {operand!r}"
    if operand in consumer.out_names:
        return f"stitched operand {operand!r} is consumer in-place state"
    if producer.grid != consumer.grid:
        return f"grid mismatch: {producer.grid} vs {consumer.grid}"

    sidx = consumer.in_names.index(operand)
    pout, cin = producer.outputs[0], consumer.inputs[sidx]
    if jnp.dtype(pout.dtype) != jnp.dtype(cin.dtype):
        return f"dtype mismatch: {pout.dtype} vs {cin.dtype}"
    if math.prod(pout.shape) != math.prod(cin.shape):
        return f"element count mismatch: {pout.shape} vs {cin.shape}"
    if not (_blocks_identical(pout, cin, producer.grid)
            or (_row_stream(pout, producer.grid)
                and _row_stream(cin, consumer.grid)
                and math.prod(pout.block_shape)
                == math.prod(cin.block_shape))):
        return ("per-step block mismatch: "
                f"{pout.block_shape}@{pout.shape} vs "
                f"{cin.block_shape}@{cin.shape}")

    merged_in = producer.in_names + tuple(n for n in consumer.in_names
                                          if n != operand)
    if len(set(merged_in)) != len(merged_in):
        return f"operand name collision in merged signature: {merged_in}"
    return None


# ---------------------------------------------------------------------------
# Building the chain OpSpec
# ---------------------------------------------------------------------------
def _array_bytes(o: Operand) -> float:
    return float(math.prod(o.shape)) * jnp.dtype(o.dtype).itemsize


def stitch(producer: OpSpec, consumer: OpSpec, operand: str) -> OpSpec:
    """Contract producer→consumer into one OpSpec (``can_stitch`` must
    pass).  External operands only: the chain's inputs are the producer's
    plus the consumer's minus the stitched one; its outputs are the
    consumer's.  ``hbm_bytes`` drops the intermediate's write+read — the
    memory-traffic saving the cost model prices; the live block rides in
    ``extra_vmem_bytes`` so VMEM pressure is not understated."""
    reason = can_stitch(producer, consumer, operand)
    if reason is not None:
        raise ValueError(
            f"cannot stitch {producer.name}{CHAIN_SEP}{consumer.name}: "
            f"{reason}")

    sidx = consumer.in_names.index(operand)
    pout = producer.outputs[0]
    cin = consumer.inputs[sidx]
    n_pi, n_ci = len(producer.inputs), len(consumer.inputs)
    n_co, n_ps = len(consumer.outputs), len(producer.scratch)
    reshape_to = (None if pout.block_shape == cin.block_shape
                  else cin.block_shape)
    p_body, c_body = producer.body, consumer.body

    def body(step, *refs):
        pin = refs[:n_pi]
        cin_ext = refs[n_pi:n_pi + n_ci - 1]
        couts = refs[n_pi + n_ci - 1:n_pi + n_ci - 1 + n_co]
        scr = refs[n_pi + n_ci - 1 + n_co:]
        cap = _CaptureRef(pout.block_shape, pout.dtype)
        p_body(step, *pin, cap, *scr[:n_ps])
        if cap.value is None:
            raise RuntimeError(
                f"{producer.name}: body never wrote its output block")
        val = cap.value if reshape_to is None else cap.value.reshape(
            reshape_to)
        crefs = (*cin_ext[:sidx], _ValueRef(val), *cin_ext[sidx:])
        c_body(step, *crefs, *couts, *scr[n_ps:])

    def shrink(factor: int) -> Optional[OpSpec]:
        ps = shrink_blocks(producer, factor)
        cs = shrink_blocks(consumer, factor)
        if ps is None or cs is None or can_stitch(ps, cs, operand):
            return None
        return stitch(ps, cs, operand)

    saved = _array_bytes(pout) + _array_bytes(cin)
    tag = "|".join(t for t in (producer.tag, consumer.tag) if t)
    return OpSpec(
        name=f"{producer.name}{CHAIN_SEP}{consumer.name}",
        grid=producer.grid,
        body=body,
        inputs=producer.inputs + consumer.inputs[:sidx]
        + consumer.inputs[sidx + 1:],
        outputs=consumer.outputs,
        flops=producer.flops + consumer.flops,
        hbm_bytes=max(producer.hbm_bytes + consumer.hbm_bytes - saved, 1.0),
        tag=f"chain:{tag}" if tag else "chain",
        shrink=shrink,
        in_names=producer.in_names + consumer.in_names[:sidx]
        + consumer.in_names[sidx + 1:],
        out_names=consumer.out_names,
        chain=(producer.name, consumer.name),
        scratch=producer.scratch + consumer.scratch,
        extra_vmem_bytes=(pout.block_bytes() + producer.extra_vmem_bytes
                          + consumer.extra_vmem_bytes),
    )
