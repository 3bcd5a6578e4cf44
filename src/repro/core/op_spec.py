"""OpSpec — the fusible-kernel IR of the horizontal-fusion engine.

An OpSpec is the TPU analogue of the paper's "input kernel": a computation
with a linear (1-D) grid of independent steps, per-operand BlockSpecs, and a
resource profile (FLOPs / HBM bytes / VMEM working set).  The paper's kernels
are CUDA source; ours are Pallas bodies.  The 1-D grid plays the role of the
block space; the *fused* kernel's grid (core/hfuse.py) partitions / interleaves
its steps between two ops the way HFUSE partitions the thread space.

Contract for ``body``:
  body(step, *in_refs, *out_refs, *scratch_refs) — ``step`` is the
  op-local grid step (a traced scalar); refs are VMEM blocks selected by
  the index maps (SMEM tables for ``Operand.smem``, whole HBM arrays for
  ``Operand.hbm``), then one scratch buffer per ``OpSpec.scratch`` entry
  (VMEM, or DMA semaphores), persistent across the op's steps.
  The body must not call pl.program_id itself (the fused kernel owns it).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.distributed.hlo_analysis import HBM_BW, PEAK_FLOPS, RIDGE, VMEM_BYTES


@dataclass(frozen=True)
class Operand:
    """One input or output of a fusible op.

    ``smem=True`` marks a small int32 table (per-slot lengths, chunk
    offsets, block-table rows) that the kernel reads as scalars: it lives
    whole in scalar memory, so ``block_shape`` equals ``shape``, the index
    map is never consulted and it costs no VMEM.

    ``hbm=True`` marks an array the kernel leaves in HBM and copies from
    itself (``pltpu.make_async_copy`` into ``OpSpec.scratch`` buffers):
    ``block_shape`` equals ``shape``, the index map is never consulted and
    the operand itself costs no VMEM — its buffers are the op's scratch."""
    shape: tuple[int, ...]
    dtype: Any
    block_shape: tuple[int, ...]
    index_map: Callable[[Any], tuple]      # op-local step -> block indices
    smem: bool = False
    hbm: bool = False

    def block_bytes(self) -> int:
        if self.smem or self.hbm:
            return 0
        return vmem_bytes_of(self.block_shape, self.dtype)


def vmem_bytes_of(shape: Sequence[int], dtype) -> int:
    """Bytes one VMEM buffer of ``shape`` occupies: the last two dims round
    up to the (8, 128) tile, which is what the compiler allocates (a
    (ck, Hkv, 64) cache block takes 128 lanes per row, not 64)."""
    dims = list(shape)
    if dims:
        dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        dims[-2] = -(-dims[-2] // 8) * 8
    return int(math.prod(dims)) * jnp.dtype(dtype).itemsize


def smem_operand(shape: tuple[int, ...]) -> Operand:
    """A whole int32 array in scalar memory (see ``Operand.smem``)."""
    return Operand(tuple(shape), jnp.int32, tuple(shape),
                   lambda s: (0,) * len(shape), smem=True)


def hbm_operand(shape: tuple[int, ...], dtype) -> Operand:
    """A whole array left in HBM for the body to copy from (see
    ``Operand.hbm``)."""
    return Operand(tuple(shape), dtype, tuple(shape),
                   lambda s: (0,) * len(shape), hbm=True)


# ``OpSpec.scratch`` dtype of an array of DMA semaphores (no VMEM)
DMA_SEMAPHORE = "dma_semaphore"


def scratch_bytes(shape: Sequence[int], dtype) -> int:
    """VMEM one ``OpSpec.scratch`` entry takes (semaphores take none)."""
    if dtype == DMA_SEMAPHORE:
        return 0
    return vmem_bytes_of(shape, dtype)


@dataclass
class OpSpec:
    name: str
    grid: int                              # number of op-local steps
    body: Callable                         # body(step, *in_refs, *out_refs)
    inputs: tuple[Operand, ...]
    outputs: tuple[Operand, ...]
    flops: float                           # whole-op FLOPs
    hbm_bytes: float                       # whole-op HBM traffic (streaming)
    tag: str = ""                          # provenance (paper-suite name etc.)
    shrink: Optional[Callable] = None      # factor -> OpSpec with smaller
    #                                        blocks (overrides shrink_blocks'
    #                                        structural rewrite)
    # Epilogue contract (core/stitch.py): declaring ``epilogue=(consumer,
    # operand)`` on a producer asserts its single output feeds EXACTLY that
    # consumer's named operand and is dead afterwards — the planner may then
    # contract the pair into one stitched chain whose intermediate never
    # round-trips HBM.  ``chain`` marks an OpSpec that IS such a chain (the
    # member names, producer first); ``extra_vmem_bytes`` accounts for the
    # register/VMEM-resident intermediate the stitch keeps live per step.
    epilogue: Optional[tuple[str, str]] = None
    chain: tuple[str, ...] = ()
    extra_vmem_bytes: int = 0
    # Stable operand signature (core/binding.py contract): one name per
    # input/output, positional order.  An op with names can be bound to live
    # arrays by the executor; unnamed operands are tuning-only.  A name may
    # appear in BOTH tuples (in-place semantics: adamw's p/m/v) — the
    # binding then reads and rewrites the same state key.
    in_names: tuple[str, ...] = ()
    out_names: tuple[str, ...] = ()
    # Persistent scratch ((shape, dtype) each) handed to the body after its
    # output refs: VMEM buffers — carries such as online-softmax statistics
    # that need no HBM copy, or the landing buffers of the body's own DMAs —
    # and, with dtype ``DMA_SEMAPHORE``, arrays of DMA semaphores.
    scratch: tuple[tuple[tuple[int, ...], Any], ...] = ()

    def __post_init__(self):
        if self.in_names and len(self.in_names) != len(self.inputs):
            raise ValueError(f"{self.name}: {len(self.in_names)} in_names "
                             f"for {len(self.inputs)} inputs")
        if self.out_names and len(self.out_names) != len(self.outputs):
            raise ValueError(f"{self.name}: {len(self.out_names)} out_names "
                             f"for {len(self.outputs)} outputs")

    @property
    def has_signature(self) -> bool:
        return bool(self.in_names) and bool(self.out_names)

    # ------------------------------------------------------------------
    @property
    def vmem_bytes(self) -> int:
        """Per-step working set (single-buffered blocks plus scratch); the
        body's live intermediates (a stitched chain's resident block, an
        attention score tile) ride in ``extra_vmem_bytes``."""
        return (sum(o.block_bytes() for o in (*self.inputs, *self.outputs))
                + sum(scratch_bytes(shape, dt) for shape, dt in self.scratch)
                + self.extra_vmem_bytes)

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1.0)

    @property
    def bound(self) -> str:
        """Roofline classification — the paper's 'kind of GPU resource'."""
        return "compute" if self.arithmetic_intensity >= RIDGE else "memory"

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_native(self) -> float:
        """Ideal pipelined standalone time: max of the two engine terms."""
        return max(self.t_compute, self.t_memory)

    def step_costs(self) -> tuple[float, float]:
        """(compute, memory) seconds per grid step (uniform-step assumption)."""
        return self.t_compute / self.grid, self.t_memory / self.grid

    def describe(self) -> dict:
        return {
            "name": self.name, "grid": self.grid, "flops": self.flops,
            "hbm_bytes": self.hbm_bytes, "vmem_bytes": self.vmem_bytes,
            "arithmetic_intensity": round(self.arithmetic_intensity, 2),
            "bound": self.bound,
            "t_compute_us": self.t_compute * 1e6,
            "t_memory_us": self.t_memory * 1e6,
            "t_native_us": self.t_native * 1e6,
        }


def make_operand(arr_or_sds, block_shape, index_map) -> Operand:
    return Operand(tuple(arr_or_sds.shape), arr_or_sds.dtype,
                   tuple(block_shape), index_map)


# ---------------------------------------------------------------------------
# Automatic block shrinking (the paper's register-cap analogue)
# ---------------------------------------------------------------------------
MIN_BLOCK_ROWS = 8                # TPU sublane floor (f32 tile is (8, 128))


def _index_pattern(operand: Operand, grid: int = 8) -> Optional[str]:
    """Classify an index map by probing it with concrete steps sampled
    across the whole ``grid``.

    'const'  — same block every step (broadcast operand: weights, carries).
    'stream' — unit-stride in the leading axis, (s, c1, ..) with the other
               components constant: the row-partitioned streaming pattern
               every shrinkable op in this repo uses.
    None     — anything else (opaque/affine maps): not safely rewritable.

    The probe sample must include late steps: batch-major maps like
    ``s // nk`` (decode attention's per-slot operands) are constant over
    the first ``nk`` steps and would masquerade as 'const' under a probe
    of small steps only — misclassifying a streamed operand as a
    broadcast would let ``shrink_blocks`` silently break the body's slot
    addressing.  Probing {grid//2, grid-1} alongside {0, 1, 2} rules that
    out for every monotone map at any ``nk``; the small steps are probed
    even past a tiny grid (pure extrapolation) so grid-1 streaming ops
    still classify as 'stream' and keep their halved-block variant.
    """
    steps = sorted({0, 1, 2, grid // 2, max(grid - 1, 0)})
    try:
        probes = {s: tuple(int(c) for c in operand.index_map(s))
                  for s in steps}
    except Exception:
        return None
    first = probes[0]
    if all(p == first for p in probes.values()):
        return "const"
    if (all(p[0] == s for s, p in probes.items())
            and all(p[1:] == first[1:] for p in probes.values())):
        return "stream"
    return None


def shrink_blocks(op: OpSpec, factor: int = 2) -> Optional[OpSpec]:
    """Halve (``factor=2``) every streamed operand's leading block dim and
    scale the grid to match — the working set shrinks x``factor``, total
    work is unchanged.  This is the paper's Fig. 6 register-bound move
    (maxrregcount r0) translated to VMEM: when a fused bundle can't
    co-reside double-buffered, smaller blocks restore pipelining headroom.

    Returns None when the rewrite can't be proven safe:
      * an op-provided ``shrink`` factory takes precedence (exact rewrite);
      * every operand must classify as 'const' or unit-stride 'stream';
      * streamed leading dims must divide by ``factor`` and stay >= the
        sublane floor;
      * a const operand whose block shares a streamed leading dim is
        assumed shape-coupled to the stream inside the body (e.g.
        ethash's seed block is added elementwise to the DAG block) —
        shrinking one side would break the body.
    """
    if factor <= 1:
        return op
    if op.shrink is not None:
        return op.shrink(factor)

    operands = (*op.inputs, *op.outputs)
    patterns = [_index_pattern(o, op.grid) for o in operands]
    if any(p is None for p in patterns):
        return None
    stream_leads = {o.block_shape[0]
                    for o, p in zip(operands, patterns) if p == "stream"}
    if not stream_leads:
        return None                           # nothing streams: nothing to shrink
    for o, p in zip(operands, patterns):
        if p == "stream":
            lead = o.block_shape[0]
            if lead % factor or lead // factor < MIN_BLOCK_ROWS:
                return None
        elif any(d in stream_leads for d in o.block_shape):
            return None                       # body-coupled const operand

    def shrunk(o: Operand, p: str) -> Operand:
        if p == "const":
            return o
        return dataclasses.replace(
            o, block_shape=(o.block_shape[0] // factor, *o.block_shape[1:]))

    n_in = len(op.inputs)
    new = [shrunk(o, p) for o, p in zip(operands, patterns)]
    return dataclasses.replace(
        op, grid=op.grid * factor,
        inputs=tuple(new[:n_in]), outputs=tuple(new[n_in:]),
        tag=f"{op.tag}|blocks/{factor}" if op.tag else f"blocks/{factor}")
