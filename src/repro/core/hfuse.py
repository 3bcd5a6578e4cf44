"""Generate() — build the horizontally-fused Pallas kernel from N OpSpecs.

This is the TPU realization of the paper's Fig. 5 algorithm, generalized
from kernel *pairs* to N-op *bundles*:

  paper (CUDA thread space)             here (Pallas grid space)
  -------------------------------------------------------------------------
  threads [0,d1) run K1, [d1,d0) K2     grid steps interleave the bundle per
                                        the Schedule (r_0 : r_1 : ... : r_N)
  branch on threadIdx.x                 @pl.when(phase(program_id))
  replace threadIdx/blockDim with       op-local step s_i(t) passed to each
  tid_1/size_1, tid_2/size_2            body
  bar.sync id, d partial barriers       not needed: grid steps independent
                                        (see DESIGN.md §2)
  register cap (maxrregcount)           VMEM cap via block-shape choice +
                                        compiler vmem limit

DMA-elision scheduling: during any other op's phase, every operand's index
map *holds* its last value (Pallas skips the copy when the block index is
unchanged between steps).  Thus while a compute-bound member's step occupies
the MXU, the pipeline prefetches the memory-bound members' next blocks — the
warp-scheduler latency hiding of the paper, reconstructed with the only
latency-hiding machinery a TPU has.

The 2-op entry points (``generate(a, b, sched)``, ``generate_vfused(a, b)``,
``run_native(a, b)``) remain as thin wrappers over the bundle forms.
"""
from __future__ import annotations

import math
import re
from typing import Optional, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.core.cost_model import VMEM_BUDGET, Schedule
from repro.core.op_spec import DMA_SEMAPHORE, Operand, OpSpec


def _block_spec(operand: Operand, index_map) -> pl.BlockSpec:
    if operand.smem:
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    if operand.hbm:
        return pl.BlockSpec(memory_space=pl.ANY)
    return pl.BlockSpec(operand.block_shape, index_map)


def _scratch_shape(shape, dtype):
    if dtype == DMA_SEMAPHORE:
        return pltpu.SemaphoreType.DMA(tuple(shape))
    return pltpu.VMEM(shape, dtype)


def _pallas_call(kernel, ops: Sequence[OpSpec], grid: int, in_specs,
                 out_specs, *, interpret: bool, vmem_limit: Optional[int]):
    """One pallas_call over ``ops``' operands and scratch.  A compiled
    (non-interpret) call always states its scoped-VMEM limit: the tuned cap,
    else the whole planning budget — the 16 MiB compiler default is smaller
    than bundles the cost model admits.

    The launch is named after its members: ``name`` becomes the kernel's
    and its custom call's name (characters outside ``[A-Za-z0-9_]`` read
    ``_`` there), and ``kernel_metadata`` carries the exact member list as
    ``{"launch": "a+b"}`` in the custom call's frontend attributes, which
    the device trace's op text keeps."""
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_limit or VMEM_BUDGET))
    launch = "+".join(op.name for op in ops)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype)
                   for op in ops for o in op.outputs],
        scratch_shapes=[_scratch_shape(shape, dt)
                        for op in ops for shape, dt in op.scratch],
        interpret=interpret,
        name=re.sub(r"\W", "_", launch, flags=re.ASCII),
        metadata={"launch": launch},
        **kwargs,
    )


def _bundle_phase_fns(ops: Sequence[OpSpec], sched: Schedule):
    """Per-op (step, active) grid functions + total fused step count.

    Within a super-step of ``period`` fused steps, op i owns the phase
    window [off_i, off_i + r_i).  Outside its window its step index holds
    (clips to the last block it touched) so Pallas elides the DMAs.
    """
    period = sched.period
    offsets = sched.offsets()

    def make(i):
        r, off, grid = sched.ratios[i], offsets[i], ops[i].grid

        def step(t):
            s, ph = t // period, t % period
            p = ph - off
            # before my window: hold previous super-step's last block;
            # after it: hold this super-step's last block
            idx = s * r + jnp.clip(p, -1, r - 1)
            return jnp.clip(idx, 0, grid - 1)

        def active(t):
            s, ph = t // period, t % period
            p = ph - off
            return (p >= 0) & (p < r) & (s * r + p < grid)

        return step, active

    fns = [make(i) for i in range(len(ops))]
    n_super = max(math.ceil(op.grid / r)
                  for op, r in zip(ops, sched.ratios))
    return fns, n_super * period


def _normalize(ops, b, sched):
    """Accept generate(ops, sched) or the legacy generate(a, b, sched)."""
    if isinstance(ops, OpSpec):
        ops = (ops, b)
    else:
        ops, sched = tuple(ops), b if sched is None else sched
    if sched.n_ops != len(ops):
        raise ValueError(
            f"schedule has {sched.n_ops} ratios for {len(ops)} ops")
    return ops, sched


def generate(ops, b=None, sched: Optional[Schedule] = None, *,
             interpret: bool = False, vmem_limit: Optional[int] = None):
    """Returns fused(*op0_inputs, ..., *opN_inputs) ->
    (*op0_outputs, ..., *opN_outputs) — one Pallas call for the bundle."""
    ops, sched = _normalize(ops, b, sched)
    fns, n_steps = _bundle_phase_fns(ops, sched)

    n_ins = [len(op.inputs) for op in ops]
    n_outs = [len(op.outputs) for op in ops]
    n_scr = [len(op.scratch) for op in ops]
    in_off = [sum(n_ins[:i]) for i in range(len(ops) + 1)]
    out_off = [sum(n_outs[:i]) for i in range(len(ops) + 1)]
    scr_off = [sum(n_scr[:i]) for i in range(len(ops) + 1)]
    n_in_total = in_off[-1]
    n_io = n_in_total + out_off[-1]

    def fused_kernel(*refs):
        t = pl.program_id(0)
        for i, op in enumerate(ops):
            step, active = fns[i]
            ins = refs[in_off[i]:in_off[i + 1]]
            outs = refs[n_in_total + out_off[i]:n_in_total + out_off[i + 1]]
            scr = refs[n_io + scr_off[i]:n_io + scr_off[i + 1]]

            @pl.when(active(t))
            def _(op=op, step=step, ins=ins, outs=outs, scr=scr):
                op.body(step(t), *ins, *outs, *scr)

    def remap(op_step, operand):
        return _block_spec(
            operand, lambda t, _f=operand.index_map, _s=op_step: _f(_s(t)))

    in_specs = [remap(fns[i][0], o)
                for i, op in enumerate(ops) for o in op.inputs]
    out_specs = [remap(fns[i][0], o)
                 for i, op in enumerate(ops) for o in op.outputs]
    call = _pallas_call(fused_kernel, ops, n_steps, in_specs, out_specs,
                        interpret=interpret, vmem_limit=vmem_limit)

    def fused(*operands):
        assert len(operands) == n_in_total, (len(operands), n_ins)
        outs = call(*operands)
        return tuple(outs) if isinstance(outs, (list, tuple)) else (outs,)

    fused.n_steps = n_steps
    fused.schedule = sched
    fused.ops = ops
    return fused


def generate_vfused(*ops, **kw):
    """Concatenated (vertical-style) baseline: all of op 0's steps, then all
    of op 1's, ... — one kernel, no interleaving.  Same machinery,
    degenerate schedule.  Accepts OpSpecs positionally or one sequence."""
    if len(ops) == 1 and not isinstance(ops[0], OpSpec):
        ops = tuple(ops[0])
    return generate(ops, Schedule(tuple(op.grid for op in ops)), **kw)


def run_single(op: OpSpec, *, interpret: bool = False):
    """Standalone pallas_call for one OpSpec (used by tests and `native`)."""
    def kernel(*refs):
        op.body(pl.program_id(0), *refs)

    call = _pallas_call(
        kernel, (op,), op.grid,
        [_block_spec(o, o.index_map) for o in op.inputs],
        [_block_spec(o, o.index_map) for o in op.outputs],
        interpret=interpret, vmem_limit=None)

    def run(*operands):
        outs = call(*operands)
        return tuple(outs) if isinstance(outs, (list, tuple)) else (outs,)
    return run


def run_native(*ops, interpret: bool = False):
    """The 'native' baseline: one pallas_call per op (N launches).

    NOTE: on a TPU core there is no stream concurrency — kernels
    serialize — which is why horizontal fusion is the *only* way N ops
    co-execute (DESIGN.md §8.5)."""
    if len(ops) == 1 and not isinstance(ops[0], OpSpec):
        ops = tuple(ops[0])
    calls = [run_single(op, interpret=interpret) for op in ops]

    def native(*operands):
        outs, off = [], 0
        for op, call in zip(ops, calls):
            outs.extend(call(*operands[off:off + len(op.inputs)]))
            off += len(op.inputs)
        return tuple(outs)

    return native
