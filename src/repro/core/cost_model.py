"""Three-term roofline cost model for fusion decisions (TPU v5e).

This is the napkin-math engine behind the planner and the autotuner — the
role profiling plays in the paper's ``Main()`` (Fig. 6).  The fundamental
inequality of horizontal fusion, generalized to an N-op bundle:

    t_native(K1;..;KN) = Σ_i max(tc_i, tm_i)           (N kernels, serial)
    t_hfused(K1∪..∪KN) ≈ max(Σ_i tc_i, Σ_i tm_i)       (engines overlap)

    gain = t_native − t_hfused ≥ 0, strictly > 0  iff  the bundle mixes
    bound kinds (memory- and compute-bound members) — the paper's §IV-C
    finding (Ethash+Blake256 wins, Blake256+SHA256 loses) falls out
    directly, and extends: a second memory-bound op joining a
    compute-dominated bundle still rides the idle HBM engine for free.

VMEM pressure is the occupancy analogue: the fused kernel needs every
member's blocks resident (×2 for double buffering).  Exceeding the budget
forfeits pipelining — modeled as degrading overlap from max(Σc, Σm) toward
Σc+Σm — the same cliff the paper's register-cap search navigates.
"""
from __future__ import annotations

import json
import math
import os
import re
from dataclasses import dataclass
from typing import Optional, Sequence

from repro.core.op_spec import OpSpec
from repro.distributed.hlo_analysis import HBM_BW, PEAK_FLOPS, VMEM_BYTES

VMEM_BUDGET = int(VMEM_BYTES * 0.8)        # leave headroom for spills/semaphores

# Sub-roofline terms (TPU v5e).  The paper's GPU gains come partly from
# effects *below* the roofline (issue-slot stalls); the TPU analogues we
# model are (a) kernel launch/teardown (~2us — paper footnote 1: fusion
# amortizes it N-fold) and (b) the pipeline ramp: the first block's DMA and
# the last block's compute have nothing to overlap with (one (tc+tm)/N per
# kernel; the fused kernel pays it once).  Same-resource bundles gain only
# these small terms on TPU (and can lose via VMEM pressure) — the honest
# adaptation finding, recorded in EXPERIMENTS.md §Paper-validation.
LAUNCH_S = 2e-6

# Interleave-ratio domain shared by the candidate lattice and the
# autotuner's coordinate descent — one bound, one search space.
MAX_RATIO = 4096

# ---------------------------------------------------------------------------
# Measured-delta corrections (fitted, default OFF)
#
# The measured-mode search records cm_vs_measured_delta_pct per bundle;
# ``python -m repro.tools fit-cost`` distills the measured reports
# (BENCH_measured_*.json) into a per-op-class
# multiplicative correction table — clamped medians of measured/predicted.
# The table is consulted only when loaded ($REPRO_COST_CORRECTIONS=<path>
# or set_corrections(...)); with nothing loaded every factor is exactly
# 1.0 and the model is byte-for-byte the analytic roofline above.
# ---------------------------------------------------------------------------
CORRECTION_CLAMP = (0.5, 2.0)

# parameter segments in generated op names (B3, S128, H4kv4, C8, pg16, 1d):
# a short alpha prefix followed by a digit, or a leading digit
_PARAM_SEG = re.compile(r"^[A-Za-z]{0,3}\d")
_CHAIN_SEP = "→"                       # stitch.CHAIN_SEP, sans import

_corrections: Optional[dict] = None
_corrections_env_loaded = False


def op_class(name: str) -> str:
    """Stable class key for an op name: shape/index parameters stripped.
    ``decode_attn_B3_S128_H4kv4`` and ``decode_attn_B2_S256_H8kv4`` are one
    class; ``prefill_attn0_C8_...`` and ``prefill_attn1_C16_...`` are one
    class; a stitched chain is the chain of its members' classes."""
    if _CHAIN_SEP in name:
        return _CHAIN_SEP.join(op_class(p) for p in name.split(_CHAIN_SEP))
    kept = []
    for seg in name.split("_"):
        if _PARAM_SEG.match(seg):
            continue                        # B3 / S128 / H4kv4 / 1d / pg16
        kept.append(seg.rstrip("0123456789"))   # norm1 -> norm, attn0 -> attn
    return "_".join(s for s in kept if s) or name


def set_corrections(table: Optional[dict]) -> None:
    """Install (or clear, with None) the per-op-class correction table:
    ``{class: factor}`` or the fit-cost file schema ``{"classes": {class:
    {"correction": factor, ...}}}``."""
    global _corrections, _corrections_env_loaded
    if table is not None and "classes" in table:
        table = {k: float(v["correction"] if isinstance(v, dict) else v)
                 for k, v in table["classes"].items()}
    _corrections = table
    _corrections_env_loaded = True          # explicit call wins over env


def _correction_table() -> Optional[dict]:
    global _corrections_env_loaded
    if not _corrections_env_loaded:
        _corrections_env_loaded = True
        path = os.environ.get("REPRO_COST_CORRECTIONS")
        if path:
            try:
                with open(path) as fh:
                    set_corrections(json.load(fh))
            except (OSError, json.JSONDecodeError, KeyError, TypeError,
                    ValueError):
                pass                        # unreadable table == no table
    return _corrections


def correction_for(name: str) -> float:
    """Fitted multiplicative factor for this op's class (1.0 unless a
    table is loaded and carries the class)."""
    table = _correction_table()
    if not table:
        return 1.0
    lo, hi = CORRECTION_CLAMP
    return min(hi, max(lo, float(table.get(op_class(name), 1.0))))


def native_time(op: OpSpec) -> float:
    """Standalone kernel wall-time model: roofline + ramp + launch."""
    ramp = (op.t_compute + op.t_memory) / max(op.grid, 1)
    return (max(op.t_compute, op.t_memory) + ramp) * correction_for(op.name) \
        + LAUNCH_S


class Schedule:
    """Interleave ratio vector: r_i steps of op i per super-step, in order.

    ``Schedule(ratios)`` takes the N-way ratio tuple; ``Schedule(ra, rb)``
    is the 2-op form (the paper's thread-partition point d1): it sets how
    much of each op is in flight per super-step.  DMA-elision index maps
    (core/hfuse.py) hold each op's blocks outside its own phase.
    """
    __slots__ = ("ratios",)

    def __init__(self, *args):
        if len(args) == 1 and not isinstance(args[0], int):
            ratios = tuple(int(r) for r in args[0])
        else:
            ratios = tuple(int(a) for a in args)
        if not ratios or any(r < 1 for r in ratios):
            raise ValueError(f"ratios must be positive ints, got {ratios}")
        object.__setattr__(self, "ratios", ratios)

    @property
    def n_ops(self) -> int:
        return len(self.ratios)

    @property
    def ra(self) -> int:
        return self.ratios[0]

    @property
    def rb(self) -> int:
        return self.ratios[1]

    @property
    def period(self) -> int:
        return sum(self.ratios)

    def offsets(self) -> tuple[int, ...]:
        """Phase start of each op within the super-step."""
        offs, acc = [], 0
        for r in self.ratios:
            offs.append(acc)
            acc += r
        return tuple(offs)

    def label(self) -> str:
        return ":".join(str(r) for r in self.ratios)

    def __eq__(self, other):
        return isinstance(other, Schedule) and self.ratios == other.ratios

    def __hash__(self):
        return hash(self.ratios)

    def __repr__(self):
        return f"Schedule({self.ratios})"


@dataclass
class FusedEstimate:
    t_native: float
    t_vfused: float
    t_hfused: float
    gain_vs_native: float
    gain_vs_vfused: float
    vmem_bytes: int
    vmem_ok: bool
    overlap_eff: float

    def speedup_pct(self) -> float:
        return 100.0 * self.gain_vs_native / max(self.t_native, 1e-30)


def _as_bundle(args) -> tuple[tuple[OpSpec, ...], Schedule]:
    """Accept (a, b, sched) legacy positionals or (ops, sched)."""
    if isinstance(args[0], OpSpec):
        *ops, sched = args
        ops = tuple(ops)
    else:
        ops, sched = tuple(args[0]), args[1]
    if sched.n_ops != len(ops):
        raise ValueError(
            f"schedule has {sched.n_ops} ratios for {len(ops)} ops")
    return ops, sched


def hfused_cost(*args, vmem_budget: int = VMEM_BUDGET) -> FusedEstimate:
    """Cost of the interleaved fused bundle under a schedule.

    ``hfused_cost(ops, sched)`` for an N-op bundle, or the legacy 2-op
    ``hfused_cost(a, b, sched)``.
    """
    ops, sched = _as_bundle(args)
    corr = [correction_for(op.name) for op in ops]
    tcs = [op.t_compute * c for op, c in zip(ops, corr)]
    tms = [op.t_memory * c for op, c in zip(ops, corr)]
    ramps = [(tc + tm) / max(op.grid, 1)
             for op, tc, tm in zip(ops, tcs, tms)]
    t_native = sum(native_time(op) for op in ops)       # N launches
    # vertical/concatenated baseline: one kernel, phases stay serial;
    # saves N-1 launches + all but one boundary ramp (paper footnote 1)
    t_vfused = sum(max(tc, tm) for tc, tm in zip(tcs, tms)) \
        + max(ramps) + LAUNCH_S

    # The interleave ratios control how long the ops co-execute: with grids
    # N_i and ratios r_i, full co-execution lasts until the shortest op (in
    # super-steps) is exhausted; each op's leftover runs progressively less
    # overlapped — modeled as its un-overlapped tail.
    ss = [math.ceil(op.grid / r) for op, r in zip(ops, sched.ratios)]
    co = min(ss)                            # super-steps with all ops active
    fs = [co / s for s in ss]
    # overlapped portion: engines add across the bundle; tails: leftovers
    t_overlap = max(sum(f * tc for f, tc in zip(fs, tcs)),
                    sum(f * tm for f, tm in zip(fs, tms)))
    t_tail = sum(max((1 - f) * tc, (1 - f) * tm)
                 for f, tc, tm in zip(fs, tcs, tms))

    # VMEM: every member's blocks resident, double-buffered
    vmem = 2 * sum(op.vmem_bytes for op in ops)
    vmem_ok = vmem <= vmem_budget
    ramp_fused = max(ramps)
    if vmem_ok:
        t_h = t_overlap + t_tail + ramp_fused + LAUNCH_S
        eff = 1.0
    else:
        # pipelining forfeited: DMA and compute serialize (the "occupancy
        # cliff'); interpolate by how far over budget we are
        over = min(2.0, vmem / vmem_budget)
        serial = sum(f * tc for f, tc in zip(fs, tcs)) \
            + sum(f * tm for f, tm in zip(fs, tms))
        t_h = t_tail + t_overlap + (serial - t_overlap) * (over - 1.0) \
            + ramp_fused + LAUNCH_S
        eff = max(0.0, 2.0 - over)
    return FusedEstimate(
        t_native=t_native, t_vfused=t_vfused, t_hfused=t_h,
        gain_vs_native=t_native - t_h, gain_vs_vfused=t_vfused - t_h,
        vmem_bytes=vmem, vmem_ok=vmem_ok, overlap_eff=eff)


def fusion_profitable(a: OpSpec, b: OpSpec) -> bool:
    """The paper's scenario test: different bound kinds => profitable."""
    return a.bound != b.bound


def bundle_profitable(ops: Sequence[OpSpec]) -> bool:
    """N-way scenario test: the bundle must mix bound kinds — an all-
    compute (or all-memory) bundle only saves launches (Blake256+SHA256)."""
    return len({op.bound for op in ops}) > 1


def ratio_candidates(*args, max_ratio: int = MAX_RATIO) -> list[Schedule]:
    """Candidate interleave ratio vectors ~ the paper's d1 sweep.

    ``ratio_candidates(ops)`` for a bundle or legacy ``ratio_candidates(a, b)``.
    Includes the grid-proportional vector (so wildly imbalanced grids —
    e.g. a 2048-step decode-attention stream vs a 4-step prefill matmul —
    co-execute end-to-end) plus scaled neighbours and per-op boosts."""
    if isinstance(args[0], OpSpec):
        ops = tuple(args)
    else:
        ops = tuple(args[0])
    n = len(ops)
    cands = {(1,) * n}
    # boost one op at a time (generalizes (2,1),(1,2),(4,1),(1,4))
    for i in range(n):
        for r in (2, 4):
            v = [1] * n
            v[i] = r
            cands.add(tuple(v))
    # grid-proportional vector and its half/double neighbours
    gmin = max(1, min(op.grid for op in ops))
    for s in (0.5, 1.0, 2.0):
        cands.add(tuple(
            max(1, min(max_ratio, round(op.grid * s / gmin))) for op in ops))
    return [Schedule(v) for v in sorted(cands)]
