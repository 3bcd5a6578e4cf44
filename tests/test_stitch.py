"""Epilogue stitching (core/stitch.py) + planner chain contraction.

Parity contract: a stitched producer→consumer chain is BITWISE equal to
running the two kernels separately — including every shrink variant the
chain's shrink factory produces and the grid-1 degenerate — because the
producer's block value is captured *after* its final ``.astype`` and handed
to the consumer in-register.  Property-tested with hypothesis when it is
installed; otherwise the same check runs over a fixed seed sweep so the
contract is exercised everywhere.

Also here: the ``can_stitch`` rejection taxonomy, the row-stream reshape
case (dW (bm, N) blocks → adamw (bm·N/128, 128) blocks), planner
contraction legality (single reader, acyclicity, graceful fallback), chain
cost accounting, and the ScheduleCache regression — chain structure is part
of the bundle signature, so a stitched plan can never resolve an unstitched
plan's cached schedule.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hfuse, planner
from repro.core.op_spec import OpSpec, shrink_blocks
from repro.core.schedule_cache import ScheduleCache, bundle_signature
from repro.core.stitch import CHAIN_SEP, can_stitch, chain_label, stitch
from repro.kernels.adam import LANES, adamw_op
from repro.kernels.elementwise import (activation_op, residual_add_op,
                                       silu_gate)
from repro.kernels.matmul import matmul_1d_op
from repro.kernels.rmsnorm import rmsnorm_op

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _run(op, *args):
    return hfuse.run_single(op, interpret=True)(*args)


# ---------------------------------------------------------------------------
# Parity: chain == separate ops, bitwise
# ---------------------------------------------------------------------------
def _norm_matmul_parity(R, d, N, bm, factor, seed):
    """rmsnorm→matmul at block rows ``bm``, optionally shrunk by
    ``factor``, must match the separate pair bit for bit."""
    norm = rmsnorm_op(R=R, d=d, dtype=jnp.float32, bm=bm)
    mm = matmul_1d_op(M=R, K=d, N=N, dtype=jnp.float32, bm=bm)
    chain = stitch(norm, mm, "x")
    assert chain.name == chain_label(norm.name, mm.name)
    if factor > 1:
        chain = chain.shrink(factor)
        norm = shrink_blocks(norm, factor)
        mm = shrink_blocks(mm, factor)
        if chain is None or norm is None or mm is None:
            pytest.skip(f"factor {factor} unprovable at bm={bm}")
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(R, d)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(1, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, N)), jnp.float32)
    (y_sep,) = _run(norm, x, scale)
    (o_sep,) = _run(mm, y_sep, w)
    (o_chain,) = _run(chain, x, scale, w)
    assert np.array_equal(np.asarray(o_chain), np.asarray(o_sep)), \
        f"chain diverged at R={R} d={d} N={N} bm={bm} factor={factor}"


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(rows=st.sampled_from([16, 32, 64]),
           d=st.sampled_from([128, 256]),
           n=st.sampled_from([128, 384]),
           split=st.sampled_from([1, 2, 4]),
           factor=st.sampled_from([1, 2]),
           seed=st.integers(0, 2**31 - 1))
    def test_chain_parity_property(rows, d, n, split, factor, seed):
        bm = max(rows // split, 8)
        _norm_matmul_parity(rows, d, n, bm, factor, seed)
else:
    @pytest.mark.parametrize("rows,d,n,split,factor,seed", [
        (16, 128, 128, 1, 1, 0),       # grid-1 (whole array in one block)
        (32, 128, 384, 2, 1, 1),
        (32, 256, 128, 2, 2, 2),       # shrunk chain variant
        (64, 128, 128, 4, 1, 3),
        (64, 256, 384, 4, 2, 4),
        (64, 128, 384, 1, 2, 5),       # grid-1 shrunk into grid-2
    ])
    def test_chain_parity_property(rows, d, n, split, factor, seed):
        bm = max(rows // split, 8)
        _norm_matmul_parity(rows, d, n, bm, factor, seed)


def test_matmul_residual_add_chain_parity():
    R, K, N, bm = 32, 64, 128, 16
    mm = matmul_1d_op(M=R, K=K, N=N, dtype=jnp.float32, bm=bm)
    add = residual_add_op(R, N, dtype=jnp.float32, bm=bm)
    chain = stitch(mm, add, "h")
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(R, N)), jnp.float32)
    (h,) = _run(mm, x, w)
    (o_sep,) = _run(add, h, res)
    (o_chain,) = _run(chain, x, w, res)
    assert np.array_equal(np.asarray(o_chain), np.asarray(o_sep))


def test_matmul_activation_chain_parity():
    R, K, F, bm = 32, 64, 128, 16
    mm = matmul_1d_op(M=R, K=K, N=2 * F, dtype=jnp.float32, bm=bm)
    act = activation_op(R, 2 * F, F, silu_gate, dtype=jnp.float32, bm=bm)
    chain = stitch(mm, act, "h")
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, 2 * F)), jnp.float32)
    (h,) = _run(mm, x, w)
    (o_sep,) = _run(act, h)
    (o_chain,) = _run(chain, x, w)
    assert np.array_equal(np.asarray(o_chain), np.asarray(o_sep))


@pytest.mark.parametrize("bn", [None, 128, 256])
def test_gated_tiled_matmul_activation_matches_reference(bn):
    """A gated in-projection tiled over its columns (the [gate | up] weight
    read as two tile views) stitched onto the column-tiled activation: the
    chain equals the separate pair bitwise and silu(x@gate) * (x@up)."""
    R, K, F, bm = 16, 64, 512, 8
    mm = matmul_1d_op(M=R, K=K, N=2 * F, dtype=jnp.float32, bm=bm, bn=bn,
                      gated=True)
    act = activation_op(R, 2 * F, F, silu_gate, dtype=jnp.float32, bm=bm,
                        bn=bn)
    assert mm.grid == act.grid == (R // bm) * (F // (bn or F))
    chain = stitch(mm, act, "h")
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, 2 * F)) / 8, jnp.float32)
    (h,) = _run(mm, x, w, w)
    (o_sep,) = _run(act, h)
    (o_chain,) = _run(chain, x, w, w)
    assert np.array_equal(np.asarray(o_chain), np.asarray(o_sep))
    with jax.default_matmul_precision("highest"):
        want = jax.nn.silu(x @ w[:, :F]) * (x @ w[:, F:])
    np.testing.assert_allclose(np.asarray(o_chain), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dw_adamw_reshape_chain_parity():
    """The row-stream case: dW's (bm, N) blocks feed adamw's (bm*N/128,
    128) blocks through a row-major reshape — same elements per step."""
    d_in, K, d_out, bmm = 32, 64, 256, 16
    rows = d_in * d_out // LANES                       # 64, no padding
    bm_i = bmm * d_out // LANES                        # 32 -> equal grids
    dw = matmul_1d_op(M=d_in, K=K, N=d_out, dtype=jnp.float32, bm=bmm)
    upd = adamw_op(R=rows, dtype=jnp.float32, bm=bm_i)
    chain = stitch(dw, upd, "g")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(d_in, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, d_out)), jnp.float32)
    sc = (jnp.zeros((1, LANES), jnp.float32)
          .at[0, 0].set(1e-3).at[0, 1].set(0.1).at[0, 2].set(0.05))
    p = jnp.asarray(rng.normal(size=(rows, LANES)), jnp.float32)
    m = jnp.zeros((rows, LANES)), jnp.zeros((rows, LANES))
    m, v = m
    (g,) = _run(dw, x, w)
    sep = _run(upd, sc, p, g.reshape(rows, LANES), m, v)
    out = _run(chain, x, w, sc, p, m, v)
    for a, b in zip(out, sep):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# can_stitch rejection taxonomy
# ---------------------------------------------------------------------------
def test_can_stitch_rejections():
    norm = rmsnorm_op(R=32, d=128, dtype=jnp.float32, bm=16)
    mm = matmul_1d_op(M=32, K=128, N=128, dtype=jnp.float32, bm=16)
    assert can_stitch(norm, mm, "x") is None
    # grid mismatch
    mm8 = matmul_1d_op(M=32, K=128, N=128, dtype=jnp.float32, bm=8)
    assert "grid" in can_stitch(norm, mm8, "x")
    # dtype mismatch
    mmb = matmul_1d_op(M=32, K=128, N=128, dtype=jnp.bfloat16, bm=16)
    assert "dtype" in can_stitch(norm, mmb, "x")
    # unknown operand / wrong block shape for the named operand
    assert "no input named" in can_stitch(norm, mm, "nope")
    assert "block mismatch" in can_stitch(mm, mm, "x") or \
        can_stitch(mm, mm, "x") is not None
    # chains never cascade
    chain = stitch(norm, mm, "x")
    assert "cascade" in can_stitch(chain, mm, "x")
    # in-place consumer state can't be stitched
    upd = adamw_op(R=32, dtype=jnp.float32, bm=16)
    assert "in-place" in can_stitch(norm, upd, "p")
    # stitch() surfaces the reason
    with pytest.raises(ValueError, match="grid mismatch"):
        stitch(norm, mm8, "x")


def test_chain_cost_accounting():
    norm = rmsnorm_op(R=32, d=128, dtype=jnp.float32, bm=16)
    mm = matmul_1d_op(M=32, K=128, N=128, dtype=jnp.float32, bm=16)
    chain = stitch(norm, mm, "x")
    inter = 32 * 128 * 4                     # the eliminated intermediate
    assert chain.flops == norm.flops + mm.flops
    assert chain.hbm_bytes == norm.hbm_bytes + mm.hbm_bytes - 2 * inter
    # the live block rides VMEM instead
    assert chain.extra_vmem_bytes == norm.outputs[0].block_bytes()
    assert chain.vmem_bytes > mm.vmem_bytes
    assert chain.chain == (norm.name, mm.name)
    assert chain.in_names == ("x", "scale", "w")
    assert chain.out_names == ("out",)


# ---------------------------------------------------------------------------
# Planner contraction: graph-level legality
# ---------------------------------------------------------------------------
def _epilogue_graph(consumer_bm=16, extra_reader=False):
    norm = rmsnorm_op(R=32, d=128, dtype=jnp.float32, bm=16)
    mm = matmul_1d_op(M=32, K=128, N=128, dtype=jnp.float32, bm=consumer_bm)
    mm = dataclasses.replace(mm, name="mm")
    norm = dataclasses.replace(norm, name="norm",
                               epilogue=(mm.name, "x"))
    graph = [planner.GraphOp(norm),
             planner.GraphOp(mm, deps=frozenset({"norm"}))]
    if extra_reader:
        other = dataclasses.replace(
            rmsnorm_op(R=32, d=128, dtype=jnp.float32, bm=16), name="other")
        graph.append(planner.GraphOp(other, deps=frozenset({"norm"})))
    return graph


def test_planner_contracts_declared_epilogue():
    plan = planner.plan(_epilogue_graph(), max_ways=2)
    names = [m for d in plan.fused for m in d.members] + list(plan.singles)
    assert f"norm{CHAIN_SEP}mm" in names
    assert "norm" not in names and "mm" not in names


def test_planner_skips_contraction_with_second_reader():
    plan = planner.plan(_epilogue_graph(extra_reader=True), max_ways=2)
    names = [m for d in plan.fused for m in d.members] + list(plan.singles)
    assert "norm" in names and "mm" in names      # pair left unstitched
    assert not any(CHAIN_SEP in n for n in names)


def test_planner_falls_back_when_kernels_cannot_stitch():
    # grid mismatch: the declaration is advisory, the plan stays valid
    plan = planner.plan(_epilogue_graph(consumer_bm=8), max_ways=2)
    names = [m for d in plan.fused for m in d.members] + list(plan.singles)
    assert "norm" in names and "mm" in names
    assert not any(CHAIN_SEP in n for n in names)


def test_chain_renders_in_plan_summary():
    plan = planner.plan(_epilogue_graph(), max_ways=2)
    assert any(CHAIN_SEP in r["members"] for r in plan.summary())


# ---------------------------------------------------------------------------
# ScheduleCache regression: chain structure is part of the identity
# ---------------------------------------------------------------------------
def test_bundle_signature_distinguishes_chain_structure():
    norm = rmsnorm_op(R=32, d=128, dtype=jnp.float32, bm=16)
    mm = matmul_1d_op(M=32, K=128, N=128, dtype=jnp.float32, bm=16)
    chain = stitch(norm, mm, "x")
    # same name/operands/flops/bytes, chain markers stripped — the v2 bug
    # this guards against: a stitched bundle resolving an unstitched entry
    impostor = dataclasses.replace(chain, chain=(), extra_vmem_bytes=0)
    sig = bundle_signature([chain], vmem_budget=1 << 20)
    assert sig != bundle_signature([impostor], vmem_budget=1 << 20)
    # extra VMEM residency alone changes the tuning problem too
    fatter = dataclasses.replace(chain,
                                 extra_vmem_bytes=chain.extra_vmem_bytes * 2)
    assert sig != bundle_signature([fatter], vmem_budget=1 << 20)


def test_cache_version_bump_discards_v2_entries(tmp_path):
    path = tmp_path / "sched.json"
    import json
    path.write_text(json.dumps({
        "version": 2,
        "entries": {"deadbeef": {"members": ["a"], "ratios": [1],
                                 "variant": 0, "vmem_cap": None,
                                 "predicted_s": 1.0, "measured_s": None,
                                 "delta_pct": None, "mode": "costmodel"}},
        "meta": {"deadbeef": {"last_used": 1, "uses": 1}}, "clock": 1}))
    cache = ScheduleCache(path)
    assert len(cache) == 0, "pre-chain schedule survived the version bump"
