"""Chunked flash prefill: the PrefillBudget API, the blockwise
prefill-attention kernel, and the chunk-granular continuous engine.

Differential contract: with a small ``chunk_rows`` budget, prompts spanning
1, 2, and 5+ chunks are chipped away across iterations and the executed
engine stays token-for-token identical to the wavefront oracle (which
prefills whole prompts in one shot) — including mid-batch EOS retirement.
Structural contract: ``Program.fused_members`` shows every prefill chunk
co-resident with decode-side work — one with decode attention, one with the
stitched ``ffn_proj→decode_act`` epilogue chain.  Plus: the kernel's
online-softmax numerics vs a dense jnp reference at nonzero chunk offsets,
``reject_overlong=True`` restoring the legacy admission contract, and
DeprecationWarnings on the prefill_rows/prefill_chunk/pad_prefill_rows
aliases the budget replaced."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hfuse
from repro.core.stitch import CHAIN_SEP
from repro.kernels.prefill_attention import prefill_attention_op
from repro.models import lm
from repro.serve.engine import (PrefillBudget, Request, ServeEngine,
                                pad_prefill_rows)


def _cfg():
    return dataclasses.replace(get_config("granite-3-2b").reduced(),
                               dtype="float32")


# Prompt lengths span 1, 2, and 6 chunks at chunk_rows=8 (cache 128 ->
# effective chunk 8); budgets staggered so slots retire mid-run.
CHUNKED_LENS = (6, 15, 41)
CHUNKED_BUDGETS = (3, 4, 3)
BUDGET = PrefillBudget(chunk_rows=8, max_coresident_chunks=2)


def _requests(cfg, lens, budgets, eos=None, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=m, eos_token=eos)
            for i, (L, m) in enumerate(zip(lens, budgets))]


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    wave = ServeEngine(cfg, params, batch=2, max_len=48,
                       scheduling="wavefront")
    chunked = ServeEngine(cfg, params, batch=2, max_len=48,
                          scheduling="continuous", plan_fusion=True,
                          prefill_budget=BUDGET)
    assert chunked.executed, "reduced granite must support the executed path"
    return cfg, params, wave, chunked


# ---------------------------------------------------------------------------
# PrefillBudget unit contract
# ---------------------------------------------------------------------------
def test_budget_validates():
    for bad in (dict(chunk_rows=0), dict(max_coresident_chunks=0),
                dict(pad_to=-1)):
        with pytest.raises(ValueError, match="must be >= 1"):
            PrefillBudget(**bad)
    with pytest.raises(ValueError, match="policy"):
        PrefillBudget(policy="lifo")


def test_srpf_policy_lowers_admission_latency(setup):
    """Shortest-remaining-prefill-first: with one chunk of budget per step
    and a short prompt queued behind a long one, FIFO makes the short
    prompt wait out the long prefill's tail; SRPF admits it first.  Token
    streams stay identical to the wavefront oracle either way."""
    cfg, params, wave, _chunked = setup
    lens, buds = (41, 6), (3, 3)          # 6-chunk prompt, then a 1-chunk
    ref = _requests(cfg, lens, buds)
    wave.run(ref)
    stats = {}
    for policy in ("fifo", "srpf"):
        eng = ServeEngine(
            cfg, params, batch=2, max_len=48, scheduling="continuous",
            plan_fusion=True,
            prefill_budget=dataclasses.replace(
                BUDGET, max_coresident_chunks=1, policy=policy))
        rs = _requests(cfg, lens, buds)
        eng.run(rs)
        assert [r.out_tokens for r in rs] == [r.out_tokens for r in ref], \
            f"{policy} diverged from the wavefront oracle"
        stats[policy] = eng.stats
    assert (stats["srpf"].mean_admission_latency
            < stats["fifo"].mean_admission_latency), (
        stats["srpf"].admission_latencies,
        stats["fifo"].admission_latencies)


def test_budget_effective_chunk_divides_cache():
    assert PrefillBudget(chunk_rows=8).effective_chunk(128) == 8
    assert PrefillBudget(chunk_rows=2048).effective_chunk(128) == 128
    # rounds down to a divisor so chunk offsets stay chunk-aligned
    assert PrefillBudget(chunk_rows=24).effective_chunk(128) == 16
    assert PrefillBudget(chunk_rows=7).effective_chunk(128) == 4
    for rows, cache in ((8, 128), (24, 128), (100, 384)):
        c = PrefillBudget(chunk_rows=rows).effective_chunk(cache)
        assert c <= rows and cache % c == 0


def test_budget_effective_chunk_ragged_cache_lengths():
    """Direct largest-divisor computation (no O(cache_len) scan): exact on
    ragged cache lengths — primes, prime powers, highly-composite — and on
    the paged form, where the chunk must ALSO be a multiple of the KV block
    size so every chunk is a whole number of pages."""
    for rows, cache in ((8, 127), (50, 121), (36, 360), (17, 97),
                        (1, 4096), (5000, 3600), (64, 2 * 3 * 5 * 7 * 11)):
        got = PrefillBudget(chunk_rows=rows).effective_chunk(cache)
        brute = max(d for d in range(1, min(rows, cache) + 1)
                    if cache % d == 0)
        assert got == brute, (rows, cache, got, brute)
    # multiple=: chunk is the largest divisor of cache that is BOTH a
    # multiple of `multiple` and <= chunk_rows (floored up to `multiple`)
    for rows, cache, mult in ((8, 128, 16), (48, 96, 16), (40, 320, 8),
                              (16, 256, 16), (9, 144, 4)):
        got = PrefillBudget(chunk_rows=rows).effective_chunk(cache, mult)
        cands = [d for d in range(mult, cache + 1, mult)
                 if cache % d == 0 and d <= max(rows, mult)]
        assert got == (max(cands) if cands else mult), \
            (rows, cache, mult, got)
        assert got % mult == 0 and cache % got == 0
    with pytest.raises(ValueError, match="multiple"):
        PrefillBudget(chunk_rows=8).effective_chunk(100, 16)


def test_budget_pad_rows():
    b = PrefillBudget(pad_to=128)
    assert b.pad_rows(7) == 7            # raw below one tile
    assert b.pad_rows(128) == 128
    assert b.pad_rows(129) == 256        # next tile multiple beyond


# ---------------------------------------------------------------------------
# Kernel numerics: blockwise online softmax vs dense reference
# ---------------------------------------------------------------------------
def _ref_attn(q, k, v, off):
    C, H, D = q.shape
    S, Hkv, _ = k.shape
    rep = H // Hkv
    qf = q.astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("chrd,khd->chrk", qf.reshape(C, Hkv, rep, D),
                   k.astype(jnp.float32))
    kpos = jnp.arange(S)[None, None, None, :]
    qpos = off + jnp.arange(C)[:, None, None, None]
    s = jnp.where(kpos <= qpos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("chrk,khd->chrd", p, v.astype(jnp.float32))
    return o.reshape(C, H, D)


@pytest.mark.parametrize("C,S,Hkv,ck,off", [
    (8, 64, 2, 16, 0),       # multi-block grid, prefix-free chunk
    (8, 64, 2, 16, 23),      # chunk in the middle of a prefix (GQA rep=2)
    (8, 128, 4, 128, 40),    # grid-1: whole cache in one k/v block
    (5, 128, 4, 128, 0),     # ragged chunk rows (C below the lane tile)
])
def test_prefill_kernel_matches_reference(C, S, Hkv, ck, off):
    H, D = 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(C, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, Hkv, D)), jnp.float32)
    op = prefill_attention_op(C, S, H, Hkv, D, dtype=jnp.float32, ck=ck)
    offa = jnp.full((1, 1), off, jnp.int32)
    (o,) = hfuse.run_single(op, interpret=True)(
        offa, q.transpose(1, 0, 2), k, v)                  # head-major q/o
    np.testing.assert_allclose(np.asarray(o.transpose(1, 0, 2)),
                               np.asarray(_ref_attn(q, k, v, off)),
                               atol=1e-4, rtol=1e-4)


def test_prefill_op_shrinks_blockwise():
    op = prefill_attention_op(8, 128, 4, 4, 16, dtype=jnp.float32, ck=64)
    small = op.shrink(2)
    assert small is not None and small.grid == 4      # ck 64 -> 32
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)   # (H, C, D)
    k = jnp.asarray(rng.normal(size=(128, 4, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(128, 4, 16)), jnp.float32)
    offa = jnp.full((1, 1), 16, jnp.int32)
    o_big, *_ = hfuse.run_single(op, interpret=True)(offa, q, k, v)
    o_small, *_ = hfuse.run_single(small, interpret=True)(offa, q, k, v)
    np.testing.assert_allclose(np.asarray(o_small), np.asarray(o_big),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Structural: the hybrid mixed-iteration program — every prefill chunk rides
# a fused launch with decode-side work, and one of those partners is a
# stitched epilogue chain (vertical fusion INSIDE the horizontal bundle)
# ---------------------------------------------------------------------------
def test_program_fuses_chunks_with_decode_side_work(setup):
    _cfg_, _params, _wave, chunked = setup
    prog = chunked.build_decode_program(prefill_chunks=2)
    fused = prog.fused_members
    mixed = [ms for ms in fused
             if any(m.startswith("prefill_attn") for m in ms)
             and any(not m.startswith("prefill_attn") for m in ms)]
    # both chunks co-reside with decode-side work
    chunks_fused = {m for ms in mixed for m in ms
                    if m.startswith("prefill_attn")}
    assert len(chunks_fused) == 2, f"chunk not fused with decode: {fused}"
    # decode attention carries a chunk (the paper's heterogeneous pairing)
    assert any(any(m.startswith("decode_attn") for m in ms)
               for ms in mixed), f"decode attention rides alone: {fused}"
    # and a stitched producer→consumer chain rides a mixed launch too
    assert any(any(CHAIN_SEP in m for m in ms) for ms in mixed), \
        f"no stitched chain inside a mixed launch: {fused}"


# ---------------------------------------------------------------------------
# Differential: chunked admission == wavefront oracle, token for token
# ---------------------------------------------------------------------------
def test_chunked_matches_wavefront(setup):
    cfg, _params, wave, chunked = setup
    rw = _requests(cfg, CHUNKED_LENS, CHUNKED_BUDGETS)
    rc = _requests(cfg, CHUNKED_LENS, CHUNKED_BUDGETS)
    wave.run(rw)
    chunked.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    st = chunked.stats
    # every prompt admitted chunk-by-chunk: 1 + 2 + 6 chunks of 8 rows
    assert st.prefill_chunks == sum(-(-L // 8) for L in CHUNKED_LENS)
    # the 41-token prompt needed >= 2 iterations of chipping (6 chunks,
    # one per iteration while its slot prefills)
    assert max(st.admission_latencies) >= 5
    assert st.mixed_steps > 0, "no chunk ever rode a decode step"
    assert st.fused_prefill_fraction > 0.0
    assert st.tokens == sum(len(r.out_tokens) for r in rc)


def test_chunked_eos_finishes_mid_batch(setup):
    cfg, _params, wave, chunked = setup
    probe = _requests(cfg, CHUNKED_LENS, CHUNKED_BUDGETS)
    wave.run(probe)
    eos = probe[1].out_tokens[1]          # fires after 2 of its 4 tokens
    rw = _requests(cfg, CHUNKED_LENS, CHUNKED_BUDGETS, eos=eos)
    rc = _requests(cfg, CHUNKED_LENS, CHUNKED_BUDGETS, eos=eos)
    wave.run(rw)
    chunked.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    assert any(reason == "eos" for _s, _r, reason
               in chunked.stats.retirements)
    assert len(rc[1].out_tokens) < CHUNKED_BUDGETS[1]


def test_reject_overlong_restores_legacy_contract(setup):
    cfg, params, _wave, _chunked = setup
    eng = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous", prefill_budget=BUDGET,
                      reject_overlong=True)
    ok = _requests(cfg, (6,), (2,))
    eng.run(ok)                           # one chunk: still admitted
    assert len(ok[0].out_tokens) == 2
    bad = _requests(cfg, (15,), (2,))
    with pytest.raises(ValueError, match="per-iteration prefill budget"):
        eng.run(bad)


# ---------------------------------------------------------------------------
# Deprecated aliases still work, loudly
# ---------------------------------------------------------------------------
def test_pad_prefill_rows_alias_warns():
    with pytest.warns(DeprecationWarning, match="PrefillBudget.pad_rows"):
        assert pad_prefill_rows(129) == PrefillBudget().pad_rows(129) == 256


def test_decode_graph_prefill_rows_alias_warns(setup):
    _cfg_, _params, _wave, chunked = setup
    with pytest.warns(DeprecationWarning, match="prefill_rows"):
        graph = chunked.decode_graph(prefill_rows=128)
    assert any(g.op.name == "prefill_ffn" for g in graph)


def test_plan_decode_fusion_prefill_chunk_alias_warns(setup):
    _cfg_, _params, _wave, chunked = setup
    with pytest.warns(DeprecationWarning, match="prefill_chunk"):
        plan = chunked.plan_decode_fusion(prefill_chunk=8)
    names = [m for d in plan.fused for m in d.members] + list(plan.singles)
    assert any(n.startswith("prefill_attn") for n in names)


def test_build_decode_program_prefill_rows_alias_warns(setup):
    _cfg_, _params, _wave, chunked = setup
    with pytest.warns(DeprecationWarning, match="prefill_rows"):
        prog = chunked.build_decode_program(prefill_rows=128)
    assert any(any(m == "prefill_ffn" for m in s.members)
               for s in prog.steps)
