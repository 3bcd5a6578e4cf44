"""Paged KV-cache serve path (serve/kv_pool.py + the ``block_table=``
kernels): the executed continuous engine with ``paged_kv=True``.

Differential contract: the paged engine stays token-for-token identical
to the contiguous executed engine (itself pinned to the wavefront oracle)
on mixed-length traces and mid-batch EOS retirement — the block-table
indirection is pure data movement.  Capability contract: a shared-prefix
trace runs STRICTLY fewer prefill chunks at identical tokens (the prefix
cache skips whole chunks), and a prompt longer than ``max_len`` is served
once ``kv_slot_blocks`` raises the logical capacity — the per-engine
``max_len`` ceiling is gone.  Structural contract: the fused decode
launch carries the paged prefill chunk ⊕ paged decode attention, both
with the block table bound as a real operand ("bt" in in_names).
Plus: ``max_len`` immutability (``cache_len`` exposes the aligned/paged
capacity instead of mutating the user's value), constructor validation,
and graceful degradation when the arena is undersized."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import PrefillBudget, Request, ServeEngine

PG = dict(paged_kv=True, kv_block_size=16)
BUDGET = PrefillBudget(chunk_rows=8, max_coresident_chunks=2)
# chunk_rows=16 makes the effective chunk 16 on BOTH paths (contiguous
# and paged, whose chunk must be a block multiple) — chunk counts compare
# apples to apples in the shared-prefix test
BUDGET16 = PrefillBudget(chunk_rows=16, max_coresident_chunks=2)
LENS = (6, 15, 41, 9)
BUDGETS = (3, 4, 3, 2)


def _cfg():
    return dataclasses.replace(get_config("granite-3-2b").reduced(),
                               dtype="float32")


def _requests(cfg, lens, budgets, eos=None, prefix=0, seed=11):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, prefix).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate([
                        shared,
                        rng.integers(1, cfg.vocab_size, L).astype(np.int32)]),
                    max_new_tokens=m, eos_token=eos)
            for i, (L, m) in enumerate(zip(lens, budgets))]


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    contig = ServeEngine(cfg, params, batch=2, max_len=48,
                         scheduling="continuous", plan_fusion=True,
                         prefill_budget=BUDGET)
    paged = ServeEngine(cfg, params, batch=2, max_len=48,
                        scheduling="continuous", plan_fusion=True,
                        prefill_budget=BUDGET, **PG)
    assert contig.executed and paged.executed
    return cfg, params, contig, paged


# ---------------------------------------------------------------------------
# Constructor contract: max_len immutability, cache_len, validation
# ---------------------------------------------------------------------------
def test_max_len_stays_immutable_cache_len_exposes_capacity(setup):
    cfg, params, contig, paged = setup
    # the executed engine used to silently mutate max_len to the
    # 128-aligned cache size; now the user's value survives and the
    # aligned capacity lives in cache_len
    assert contig.max_len == 48 and contig.cache_len == 128
    assert paged.max_len == 48 and paged.cache_len == 128
    big = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=BUDGET, kv_slot_blocks=16, **PG)
    assert big.max_len == 48 and big.cache_len == 256
    # non-executed engines never aligned: cache_len == max_len
    plain = ServeEngine(cfg, params, batch=2, max_len=48)
    assert plain.cache_len == plain.max_len == 48


def test_paged_constructor_validation(setup):
    cfg, params, _contig, _paged = setup
    with pytest.raises(ValueError, match="plan_fusion"):
        ServeEngine(cfg, params, batch=2, max_len=48, paged_kv=True)
    with pytest.raises(ValueError, match="must divide"):
        ServeEngine(cfg, params, batch=2, max_len=48,
                    scheduling="continuous", plan_fusion=True,
                    paged_kv=True, kv_block_size=12)
    with pytest.raises(ValueError, match="multiple of 128"):
        ServeEngine(cfg, params, batch=2, max_len=48,
                    scheduling="continuous", plan_fusion=True,
                    kv_slot_blocks=9, **PG)


# ---------------------------------------------------------------------------
# Structural: the fused launch binds the block table on both kernels
# ---------------------------------------------------------------------------
def test_fused_launch_carries_paged_ops_with_block_table(setup):
    _cfg_, _params, _contig, paged = setup
    graph = paged.decode_graph(prefill_chunks=1)
    att = [g.op for g in graph if g.op.name.startswith("decode_attn")]
    pf = [g.op for g in graph if g.op.name.startswith("prefill_attn")]
    assert att and pf
    for op in att + pf:
        assert op.name.endswith("_pg16"), op.name
        assert "bt" in op.in_names, (op.name, op.in_names)
    prog = paged.build_decode_program(prefill_chunks=1)
    mixed = [ms for ms in prog.fused_members
             if any(m.startswith("prefill_attn") for m in ms)
             and any(not m.startswith("prefill_attn") for m in ms)]
    assert mixed, f"paged chunk not fused with decode work: " \
                  f"{prog.fused_members}"


# ---------------------------------------------------------------------------
# Differential: paged == contiguous executed engine, token for token
# ---------------------------------------------------------------------------
def test_paged_matches_contiguous_mixed_lengths(setup):
    cfg, _params, contig, paged = setup
    rc = _requests(cfg, LENS, BUDGETS)
    rp = _requests(cfg, LENS, BUDGETS)
    contig.run(rc)
    paged.run(rp)
    assert [r.out_tokens for r in rp] == [r.out_tokens for r in rc]
    st = paged.stats
    assert st.blocks_in_use > 0
    assert st.fused_prefill_fraction > 0.0


def test_paged_matches_contiguous_mid_batch_eos(setup):
    cfg, _params, contig, paged = setup
    probe = _requests(cfg, LENS, BUDGETS)
    contig.run(probe)
    eos = probe[1].out_tokens[1]          # fires after 2 of its 4 tokens
    rc = _requests(cfg, LENS, BUDGETS, eos=eos)
    rp = _requests(cfg, LENS, BUDGETS, eos=eos)
    contig.run(rc)
    paged.run(rp)
    assert [r.out_tokens for r in rp] == [r.out_tokens for r in rc]
    assert any(reason == "eos" for _s, _r, reason in paged.stats.retirements)


# ---------------------------------------------------------------------------
# Capability: prefix cache drops whole chunks; max_len ceiling is gone
# ---------------------------------------------------------------------------
def test_shared_prefix_runs_strictly_fewer_chunks(setup):
    cfg, params, _contig, _paged = setup
    kw = dict(batch=2, max_len=64, scheduling="continuous",
              plan_fusion=True, prefill_budget=BUDGET16)
    contig = ServeEngine(cfg, params, **kw)
    paged = ServeEngine(cfg, params, **kw, **PG)
    lens, buds = (7, 9, 5, 11), (3, 3, 3, 3)
    rc = _requests(cfg, lens, buds, prefix=32)
    rp = _requests(cfg, lens, buds, prefix=32)
    contig.run(rc)
    paged.run(rp)
    assert [r.out_tokens for r in rp] == [r.out_tokens for r in rc]
    st = paged.stats
    assert st.prefill_chunks < contig.stats.prefill_chunks, \
        (st.prefill_chunks, contig.stats.prefill_chunks)
    assert st.prefix_hits >= 2 and st.prefix_hit_rate > 0
    assert st.prefix_tokens_reused >= 2 * 32


def test_prefix_cache_survives_across_runs(setup):
    cfg, params, _contig, _paged = setup
    eng = ServeEngine(cfg, params, batch=2, max_len=64,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=BUDGET16, **PG)
    lens, buds = (7, 9), (2, 2)
    eng.run(_requests(cfg, lens, buds, prefix=32))
    first = eng.stats.prefix_hits
    # same prompts again: EVERY admission now hits the persistent pool
    eng.run(_requests(cfg, lens, buds, prefix=32))
    assert eng.stats.prefix_hits == 2 and eng.stats.prefix_hits >= first


def test_prompt_longer_than_max_len_serves_when_paged(setup):
    cfg, params, _contig, _paged = setup
    kw = dict(batch=2, max_len=48, scheduling="continuous",
              plan_fusion=True, prefill_budget=BUDGET)
    long_req = lambda: _requests(cfg, (150,), (3,))
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        ServeEngine(cfg, params, **kw).run(long_req())
    # same max_len, but kv_slot_blocks raises the logical capacity to 256
    eng = ServeEngine(cfg, params, **kw, kv_slot_blocks=16, **PG)
    reqs = long_req()
    eng.run(reqs)
    assert len(reqs[0].out_tokens) == 3


# ---------------------------------------------------------------------------
# Degradation: an undersized arena retires instead of crashing or hanging
# ---------------------------------------------------------------------------
def test_tight_pool_completes_gracefully(setup):
    cfg, params, _contig, _paged = setup
    eng = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=BUDGET, kv_blocks=8, **PG)
    reqs = _requests(cfg, (41, 41, 41), (3, 3, 3), seed=3)
    eng.run(reqs)                         # must terminate
    served = [r for r in reqs if len(r.out_tokens) == 3]
    starved = {_r for _s, _r, reason in eng.stats.retirements
               if reason == "pool_full"}
    assert len(served) + len(starved) >= 3, \
        (eng.stats.retirements, [len(r.out_tokens) for r in reqs])


def test_paged_steps_keep_their_form(setup):
    """The paged arena is not carried in place: no step counts as such."""
    cfg, _params, _contig, paged = setup
    paged.run(_requests(cfg, (6, 20), (3, 2), seed=29))
    assert not paged.kv_in_place
    assert paged.stats.steps > 0 and paged.stats.kv_in_place_steps == 0
