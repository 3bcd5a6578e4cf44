"""MoE on the executed serve path: the router projection and the grouped
expert GMM run as planner ops (serve/engine.decode_graph), with the
top-k/softmax/dispatch/combine glue in binding slots — token-for-token
identical to the hand-wired vmapped fallback, the expert GMM co-resident
in a fused launch, and the three ISSUE-named bugs pinned by regression
tests: the wavefront co-prefill partner width (cfg.d_ff vs the expert
FFN width), the moe_gmm_op capacity/block-divisibility crash, and the
capacity() truncation to 0 at B=1 decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotuner
from repro.models import lm
from repro.models import moe as moe_mod
from repro.serve.engine import (PrefillBudget, Request, ServeEngine,
                                executable_decode_supported)


def _cfg(**over):
    cfg = dataclasses.replace(get_config("phi3.5-moe-rms").reduced(),
                              dtype="float32")
    return dataclasses.replace(cfg, **over) if over else cfg


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
    budget = PrefillBudget(chunk_rows=8, max_coresident_chunks=2)
    exe = ServeEngine(cfg, params, batch=3, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=budget)
    fb = ServeEngine(cfg, params, batch=3, max_len=48,
                     scheduling="continuous", prefill_budget=budget)
    return cfg, params, exe, fb


# ---------------------------------------------------------------------------
# The fence is down: MoE is executor-supported and plans router + GMM
# ---------------------------------------------------------------------------
def test_moe_executable_and_planned(setup):
    cfg, _params, exe, fb = setup
    assert executable_decode_supported(cfg) is None
    assert exe.executed and not fb.executed
    names = [g.op.name for g in exe.decode_graph()]
    assert "moe_router" in names
    assert any(n.startswith("moe_gmm") for n in names)
    # the faithful LayerNorm phi3.5 variant still falls back (norm fence)
    ln = get_config("phi3.5-moe-42b-a6.6b").reduced()
    assert executable_decode_supported(ln) is not None


def test_moe_gmm_co_resident_in_fused_launch(setup):
    cfg, _params, exe, _fb = setup
    prog = exe.build_decode_program(prefill_chunks=2)
    bundles = [ms for ms in prog.fused_members
               if any(m.startswith("moe_gmm") for m in ms)]
    assert bundles and all(len(ms) > 1 for ms in bundles), \
        f"expert GMM not co-resident in any fused launch: {prog.describe()}"


# ---------------------------------------------------------------------------
# Differential parity: executed == vmapped fallback, token for token
# ---------------------------------------------------------------------------
PROMPT_SETS = [
    ((6, 9, 7, 12), (3, 5, 2, 4)),
    ((8, 8, 8, 8, 8), (2, 6, 3, 3, 5)),
    ((10, 5, 20, 6, 9, 7), (4, 4, 1, 6, 2, 3)),   # 20 spans 3 chunks
]


@pytest.mark.parametrize("lens,budgets", PROMPT_SETS)
def test_moe_executed_matches_fallback(setup, lens, budgets):
    cfg, _params, exe, fb = setup
    re_ = _requests(cfg, lens, budgets)
    rf = _requests(cfg, lens, budgets)
    exe.run(re_)
    fb.run(rf)
    assert [r.out_tokens for r in re_] == [r.out_tokens for r in rf]
    st = exe.stats
    assert st.tokens == sum(len(r.out_tokens) for r in re_)
    # expert stats really accumulated, and conserve routed slot-tokens:
    # every decoding slot routes to exactly top_k experts per layer-step
    # (capacity >= B * top_k at this scale, so nothing is ever dropped)
    n_layers = lm.layer_runs(cfg)[0].count
    assert sum(st.expert_hits) == \
        cfg.moe.top_k * st.slot_steps * n_layers


def test_moe_stacked_kv_in_place_matches_fallback():
    """Two stacked MoE layers: the scan carries the cache in place beside
    the expert-hit accumulator, and the tokens are the vmapped
    fallback's, token for token."""
    cfg = _cfg(num_layers=2, block_pattern=("attn", "attn"))
    assert lm.layer_runs(cfg)[0].count == 2
    params = lm.init(cfg, jax.random.PRNGKey(2))
    # embedding at std 1/d, so the tokens depend on what attention reads
    params = {**params, "embed": {"embedding": params["embed"]["embedding"]
                                  / cfg.d_model}}
    budget = PrefillBudget(chunk_rows=8, max_coresident_chunks=2)
    exe = ServeEngine(cfg, params, batch=3, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=budget)
    fb = ServeEngine(cfg, params, batch=3, max_len=48,
                     scheduling="continuous", prefill_budget=budget)
    assert exe.executed and exe.kv_in_place
    # prompts of at most 10 rows: the fallback routes a whole prompt at
    # once, and at 20 rows its per-expert capacity (16) drops tokens the
    # 8-row chunks (capacity 8) keep
    lens, budgets = (10, 5, 9, 6), (4, 4, 3, 5)
    re_ = exe.run(_requests(cfg, lens, budgets))
    rf = fb.run(_requests(cfg, lens, budgets))
    assert [r.out_tokens for r in re_] == [r.out_tokens for r in rf]
    st = exe.stats
    assert st.kv_in_place_steps == st.steps > 0
    assert sum(st.expert_hits) == cfg.moe.top_k * st.slot_steps * 2


def test_moe_mid_batch_eos(setup):
    cfg, _params, exe, fb = setup
    lens, budgets = (6, 9, 7, 12), (6, 6, 6, 6)
    # probe run picks a token that really appears mid-stream, then both
    # engines must cut that request at the same position
    probe = _requests(cfg, lens, budgets)
    exe.run(probe)
    eos = probe[1].out_tokens[1]
    re_ = _requests(cfg, lens, budgets, eos=eos)
    rf = _requests(cfg, lens, budgets, eos=eos)
    exe.run(re_)
    fb.run(rf)
    assert [r.out_tokens for r in re_] == [r.out_tokens for r in rf]
    assert any(reason == "eos" for _s, _r, reason in exe.stats.retirements)


def test_moe_warm_cache_zero_new_searches(tmp_path):
    from repro.core.schedule_cache import ScheduleCache
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    budget = PrefillBudget(chunk_rows=8, max_coresident_chunks=2)
    sched = ScheduleCache(tmp_path / "sched.json")
    kw = dict(batch=3, max_len=48, scheduling="continuous",
              plan_fusion=True, prefill_budget=budget, schedule_cache=sched)
    ServeEngine(cfg, params, **kw).run(_requests(cfg, (6, 9, 7), (3, 3, 3)))
    n = autotuner.SEARCH_COUNT
    eng = ServeEngine(cfg, params, **kw)
    eng.run(_requests(cfg, (6, 9, 7), (3, 3, 3)))
    assert autotuner.SEARCH_COUNT == n, \
        "warm-cache MoE replan re-searched a bundle"
    assert eng.executed


# ---------------------------------------------------------------------------
# Load-aware admission: eload sheds a coresident chunk under expert skew
# ---------------------------------------------------------------------------
def test_moe_eload_sheds_under_skew():
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    # zero the router: logits all equal, top_k tie-breaks to experts 0 and
    # 1 for EVERY token — skew is exactly E/top_k = 2.0, deterministically
    # above the 1.5 threshold.  Prompt length == chunk == 8 keeps both
    # paths routing identical token groups (no capacity drops), so parity
    # still holds under the pathological router.
    run = lm.layer_runs(cfg)[0]
    blk = dict(params[run.name])
    moe_p = dict(blk["moe"])
    moe_p["router"] = jnp.zeros_like(moe_p["router"])
    blk["moe"] = moe_p
    params = dict(params)
    params[run.name] = blk
    budget = PrefillBudget(chunk_rows=4, max_coresident_chunks=2,
                           policy="eload", skew_threshold=1.5)
    reqs = lambda: _requests(cfg, (8, 8, 8, 8, 8, 8), (4, 4, 4, 4, 4, 4))
    eng = ServeEngine(cfg, params, batch=4, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=budget)
    out = reqs()
    eng.run(out)
    st = eng.stats
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    # all hits on experts 0/1, none elsewhere — skew pinned at E/K
    assert st.expert_hits[2:] == [0] * (E - 2)
    assert st.expert_skew == pytest.approx(E / K)
    assert st.load_shed_steps >= 1, \
        "eload never shed a coresident chunk despite 2.0 skew"
    # shedding changes scheduling, never tokens: the fallback agrees
    fb = ServeEngine(cfg, params, batch=4, max_len=48,
                     scheduling="continuous", prefill_budget=budget)
    ref = reqs()
    fb.run(ref)
    assert [r.out_tokens for r in out] == [r.out_tokens for r in ref]


def test_eload_budget_validation():
    assert PrefillBudget(policy="eload").skew_threshold == 1.5
    with pytest.raises(ValueError):
        PrefillBudget(policy="eload", skew_threshold=0.5)
    with pytest.raises(ValueError):
        PrefillBudget(policy="nope")


# ---------------------------------------------------------------------------
# Bugfix 1: wavefront co-prefill partner width is the EXPERT FFN width
# ---------------------------------------------------------------------------
def test_wavefront_partner_width_is_expert_ffn():
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous")
    graph = eng.decode_graph(ffn_rows=16)
    pf = next(g.op for g in graph if g.op.name == "prefill_ffn")
    m = cfg.moe
    want = 2 * m.d_ff_expert if cfg.activation in ("silu", "gelu") \
        else m.d_ff_expert
    assert pf.inputs[1].shape == (cfg.d_model, want), \
        f"partner is {pf.inputs[1].shape}, not the (gated) expert FFN " \
        f"in-projection (d, {want}) — the cfg.d_ff regression"
    # dense configs keep the dense width
    dcfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                               dtype="float32")
    dparams = lm.init(dcfg, jax.random.PRNGKey(0))
    deng = ServeEngine(dcfg, dparams, batch=2, max_len=48,
                       scheduling="continuous")
    dpf = next(g.op for g in deng.decode_graph(ffn_rows=16)
               if g.op.name == "prefill_ffn")
    dwant = 2 * dcfg.d_ff if dcfg.activation in ("silu", "gelu") \
        else dcfg.d_ff
    assert dpf.inputs[1].shape == (dcfg.d_model, dwant)


# ---------------------------------------------------------------------------
# Bugfix 2: moe_gmm_op clamps bc to a divisor of C (small capacities build)
# ---------------------------------------------------------------------------
def test_moe_gmm_op_small_capacity_builds():
    from repro.kernels.moe_gmm import moe_gmm, moe_gmm_op
    # C=8 against the default bc=128 used to fail `assert C % bc == 0`
    op = moe_gmm_op(E=4, C=8, d=32, f=16, dtype=jnp.float32)
    assert op.inputs[0].block_shape == (1, 8, 32)
    assert op.grid == 4
    # non-power-of-two: bc rounds DOWN to a divisor (12 % 8 != 0 -> 6)
    op = moe_gmm_op(E=2, C=12, d=32, f=16, dtype=jnp.float32, bc=8)
    bc = op.outputs[0].block_shape[1]
    assert 12 % bc == 0 and bc <= 8 and op.grid == 2 * (12 // bc)
    # operand signature is stable for the BindingRegistry
    assert op.in_names == ("xe", "w_in", "w_out")
    assert op.out_names == ("ye",)
    # numerics: the op body matches the reference pallas kernel and the
    # jnp einsum substrate on a small gated case
    rng = np.random.default_rng(0)
    E, C, d, f = 4, 8, 32, 16
    xe = jnp.asarray(rng.standard_normal((E, C, d)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((E, d, 2 * f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    ref = moe_gmm(xe, w_in, w_out, act="silu", interpret=True)
    cfg = _cfg()
    got = moe_mod.expert_ffn(cfg, {"w_in": w_in, "w_out": w_out}, xe)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Bugfix 3: capacity() floors at 1 before block alignment (B=1 decode)
# ---------------------------------------------------------------------------
def test_capacity_floors_at_one_token():
    cfg = _cfg()   # 4 experts top-2, capacity_factor 1.25
    # B=1 decode: int(1 * 2/4 * 1.25) == 0 before the fix
    assert moe_mod.capacity(cfg, 1) >= 1
    assert moe_mod.capacity(cfg, 1) % 8 == 0          # GMM block aligned
    assert moe_mod.capacity(cfg, 1, block=1) == 1     # the raw floor
    # routing a single token must land it (not drop everything)
    r = moe_mod.route_from_logits(
        cfg, jnp.asarray([[0.1, 0.5, 0.2, 0.3]], jnp.float32))
    assert int((r.dispatch_idx == 0).sum()) == cfg.moe.top_k


def test_moe_b1_decode_executed_matches_fallback():
    cfg = _cfg()
    params = lm.init(cfg, jax.random.PRNGKey(0))
    budget = PrefillBudget(chunk_rows=8, max_coresident_chunks=1)
    exe = ServeEngine(cfg, params, batch=1, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=budget)
    assert exe.executed
    fb = ServeEngine(cfg, params, batch=1, max_len=48,
                     scheduling="continuous", prefill_budget=budget)
    re_ = _requests(cfg, (7, 5), (4, 3))
    rf = _requests(cfg, (7, 5), (4, 3))
    exe.run(re_)
    fb.run(rf)
    assert [r.out_tokens for r in re_] == [r.out_tokens for r in rf]


# ---------------------------------------------------------------------------
# Fences: paths the MoE executed program does not (yet) cover say so
# ---------------------------------------------------------------------------
def test_moe_fenced_paths(setup):
    import types
    cfg, params, _exe, _fb = setup
    # wavefront scheduling serves MoE on the fallback, not the executor
    wf = ServeEngine(cfg, params, batch=2, max_len=48,
                     scheduling="wavefront", plan_fusion=True)
    assert not wf.executed
    # paged KV + MoE is rejected up front (no paged fallback exists)
    with pytest.raises(ValueError, match="MoE"):
        ServeEngine(cfg, params, batch=2, max_len=48,
                    scheduling="continuous", plan_fusion=True,
                    paged_kv=True, kv_block_size=16)
    # tensor-parallel MoE serve is explicitly rejected (expert-major
    # weights are not head/column-sharded)
    fake_mesh = types.SimpleNamespace(shape={"model": 2})
    with pytest.raises(ValueError, match="expert"):
        ServeEngine(cfg, params, batch=2, max_len=48,
                    scheduling="continuous", plan_fusion=True,
                    mesh=fake_mesh, shard_axis="model")
