"""Differential serving harness: the continuous-batching engine must be
token-for-token identical to the legacy wavefront engine on mixed-length
prompt sets (greedy decode, interpret mode) — including requests that
finish mid-batch (EOS and budget) and slots refilled by co-prefill — plus
slot-manager edge cases: same-step mass retirement, overlong-prompt
rejection, cache-full truncation, deterministic refill order, and the
zero-new-searches replan contract for the executed continuous programs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotuner
from repro.core.schedule_cache import ScheduleCache
from repro.models import lm
from repro.serve.engine import Request, ServeEngine, ServeStats


def _cfg():
    return dataclasses.replace(get_config("granite-3-2b").reduced(),
                               dtype="float32")


# Three mixed-length prompt sets: (prompt lengths, token budgets).  Budgets
# are staggered so slots retire (and refill) mid-batch, never in lock-step.
PROMPT_SETS = [
    ((6, 9, 7, 12), (3, 5, 2, 4)),
    ((8, 8, 8, 8, 8), (2, 6, 3, 3, 5)),        # same length, ragged budgets
    ((10, 5, 12, 6, 9, 7), (4, 4, 1, 6, 2, 3)),
]


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
    cont = ServeEngine(cfg, params, batch=2, max_len=48,
                       scheduling="continuous")
    return cfg, params, wave, cont


@pytest.fixture(scope="module")
def executed_engine(setup):
    cfg, params, _, _ = setup
    eng = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous", plan_fusion=True)
    assert eng.executed, "reduced granite must support the executed decode"
    return eng


# ---------------------------------------------------------------------------
# Differential parity: continuous == wavefront, token for token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lens,budgets", PROMPT_SETS)
def test_continuous_matches_wavefront(setup, lens, budgets):
    cfg, _params, wave, cont = setup
    rw = _requests(cfg, lens, budgets)
    rc = _requests(cfg, lens, budgets)
    wave.run(rw)
    cont.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    st = cont.stats
    # the slot manager really ran continuous: retirements mid-run refilled
    # slots (admissions spread over multiple steps, not one wavefront)
    assert len(st.admissions) == len(lens)
    assert len({step for step, _rid, _s in st.admissions}) > 1
    assert st.tokens == sum(len(r.out_tokens) for r in rc)
    assert 0.0 < st.occupancy <= 1.0


@pytest.mark.parametrize("lens,budgets", PROMPT_SETS)
def test_executed_continuous_matches_wavefront(setup, executed_engine,
                                               lens, budgets):
    """The planned-and-executed continuous engine (per-slot (B,) positions
    bound into the vectorized decode-attention kernel, refills co-prefilled
    through the fused launch) matches the hand-wired wavefront oracle."""
    cfg, _params, wave, _ = setup
    rw = _requests(cfg, lens, budgets)
    rc = _requests(cfg, lens, budgets)
    wave.run(rw)
    executed_engine.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    st = executed_engine.stats
    assert st.mixed_steps > 0, "no refill ever rode a decode step"
    # the mixed program really fused the prefill chunk with decode attention
    assert st.fused_mixed_steps == st.mixed_steps


def test_eos_finishes_mid_batch(setup):
    """A request retiring on EOS mid-batch frees its slot for refill and
    both engines agree on every stream."""
    cfg, _params, wave, cont = setup
    lens, budgets = PROMPT_SETS[0]
    probe = _requests(cfg, lens, budgets)
    wave.run(probe)
    eos = probe[1].out_tokens[1]          # fires after 2 of its 5 tokens
    rw = _requests(cfg, lens, budgets, eos=eos)
    rc = _requests(cfg, lens, budgets, eos=eos)
    wave.run(rw)
    cont.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    assert any(reason == "eos" for _s, _r, reason in cont.stats.retirements)
    assert len(rc[1].out_tokens) < budgets[1]


# ---------------------------------------------------------------------------
# Slot-manager edge cases
# ---------------------------------------------------------------------------
def test_all_slots_retire_same_step(setup):
    """Budgets tuned so both slots hit their limit on the same iteration;
    the manager refills both (one per step, deterministically) and the
    streams still match the oracle."""
    cfg, _params, wave, cont = setup
    lens, budgets = (7, 7, 7, 7), (3, 2, 2, 2)   # admits at steps 0,1 ->
    rw = _requests(cfg, lens, budgets)           # both retire at step 2
    rc = _requests(cfg, lens, budgets)
    wave.run(rw)
    cont.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    by_step: dict[int, int] = {}
    for step, _rid, _reason in cont.stats.retirements:
        by_step[step] = by_step.get(step, 0) + 1
    assert max(by_step.values()) == cont.batch, \
        f"no step retired the whole batch: {cont.stats.retirements}"


def test_overlong_prompt_rejected(setup):
    cfg, _params, _wave, cont = setup
    bad = _requests(cfg, (cont.max_len + 1,), (2,))
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        cont.run(bad)


def test_cache_full_retires_with_truncation(setup):
    """When a slot's position reaches max_len the request is retired
    (reason max_len) instead of writing past the cache."""
    cfg, params, _wave, _cont = setup
    eng = ServeEngine(cfg, params, batch=2, max_len=12,
                      scheduling="continuous")
    reqs = _requests(cfg, (10, 4), (8, 3))
    eng.run(reqs)
    # slot 0: admitted at pos 10, 1 prompt token + 2 decodes fill the cache
    assert len(reqs[0].out_tokens) == 12 - 10 + 1
    assert any(reason == "max_len" for _s, _r, reason
               in eng.stats.retirements)
    assert len(reqs[1].out_tokens) == 3          # unaffected neighbour


def test_refill_order_deterministic(setup):
    """Identical arrival queues produce identical admission schedules
    (step, rid, slot) and identical streams across runs."""
    cfg, _params, _wave, cont = setup
    lens, budgets = PROMPT_SETS[2]
    r1 = _requests(cfg, lens, budgets)
    r2 = _requests(cfg, lens, budgets)
    cont.run(r1)
    first = list(cont.stats.admissions)
    cont.run(r2)
    assert cont.stats.admissions == first
    assert [r.out_tokens for r in r1] == [r.out_tokens for r in r2]
    # FIFO admission: rids admitted in arrival order
    assert [rid for _s, rid, _slot in first] == sorted(
        rid for _s, rid, _slot in first)


def test_delayed_arrivals_idle_then_admit(setup):
    """Requests arriving after step 0 are not admitted early; the engine
    idles until the arrival step and the streams still match the oracle."""
    cfg, _params, wave, cont = setup
    rw = _requests(cfg, (6, 9), (3, 3))
    rc = _requests(cfg, (6, 9), (3, 3))
    rc[1].arrival = 4
    wave.run(rw)
    cont.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    admit = {rid: step for step, rid, _slot in cont.stats.admissions}
    assert admit[1] >= 4


# ---------------------------------------------------------------------------
# Executed-path contracts
# ---------------------------------------------------------------------------
def test_continuous_replan_zero_searches(setup, tmp_path):
    """A second engine over the same schedule cache re-plans every program
    (the steady mixed graph for every refill length plus the pure-decode
    step) with ZERO new autotuner searches."""
    cfg, params, wave, _ = setup
    lens, budgets = PROMPT_SETS[0]
    cache = ScheduleCache(tmp_path / "sched.json")
    e1 = ServeEngine(cfg, params, batch=2, max_len=48,
                     scheduling="continuous", plan_fusion=True,
                     schedule_cache=cache)
    e1.run(_requests(cfg, lens, budgets))
    n = autotuner.SEARCH_COUNT
    e2 = ServeEngine(cfg, params, batch=2, max_len=48,
                     scheduling="continuous", plan_fusion=True,
                     schedule_cache=cache)
    r2 = _requests(cfg, lens, budgets)
    e2.run(r2)
    assert autotuner.SEARCH_COUNT == n, "replan re-searched a bundle"
    rw = _requests(cfg, lens, budgets)
    wave.run(rw)
    assert [r.out_tokens for r in r2] == [r.out_tokens for r in rw]


def test_stacked_layers_executed_matches_oracle():
    """A 2-layer stacked config (one ATTN run, count=2) now runs the
    executed continuous path — the per-layer program scans over the
    layer-stacked param/cache leaves — and stays token-for-token with the
    wavefront oracle (which decodes through the hand-wired lm.decode_step
    for stacked runs)."""
    cfg = dataclasses.replace(_cfg(), num_layers=2,
                              block_pattern=("attn", "attn"))
    run = lm.layer_runs(cfg)[0]
    assert run.count == 2
    params = lm.init(cfg, jax.random.PRNGKey(0))
    lens, budgets = PROMPT_SETS[0]
    probe = _requests(cfg, lens, budgets)
    wave = ServeEngine(cfg, params, batch=2, max_len=48,
                       scheduling="wavefront")
    wave.run(probe)
    eos = probe[1].out_tokens[1]          # mid-batch EOS retirement too
    rw = _requests(cfg, lens, budgets, eos=eos)
    rc = _requests(cfg, lens, budgets, eos=eos)
    wave.run(rw)
    cont = ServeEngine(cfg, params, batch=2, max_len=48,
                       scheduling="continuous", plan_fusion=True)
    assert cont.executed, "stacked config must run the executed path"
    cont.run(rc)
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    assert cont.stats.fused_mixed_steps > 0


def test_stacked_layers_gated_off_wavefront_and_paged():
    """The widened executable predicate keeps its two remaining fences:
    the wavefront executed step and the paged arena stay single-layer."""
    cfg = dataclasses.replace(_cfg(), num_layers=2,
                              block_pattern=("attn", "attn"))
    params = lm.init(cfg, jax.random.PRNGKey(0))
    wave = ServeEngine(cfg, params, batch=2, max_len=48,
                       scheduling="wavefront", plan_fusion=True)
    assert not wave.executed
    with pytest.raises(ValueError, match="single-layer"):
        ServeEngine(cfg, params, batch=2, max_len=48,
                    scheduling="continuous", plan_fusion=True,
                    paged_kv=True)


def test_stats_schema():
    st = ServeStats(batch=4)
    d = st.describe()
    assert {"steps", "decode_steps", "mixed_steps", "fused_mixed_steps",
            "tokens", "occupancy", "mixed_fraction"} <= set(d)
    assert st.occupancy == 0.0 and st.mixed_fraction == 0.0


# ---------------------------------------------------------------------------
# The KV cache in place across the layer scan (stacked contiguous runs)
# ---------------------------------------------------------------------------
def _stacked_cfg():
    return dataclasses.replace(_cfg(), num_layers=2,
                               block_pattern=("attn", "attn"))


def _attending_params(cfg, seed=0):
    """``lm.init`` params with the embedding at std 1/d: at ``lm.init``'s
    std 1 the tied head copies each input token whatever attention
    reads, so served tokens could not tell a wrong cache row."""
    params = lm.init(cfg, jax.random.PRNGKey(seed))
    return {**params, "embed": {"embedding": params["embed"]["embedding"]
                                / cfg.d_model}}


@pytest.fixture(scope="module")
def in_place_engine():
    from repro.serve.engine import PrefillBudget
    cfg = _stacked_cfg()
    params = _attending_params(cfg)
    eng = ServeEngine(cfg, params, batch=2, max_len=48,
                      scheduling="continuous", plan_fusion=True,
                      prefill_budget=PrefillBudget(chunk_rows=8,
                                                   max_coresident_chunks=2))
    return cfg, params, eng


def test_kv_in_place_chunked_matches_oracle(in_place_engine):
    """A stacked config with prompts of 1, 2 and 6 chunks: the scan carries
    the whole (L, B, S, Hkv*D) cache, and the served tokens are the
    wavefront oracle's, token for token, with a mid-batch EOS."""
    cfg, params, eng = in_place_engine
    wave = ServeEngine(cfg, params, batch=2, max_len=48,
                       scheduling="wavefront")
    lens, budgets = (6, 15, 41), (3, 5, 4)
    probe = wave.run(_requests(cfg, lens, budgets))
    eos = probe[1].out_tokens[2]
    rw = wave.run(_requests(cfg, lens, budgets, eos=eos))
    rc = eng.run(_requests(cfg, lens, budgets, eos=eos))
    assert [r.out_tokens for r in rc] == [r.out_tokens for r in rw]
    st = eng.stats
    assert eng.kv_in_place and st.prefill_chunks > 3
    assert st.kv_in_place_steps == st.steps > 0
    assert st.describe()["kv_in_place_steps"] == st.steps
    assert all(info["kv_in_place"] for info in eng.cb_program_info.values())
    run = lm.layer_runs(cfg)[0]
    k = jax.eval_shape(eng._init_slot_cache_local)[run.name]["k"]
    assert k.shape == (2, 2, eng.cache_len,
                       cfg.num_kv_heads * cfg.resolved_head_dim)


@pytest.mark.parametrize("n", [0, 1])
def test_kv_in_place_step_under_donation(in_place_engine, n):
    """Donated as on the chip, the step consumes its input cache (every
    leaf reports ``is_deleted()``) and returns what the undonated step
    returns.  On the CPU the engine's own step keeps its input."""
    cfg, _params, eng = in_place_engine
    B, C = eng.batch, eng.chunk_rows()
    i32 = jnp.int32
    tokens = jnp.asarray([3, 7], i32)
    active = jnp.asarray([True, n == 0])
    kw = {}
    if n:
        kw = dict(ch_slots=jnp.asarray([1], i32),
                  ch_offs=jnp.asarray([C], i32),
                  ch_valid=jnp.asarray([C - 3], i32),
                  ch_tokens=jnp.arange(1, C + 1, dtype=i32)[None])

    def fresh():
        cache = eng._init_slot_cache()
        cache["pos"] = jnp.asarray([5, C], i32)
        return jax.tree.map(lambda a: a + jnp.ones((), a.dtype), cache)

    kept = fresh()
    want = eng._cb_step(n)(eng._step_params, kept, tokens, active, **kw)
    assert not any(a.is_deleted() for a in jax.tree.leaves(kept))
    donated = fresh()
    step = jax.jit(eng._make_cb_step(n), donate_argnums=(1,))
    got = step(eng._step_params, donated, tokens, active, **kw)
    assert all(a.is_deleted() for a in jax.tree.leaves(donated))
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_single_layer_run_keeps_sliced_form(setup, executed_engine):
    """L == 1 keeps its per-layer form: no step counts as in place."""
    cfg, _params, _wave, _ = setup
    lens, budgets = PROMPT_SETS[0]
    executed_engine.run(_requests(cfg, lens, budgets))
    st = executed_engine.stats
    assert not executed_engine.kv_in_place
    assert st.steps > 0 and st.kv_in_place_steps == 0
    assert not any(i["kv_in_place"]
                   for i in executed_engine.cb_program_info.values())
