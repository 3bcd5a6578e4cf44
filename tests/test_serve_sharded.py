"""Tensor-parallel serve: the executed continuous engine under shard_map
on 4 fake CPU devices must be token-for-token identical to the
single-device engine — mixed-length prompts, staggered budgets, a
mid-batch EOS retirement — with a fused mixed prefill⊕decode bundle
inside each shard's program and ZERO new autotuner searches on replan
(the schedule-cache signature carries the mesh tag, so the sharded plan
caches independently of the single-device plan).  A 2-layer stacked
config exercises the lax.scan-over-layers form inside the same manual
region.  The shard-major weight permutations and the per-leaf
PartitionSpec rules are unit-tested in-process (no mesh needed)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np

from repro.distributed import sharding as shd

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# in-process: shard-major permutations + spec rules
# ---------------------------------------------------------------------------
def test_qkv_permutation_is_shard_major():
    H, Hkv, D, n = 8, 4, 4, 4
    perm = shd.tp_qkv_permutation(H, Hkv, D, n)
    assert sorted(perm) == list(range((H + 2 * Hkv) * D))   # bijection
    w = np.arange((H + 2 * Hkv) * D)
    slabs = np.take(w, perm).reshape(n, -1)
    Hl, Hkvl = H // n, Hkv // n
    for s in range(n):
        q, k, v = np.split(slabs[s], [Hl * D, (Hl + Hkvl) * D])
        # shard s's slab is [q_s | k_s | v_s] in the original numbering
        assert list(q) == list(range(s * Hl * D, (s + 1) * Hl * D))
        assert list(k) == list(range(H * D + s * Hkvl * D,
                                     H * D + (s + 1) * Hkvl * D))
        assert list(v) == list(range((H + Hkv) * D + s * Hkvl * D,
                                     (H + Hkv) * D + (s + 1) * Hkvl * D))


def test_gated_ffn_permutation_is_per_shard_gate_up():
    F, n = 12, 3
    perm = shd.tp_gated_ffn_permutation(F, n)
    assert sorted(perm) == list(range(2 * F))
    slabs = np.take(np.arange(2 * F), perm).reshape(n, -1)
    Fl = F // n
    for s in range(n):
        gate, up = np.split(slabs[s], 2)
        assert list(gate) == list(range(s * Fl, (s + 1) * Fl))
        assert list(up) == list(range(F + s * Fl, F + (s + 1) * Fl))


def test_tp_pspec_rules():
    from jax.sharding import PartitionSpec as P
    assert shd.tp_param_pspec("w_qkv", 2, "model") == P(None, "model")
    assert shd.tp_param_pspec("w_qkv", 3, "model") == P(None, None, "model")
    assert shd.tp_param_pspec("w_o", 2, "model") == P("model", None)
    assert shd.tp_param_pspec("w_out", 3, "model") == P(None, "model", None)
    assert shd.tp_param_pspec("scale", 1, "model") == P()
    assert shd.tp_cache_pspec("k", 4, "model") == P(None, None, "model",
                                                    None)
    assert shd.tp_cache_pspec("v", 5, "model") == P(None, None, None,
                                                    "model", None)
    assert shd.tp_cache_pspec("pos", 1, "model") == P()


# ---------------------------------------------------------------------------
# subprocess: 4 fake devices, sharded vs single-device differential
# ---------------------------------------------------------------------------
CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, {src!r})
    import dataclasses, tempfile
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core import autotuner
    from repro.core.schedule_cache import ScheduleCache
    from repro.models import lm
    from repro.serve.engine import PrefillBudget, Request, ServeEngine

    cfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                              dtype="float32")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices())[:4], ("model",))
    budget = PrefillBudget(chunk_rows=8)

    def requests(eos=None):
        rng = np.random.default_rng(11)
        lens, budgets = (6, 11, 7, 9, 8), (4, 6, 5, 2, 3)
        return [Request(rid=i, prompt=rng.integers(
                            1, cfg.vocab_size, L).astype(np.int32),
                        max_new_tokens=m, eos_token=eos)
                for i, (L, m) in enumerate(zip(lens, budgets))]

    def engine(**kw):
        return ServeEngine(cfg, params, batch=2, max_len=48,
                           scheduling="continuous", plan_fusion=True,
                           prefill_budget=budget, **kw)

    # EOS probe: pick a token the longest-budget request emits mid-stream
    probe = engine().run(requests())
    eos = probe[1].out_tokens[1]

    single = engine()
    a = single.run(requests(eos=eos))

    cache = ScheduleCache(tempfile.mktemp(suffix=".json"))
    tp = engine(mesh=mesh, schedule_cache=cache)
    assert tp.tp_shards == 4 and tp.executed
    b = tp.run(requests(eos=eos))

    # token-for-token parity, including the mid-batch EOS retirement
    for x, y in zip(a, b):
        assert x.out_tokens == y.out_tokens, (x.rid, x.out_tokens,
                                              y.out_tokens)
    assert any(r == "eos" for _s, _r, r in tp.stats.retirements)

    # each shard's program fuses a mixed prefill+decode bundle: SPMD traces
    # one program per shard, so the fused-chunk table IS the per-shard view
    n_top = max(n for n in tp.cb_program_info if n > 0)
    assert tp._cb_fused_chunks[n_top], "no mixed bundle in shard program"
    assert tp.cb_program_info[n_top]["fused_launches"] >= 1
    assert tp.stats.fused_mixed_steps >= 1

    # replan with the warm cache: a second sharded engine re-plans every
    # program without ONE new autotuner search
    n0 = autotuner.SEARCH_COUNT
    tp2 = engine(mesh=mesh, schedule_cache=cache)
    c = tp2.run(requests(eos=eos))
    assert autotuner.SEARCH_COUNT == n0, "sharded replan re-searched"
    assert [r.out_tokens for r in c] == [r.out_tokens for r in b]

    # stacked 2-layer config: scan-over-layers inside the manual region
    cfg2 = dataclasses.replace(cfg, num_layers=2,
                               block_pattern=("attn", "attn"))
    params2 = lm.init(cfg2, jax.random.PRNGKey(1))
    # embedding at std 1/d, so the tokens depend on what attention reads
    params2 = dict(params2, embed=dict(
        embedding=params2["embed"]["embedding"] / cfg2.d_model))
    s2 = ServeEngine(cfg2, params2, batch=2, max_len=48,
                     scheduling="continuous", plan_fusion=True,
                     prefill_budget=budget)
    t2 = ServeEngine(cfg2, params2, batch=2, max_len=48,
                     scheduling="continuous", plan_fusion=True,
                     prefill_budget=budget, mesh=mesh)
    rng = np.random.default_rng(5)
    mk = lambda: [Request(rid=i, prompt=rng.integers(
                      1, cfg2.vocab_size, L).astype(np.int32),
                  max_new_tokens=m)
                  for i, (L, m) in enumerate(zip((6, 9, 7), (3, 4, 2)))]
    rng = np.random.default_rng(5); ra = s2.run(mk())
    rng = np.random.default_rng(5); rb = t2.run(mk())
    assert [r.out_tokens for r in ra] == [r.out_tokens for r in rb]
    w2 = ServeEngine(cfg2, params2, batch=2, max_len=48,
                     scheduling="wavefront")
    rng = np.random.default_rng(5); rw = w2.run(mk())
    assert [r.out_tokens for r in rw] == [r.out_tokens for r in rb]
    # each shard carries its (L, B, S, Hkv/4 * D) cache in place
    assert t2.kv_in_place and t2.stats.kv_in_place_steps == t2.stats.steps
    k2 = jax.eval_shape(t2._init_slot_cache_local)["run00_attn"]["k"]
    assert k2.shape[-1] == cfg2.num_kv_heads * cfg2.resolved_head_dim

    print("SHARDED SERVE OK")
""")


def test_sharded_serve_token_parity():
    out = subprocess.run([sys.executable, "-c", CODE.format(src=SRC)],
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED SERVE OK" in out.stdout, out.stderr[-3000:]


def test_mesh_inspect_cli_reports_shard_topology():
    """``repro.tools mesh-inspect`` forces its own fake devices, plans one
    shard's program with the executed serve path's options, and reports
    which bundle members are shard-local vs replicated."""
    import json
    import os
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)               # the tool must self-provision
    out = subprocess.run(
        [sys.executable, "-m", "repro.tools", "mesh-inspect",
         "--mesh-shape", "2", "--json"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    rep = json.loads(out.stdout)
    assert rep["mesh"]["shape"] == {"model": 2}
    assert rep["tp_shards"] == 2 and rep["mesh_tag"] == "model:2"
    by_name = {o["op"]: o for o in rep["ops"]}
    norm = by_name["decode_norm1"]
    assert not norm["sharded"]
    assert norm["per_shard_shapes"] == norm["single_device_shapes"]
    qkv = by_name["qkv_proj"]
    assert qkv["sharded"]
    # the QKV weight's fused output axis halves per shard
    assert qkv["per_shard_shapes"][1][-1] * 2 == \
        qkv["single_device_shapes"][1][-1]
    members = [m for b in rep["bundles"] for m in b["members"]]
    assert any(m["sharded"] for m in members)
    assert any(not m["sharded"] for m in members)
