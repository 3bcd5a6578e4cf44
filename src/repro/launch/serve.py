"""Serving launcher (smoke-scale by default).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --requests 12 --prompt-len 16 --max-new 8

Default scheduling is continuous batching (per-slot cache positions;
docs/serving.md): slots retire and refill independently every iteration,
so ``--stagger`` (prompt-length/budget spread) and ``--arrival-rate``
(Poisson-ish arrival trace) exercise the steady mixed prefill⊕decode
graph.  ``--scheduling wavefront`` runs the legacy lock-step engine — the
differential oracle (tests/test_serve_continuous.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import PrefillBudget, Request, ServeEngine


def build_requests(cfg, args) -> list[Request]:
    """Deterministic request trace: ``--stagger`` spreads prompt lengths
    and token budgets so retirement/refill actually triggers mid-batch;
    ``--arrival-rate`` > 0 draws Poisson-ish (exponential-gap) arrivals."""
    rng = np.random.default_rng(args.seed)
    arrivals = np.zeros(args.requests)
    if args.arrival_rate > 0:
        arrivals = np.floor(np.cumsum(
            rng.exponential(1.0 / args.arrival_rate, args.requests)))
    shared = None
    if args.shared_prefix > 0:
        # one prefix drawn ONCE, common to every request — the paged-KV
        # prefix cache serves it from shared blocks after the first prompt
        shared = rng.integers(0, cfg.vocab_size,
                              args.shared_prefix).astype(np.int32)
    reqs = []
    for i in range(args.requests):
        spread = i % max(1, args.stagger)
        plen = args.prompt_len + spread
        tail = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        prompt = tail if shared is None else np.concatenate([shared, tail])
        reqs.append(Request(
            rid=i,
            prompt=prompt,
            max_new_tokens=max(1, args.max_new - spread),
            temperature=args.temperature,
            arrival=int(arrivals[i])))
    return reqs


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduling", choices=["continuous", "wavefront"],
                    default="continuous",
                    help="continuous = per-slot cache positions with "
                         "iteration-level refill (default); wavefront = "
                         "legacy lock-step waves")
    ap.add_argument("--stagger", type=int, default=1,
                    help="spread request i's prompt length by +(i %% N) and "
                         "its budget by -(i %% N): staggers retirements so "
                         "slots refill mid-batch")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean request arrivals per engine step (0 = all "
                         "requests queued at step 0); Poisson-ish trace "
                         "for the occupancy report")
    ap.add_argument("--chunk-rows", type=int, default=2048,
                    help="prefill budget: max prompt rows admitted per slot "
                         "per iteration (PrefillBudget.chunk_rows); longer "
                         "prompts are chipped away chunk-by-chunk")
    ap.add_argument("--coresident-chunks", type=int, default=2,
                    help="prefill budget: max prefill chunks (distinct "
                         "slots) co-resident in one fused decode launch")
    ap.add_argument("--prefill-policy", choices=["fifo", "srpf", "eload"],
                    default="fifo",
                    help="chunk-ordering under contention: fifo = claim "
                         "order; srpf = shortest-remaining-prefill-first; "
                         "eload = srpf + shed one coresident chunk while "
                         "the per-expert hit skew exceeds the budget's "
                         "threshold (MoE executed path; "
                         "PrefillBudget.policy)")
    ap.add_argument("--reject-overlong", action="store_true",
                    help="reject prompts longer than --chunk-rows instead "
                         "of admitting them across iterations")
    ap.add_argument("--expect-stitched", action="store_true",
                    help="fail unless the executed decode program carries "
                         ">=1 epilogue chain (core/stitch.py) inside a "
                         "fused launch — the CI hybrid-fusion smoke")
    ap.add_argument("--expect-moe-fused", action="store_true",
                    help="fail unless the executed decode program puts the "
                         "grouped expert GMM (kernels/moe_gmm) in a fused "
                         "launch with a co-resident partner — the CI MoE "
                         "serve smoke")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="paged KV: arena block size in tokens (0 = "
                         "contiguous per-slot cache; >0 enables the "
                         "KVPool paged path, requires --plan-fusion)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged KV: total arena blocks including per-slot "
                         "sentinels (default: batch slots' worth + slack)")
    ap.add_argument("--kv-slot-blocks", type=int, default=None,
                    help="paged KV: table columns per slot — the logical "
                         "capacity kv_slot_blocks * kv_block_size replaces "
                         "max_len as the length ceiling")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one shared N-token prefix to every "
                         "prompt: exercises the prefix cache (later "
                         "requests skip those chunks' prefill)")
    ap.add_argument("--expect-prefix-hits", action="store_true",
                    help="fail unless the prefix cache served >=1 request "
                         "from shared blocks (stats.prefix_hit_rate > 0) — "
                         "the CI paged-serve smoke")
    ap.add_argument("--kv-snapshot", default=None, metavar="PATH",
                    help="write the final KVPool snapshot as JSON "
                         "(inspect with: python -m repro.tools kv-inspect)")
    ap.add_argument("--mesh-shape", type=int, default=0, metavar="N",
                    help="tensor-parallel serve: run the executed decode "
                         "program under shard_map on an N-device 1-D mesh "
                         "(head-sharded QKV/FFN + KV cache; requires "
                         "--plan-fusion and N local devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--shard-axis", default="model",
                    help="mesh axis name the sharded leaves partition over "
                         "(default: model)")
    ap.add_argument("--expect-sharded-parity", action="store_true",
                    help="also serve the same trace on a single device and "
                         "fail unless every token stream matches — the CI "
                         "multi-device smoke gate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-fusion", action="store_true",
                    help="plan the decode-step fusion bundle "
                         "(RMSNorm + decode attention + router/FFN)")
    ap.add_argument("--measure", choices=["auto", "interpret", "tpu", "gpu"],
                    default=None,
                    help="pick planned schedules by measurement "
                         "(core/timing.make_measure backend)")
    return ap


def check_args(ap: argparse.ArgumentParser, args) -> None:
    if args.measure and not args.plan_fusion:
        ap.error("--measure only applies to --plan-fusion schedule selection")
    if args.kv_block_size > 0 and not args.plan_fusion:
        ap.error("--kv-block-size requires --plan-fusion (paged KV runs "
                 "only on the executed continuous path)")
    if args.kv_block_size <= 0 and (
            args.kv_blocks is not None or args.kv_slot_blocks is not None
            or args.expect_prefix_hits or args.kv_snapshot):
        ap.error("--kv-blocks/--kv-slot-blocks/--expect-prefix-hits/"
                 "--kv-snapshot require --kv-block-size > 0")
    if args.mesh_shape > 1 and not args.plan_fusion:
        ap.error("--mesh-shape requires --plan-fusion (only the executed "
                 "continuous step runs under shard_map)")
    if args.expect_sharded_parity and args.mesh_shape <= 1:
        ap.error("--expect-sharded-parity requires --mesh-shape > 1")


def load_model(args):
    """(cfg, params): the config at ``--scale``, weights drawn from
    ``--seed``."""
    cfg = get_config(args.arch)
    if args.scale == "smoke":
        cfg = cfg.reduced()
    # one jitted program draws every leaf on the device (the same values as
    # the eager lm.init, without a dispatch per leaf)
    init = jax.jit(lambda key: lm.init(cfg, key))
    return cfg, init(jax.random.PRNGKey(args.seed))


def build_mesh(args):
    """The 1-D tensor-parallel mesh of ``--mesh-shape`` local devices, or
    None."""
    if args.mesh_shape <= 1:
        return None
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < args.mesh_shape:
        raise SystemExit(
            f"[sharded] FAIL: --mesh-shape {args.mesh_shape} needs that "
            f"many local devices, found {len(devs)} (on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{args.mesh_shape})")
    return Mesh(np.array(devs)[:args.mesh_shape], (args.shard_axis,))


def build_engine(args, cfg, params, mesh=None) -> ServeEngine:
    """The serve engine exactly as the launcher configures it."""
    measure = None
    schedule_cache = None
    if args.plan_fusion:
        from repro.core.schedule_cache import default_cache
        from repro.core.timing import make_measure
        measure = make_measure(args.measure) if args.measure else None
        schedule_cache = default_cache()
    budget = PrefillBudget(chunk_rows=args.chunk_rows,
                           max_coresident_chunks=args.coresident_chunks,
                           policy=args.prefill_policy)
    return ServeEngine(cfg, params, batch=args.batch,
                       max_len=args.prompt_len + args.shared_prefix
                       + args.stagger + args.max_new + 8,
                       plan_fusion=args.plan_fusion, measure=measure,
                       schedule_cache=schedule_cache,
                       scheduling=args.scheduling,
                       prefill_budget=budget,
                       reject_overlong=args.reject_overlong,
                       paged_kv=args.kv_block_size > 0,
                       kv_block_size=args.kv_block_size or 16,
                       kv_blocks=args.kv_blocks,
                       kv_slot_blocks=args.kv_slot_blocks,
                       mesh=mesh, shard_axis=args.shard_axis)


def main(argv=None):
    from repro import compile_cache
    compile_cache.enable()
    ap = make_parser()
    args = ap.parse_args(argv)
    check_args(ap, args)
    cfg, params = load_model(args)
    mesh = build_mesh(args)
    if mesh is not None:
        print(f"[sharded] {args.mesh_shape}-way tensor-parallel serve over "
              f"mesh axis {args.shard_axis!r}")
    engine = build_engine(args, cfg, params, mesh)
    if engine.fusion_plan is not None:
        print("[plan-fusion] decode-step bundles:")
        for row in engine.fusion_plan.summary():
            print(f"  {row}")
        print("[plan-fusion] decode step "
              + ("EXECUTES through the plan->program executor "
                 "(core/executor)" if engine.executed
                 else "falls back to the hand-wired path"))
    if args.expect_stitched:
        from repro.core.stitch import CHAIN_SEP
        if not engine.executed:
            raise SystemExit("[stitch] FAIL: decode step is not executed "
                             "through the program executor")
        prog = engine.build_decode_program(
            prefill_chunks=args.coresident_chunks)
        chains = sorted({m for ms in prog.fused_members for m in ms
                         if CHAIN_SEP in m})
        if not chains:
            raise SystemExit("[stitch] FAIL: no epilogue chain in any "
                             "fused launch of the decode program")
        print(f"[stitch] chains in fused launches: {', '.join(chains)}")
    if args.expect_moe_fused:
        if cfg.moe is None:
            raise SystemExit("[moe] FAIL: --expect-moe-fused on a dense "
                             f"config ({cfg.name})")
        if not engine.executed:
            raise SystemExit("[moe] FAIL: MoE decode step is not executed "
                             "through the program executor")
        prog = engine.build_decode_program(
            prefill_chunks=args.coresident_chunks)
        bundles = [sorted(ms) for ms in prog.fused_members
                   if any(m.startswith("moe_gmm") for m in ms)]
        if not bundles:
            raise SystemExit("[moe] FAIL: the grouped expert GMM is not "
                             "co-resident in any fused launch of the "
                             "decode program")
        print("[moe] expert GMM co-resident in fused launch: "
              + "; ".join("+".join(ms) for ms in bundles))
    reqs = build_requests(cfg, args)
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    if args.expect_sharded_parity:
        # same deterministic trace on one device; every stream must match
        ref_engine = build_engine(args, cfg, params)
        ref = build_requests(cfg, args)
        ref_engine.run(ref)
        bad = [r.rid for r, s in zip(ref, reqs)
               if r.out_tokens != s.out_tokens]
        if bad:
            raise SystemExit("[sharded] FAIL: sharded token streams "
                             f"diverge from single-device for rids {bad}")
        print(f"[sharded] token-for-token parity with single-device "
              f"across {len(reqs)} requests")
    if args.scheduling == "continuous":
        st = engine.stats
        print(f"[slots] {st.describe()}")
        print(f"[slots] occupancy {st.occupancy:.0%}, mixed prefill⊕decode "
              f"on {st.mixed_fraction:.0%} of decode steps "
              f"({st.fused_mixed_steps} in a fused launch)")
        print(f"[prefill] {st.prefill_chunks} chunks admitted, "
              f"{st.fused_prefill_fraction:.0%} in a fused launch; "
              f"mean admission latency "
              f"{st.mean_admission_latency:.1f} steps")
        if cfg.moe is not None and st.expert_hits:
            print(f"[moe] expert hits {st.expert_hits} "
                  f"(skew {st.expert_skew:.2f}), "
                  f"{st.load_shed_steps} load-shed steps")
        if args.kv_block_size > 0:
            print(f"[paged-kv] block_size {engine.kv_block_size}, peak "
                  f"{st.blocks_in_use} blocks in use, "
                  f"prefix_hit_rate {st.prefix_hit_rate:.0%} "
                  f"({st.prefix_hits} hits, {st.prefix_tokens_reused} "
                  f"tokens reused), {st.evictions} evictions")
    if args.kv_snapshot:
        import json
        snap = engine.kv_pool.snapshot()
        with open(args.kv_snapshot, "w") as fh:
            json.dump(snap, fh, indent=2)
        print(f"[paged-kv] pool snapshot -> {args.kv_snapshot}")
    if args.expect_prefix_hits:
        if engine.stats.prefix_hit_rate <= 0:
            raise SystemExit("[paged-kv] FAIL: no request was served from "
                             "shared prefix blocks (prefix_hit_rate == 0)")
        print(f"[paged-kv] prefix cache hit "
              f"{engine.stats.prefix_hits} request(s)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.out_tokens}")


if __name__ == "__main__":
    main()
