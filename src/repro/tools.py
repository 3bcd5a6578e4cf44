"""Operational tooling CLI.

  PYTHONPATH=src python -m repro.tools cache-inspect [--cache PATH] [--json]
  PYTHONPATH=src python -m repro.tools kv-inspect --snapshot PATH [--json]
  PYTHONPATH=src python -m repro.tools fit-cost [--history DIR] [--out PATH]
  PYTHONPATH=src python -m repro.tools mesh-inspect --mesh-shape N [--json]

``cache-inspect`` dumps the persistent schedule cache
(core/schedule_cache.py): one row per tuned bundle — members, mode,
schedule, predicted vs measured time and their delta — plus aggregate
stats: entry count vs the LRU bound, measured coverage, mean/max
|cm-vs-measured delta|, and *stale signatures* (entries never consulted
since they were recorded: the bundle shape they key no longer occurs in
any planned graph, so they are LRU-eviction candidates).

``kv-inspect`` reads a paged KV-pool snapshot (``launch/serve
--kv-snapshot PATH``, serve/kv_pool.py): arena occupancy (in-use vs free
vs evictable-cached blocks), the prefix-index counters (hits, tokens
reused, trie size, evictions, COW copies), and one row per batch slot
with its mapped block-table prefix.

``fit-cost`` distills the cm-vs-measured deltas of the
``BENCH_measured_*.json`` reports in a directory (``benchmarks.run
--measure`` writes them into the working directory) into a per-op-class
correction table for the roofline cost model — clamped
medians of measured/predicted per class (core/cost_model.op_class).  The
table is inert until loaded ($REPRO_COST_CORRECTIONS=<path> or
``cost_model.set_corrections``); nothing in the default model changes.

``mesh-inspect`` reports the tensor-parallel serve topology without
running any requests: the device mesh, each planner-graph op's per-shard
operand shapes next to the single-device shapes, and which members of
the planned bundles are shard-local vs replicated.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys


def _resolve_cache(path: str | None):
    from repro.core.schedule_cache import ScheduleCache, default_cache
    if path:
        return ScheduleCache(path)
    return default_cache()


def cache_inspect(args) -> int:
    cache = _resolve_cache(args.cache)
    rows = []
    for key, e in sorted(cache.entries.items()):
        if not isinstance(e, dict):
            continue
        m = cache.meta.get(key, {})
        rows.append({
            "key": key[:12],
            "members": "+".join(e.get("members", ["?"])),
            "mode": e.get("mode"),
            "sched": ":".join(str(r) for r in e.get("ratios", [])),
            "vmem_cap": e.get("vmem_cap"),
            "predicted_us": (None if e.get("predicted_s") is None
                             else round(e["predicted_s"] * 1e6, 2)),
            "measured_us": (None if e.get("measured_s") is None
                            else round(e["measured_s"] * 1e6, 2)),
            "delta_pct": (None if e.get("delta_pct") is None
                          else round(e["delta_pct"], 1)),
            "uses": m.get("uses", 0),
            "last_used": m.get("last_used", 0),
        })
    stats = cache.stats()
    stats["max_entries"] = cache.max_entries
    if args.json:
        print(json.dumps({"stats": stats, "entries": rows}, indent=1))
        return 0
    print(f"# schedule cache: {stats['path']}")
    if not rows:
        print("# (empty)")
        return 0
    cols = list(rows[0])
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows)) for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r[c]).ljust(widths[c]) for c in cols))
    print(f"# {stats['entries']} entries"
          + (f" (bound {stats['max_entries']}, LRU)"
             if stats["max_entries"] else " (unbounded)")
          + f", {stats['measured']} measured, "
          f"{stats['stale_never_reused']} stale (never re-consulted)")
    if stats["mean_abs_delta_pct"] is not None:
        print(f"# cm-vs-measured |delta|: mean "
              f"{stats['mean_abs_delta_pct']:.1f}% "
              f"max {stats['max_abs_delta_pct']:.1f}%")
    return 0


def kv_inspect(args) -> int:
    with open(args.snapshot) as fh:
        snap = json.load(fh)
    if args.json:
        print(json.dumps(snap, indent=1))
        return 0
    nb, bs = snap["num_blocks"], snap["block_size"]
    slots = snap["slots"]
    usable = nb - slots
    used = snap["blocks_in_use"]
    print(f"# kv pool: {nb} blocks x {bs} tokens "
          f"({slots} sentinels, {usable} usable)")
    print(f"# occupancy: {used}/{usable} in use "
          f"({used / max(usable, 1):.0%}), {snap['free_blocks']} free, "
          f"{snap['evictable_blocks']} cached-evictable")
    print(f"# prefix index: {snap['trie_nodes']} trie nodes, "
          f"{snap['prefix_hits']} hits, "
          f"{snap['prefix_tokens_reused']} tokens reused, "
          f"{snap['evictions']} evictions, "
          f"{snap['cow_copies']} cow copies")
    rows = [{"slot": t["slot"], "owned": t["owned"],
             "tokens": t["owned"] * bs,
             "blocks": ",".join(str(b) for b in t["blocks"]) or "-"}
            for t in snap["tables"]]
    cols = list(rows[0])
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows)) for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r[c]).ljust(widths[c]) for c in cols))
    return 0


def fit_cost(args) -> int:
    from repro.core.cost_model import CORRECTION_CLAMP, op_class
    files = sorted(glob.glob(os.path.join(args.history,
                                          "BENCH_measured_*.json")))
    deltas: dict[str, list[float]] = {}
    n_rows = 0
    for path in files:
        try:
            with open(path) as fh:
                report = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        for row in report.get("rows", []):
            d = row.get("cm_vs_measured_delta_pct")
            if d is None or not row.get("bundle"):
                continue
            n_rows += 1
            # the bundle's disagreement is attributed to every member's
            # class — per-member deltas aren't observable from a fused
            # measurement, so each class accumulates the deltas of every
            # bundle it took part in and the median washes out partners
            for member in str(row["bundle"]).split("+"):
                deltas.setdefault(op_class(member), []).append(float(d))
    lo, hi = CORRECTION_CLAMP
    classes = {
        cls: {
            "correction": round(
                min(hi, max(lo, 1.0 + statistics.median(ds) / 100.0)), 4),
            "n": len(ds),
            "median_delta_pct": round(statistics.median(ds), 2),
        }
        for cls, ds in sorted(deltas.items())
    }
    table = {"classes": classes, "clamp": [lo, hi],
             "source_files": len(files), "rows": n_rows}
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(table, fh, indent=1)
            fh.write("\n")
    if args.json:
        print(json.dumps(table, indent=1))
        return 0
    print(f"# fit-cost: {n_rows} measured rows in {len(files)} history "
          f"files under {args.history}")
    if not classes:
        print("# (no cm_vs_measured_delta_pct data — table is empty; the "
              "cost model stays purely analytic)")
    for cls, e in classes.items():
        print(f"  {cls:<32} x{e['correction']:<7} "
              f"(median delta {e['median_delta_pct']:+.1f}%, n={e['n']})")
    if args.out:
        print(f"# wrote {args.out} — activate with "
              f"REPRO_COST_CORRECTIONS={args.out}")
    return 0


def mesh_inspect(args) -> int:
    # XLA_FLAGS must be set before jax imports; tools.py imports jax lazily
    # for exactly this reason.
    n = args.mesh_shape
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()
    import dataclasses

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import PrefillBudget, ServeEngine

    devs = jax.devices()
    if len(devs) < n:
        print(f"error: mesh shape {n} needs {n} devices, have {len(devs)} "
              f"(set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
              f"before launch)", file=sys.stderr)
        return 1
    cfg = dataclasses.replace(get_config(args.arch).reduced(),
                              dtype="float32")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(devs)[:n], (args.shard_axis,))
    kw = dict(batch=args.batch, max_len=args.max_len,
              scheduling="continuous", plan_fusion=True,
              prefill_budget=PrefillBudget(chunk_rows=args.chunk_rows))
    tp = ServeEngine(cfg, params, mesh=mesh, shard_axis=args.shard_axis,
                     **kw)
    ref = ServeEngine(cfg, params, **kw)
    chunks = tp.prefill_budget.max_coresident_chunks
    g_tp = tp.decode_graph(prefill_chunks=chunks)
    g_ref = ref.decode_graph(prefill_chunks=chunks)

    def operand_shapes(op):
        return [list(o.shape) for o in (*op.inputs, *op.outputs)]

    ops = []
    sharded_names = set()
    # both graphs come from the same builder with the same chunk count, so
    # they align positionally; an op whose operand shapes shrank under the
    # shard-local head/FFN widths is shard-local, the rest are replicated
    for gt, gr in zip(g_tp, g_ref):
        local = operand_shapes(gt.op)
        full = operand_shapes(gr.op)
        sharded = local != full
        if sharded:
            sharded_names.add(gt.op.name)
        ops.append({"op": gt.op.name, "grid": gt.op.grid,
                    "bound": gt.op.bound, "sharded": sharded,
                    "per_shard_shapes": local,
                    "single_device_shapes": full})
    # plan with the executed serve path's options (allow_same_bound: at
    # smoke scale everything is memory-bound and launch amortization still
    # decides), so the bundle report matches the program the engine runs
    from repro.core import planner
    plan = planner.plan(g_tp, max_ways=max(3, 2 + chunks),
                        allow_same_bound=True, mesh_tag=tp._mesh_tag)

    def members_of(row):
        # a stitched chain member is shard-local if any stitched op is
        return [{"member": m,
                 "sharded": any(p in sharded_names
                                for p in m.split("→"))}
                for m in row["members"].split("+")]

    bundles = [{"members": members_of(row), "schedule": row["schedule"]}
               for row in plan.summary()]
    out = {
        "mesh": {"shape": dict(mesh.shape), "axis": args.shard_axis,
                 "devices": [str(d) for d in mesh.devices.ravel()]},
        "tp_shards": tp.tp_shards,
        "mesh_tag": tp._mesh_tag,
        "ops": ops,
        "bundles": bundles,
    }
    if args.json:
        print(json.dumps(out, indent=1))
        return 0
    print(f"# mesh: {dict(mesh.shape)} over {len(mesh.devices.ravel())} "
          f"devices (axis '{args.shard_axis}', cache tag "
          f"'{tp._mesh_tag}')")
    print(f"# per-shard planner graph ({len(ops)} ops):")
    for o in ops:
        kind = "shard-local" if o["sharded"] else "replicated "
        shapes = " ".join("x".join(str(d) for d in s)
                          for s in o["per_shard_shapes"])
        print(f"  {kind}  {o['op']:<34} grid={o['grid']:<5} "
              f"{o['bound']:<7} {shapes}")
    print("# planned bundles (per shard — SPMD traces one program per "
          "shard):")
    for b in bundles:
        tags = ", ".join(
            f"{m['member']}[{'local' if m['sharded'] else 'repl'}]"
            for m in b["members"])
        print(f"  sched {b['schedule']:<9} {tags}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.tools")
    sub = ap.add_subparsers(dest="cmd", required=True)
    ci = sub.add_parser("cache-inspect",
                        help="dump the persistent schedule cache")
    ci.add_argument("--cache", default=None,
                    help="cache file (default: the shared default cache — "
                         "$REPRO_SCHEDULE_CACHE with its LRU bound)")
    ci.add_argument("--json", action="store_true")
    ci.set_defaults(fn=cache_inspect)
    ki = sub.add_parser("kv-inspect",
                        help="dump a paged KV-pool snapshot "
                             "(launch/serve --kv-snapshot)")
    ki.add_argument("--snapshot", required=True,
                    help="snapshot JSON written by launch/serve "
                         "--kv-snapshot PATH")
    ki.add_argument("--json", action="store_true")
    ki.set_defaults(fn=kv_inspect)
    fc = sub.add_parser("fit-cost",
                        help="fit per-op-class cost-model corrections from "
                             "measured benchmark reports")
    fc.add_argument("--history", default=".",
                    help="directory holding BENCH_measured_*.json reports")
    fc.add_argument("--out", default=None,
                    help="write the correction table here (activate via "
                         "REPRO_COST_CORRECTIONS=PATH)")
    fc.add_argument("--json", action="store_true")
    fc.set_defaults(fn=fit_cost)
    mi = sub.add_parser("mesh-inspect",
                        help="report the tensor-parallel serve topology "
                             "(mesh, per-shard shapes, bundle locality)")
    mi.add_argument("--arch", default="granite-3-2b")
    mi.add_argument("--mesh-shape", type=int, default=4,
                    help="devices along the shard axis (fake CPU devices "
                         "are forced if XLA_FLAGS doesn't already)")
    mi.add_argument("--shard-axis", default="model")
    mi.add_argument("--batch", type=int, default=2)
    mi.add_argument("--max-len", type=int, default=48)
    mi.add_argument("--chunk-rows", type=int, default=8)
    mi.add_argument("--json", action="store_true")
    mi.set_defaults(fn=mesh_inspect)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
