"""Persistent schedule cache — never re-search a bundle we already tuned.

Production serving/training plans the same op graphs every process start;
the paper's Main() search (and especially its measured form) is pure waste
the second time.  Entries are keyed by an exact *bundle signature* — op
names, grids, operand shapes/dtypes/block shapes, FLOP/byte counts, the
VMEM budget, and the scoring mode (cost model vs measurement backend) — so
any change that could alter the tuned schedule changes the key and the
stale entry is simply never consulted again.  Bumping ``CACHE_VERSION``
(schema or search-semantics changes) invalidates every file on disk.

File format (JSON, human-inspectable):

    {"version": 4,
     "entries": {"<sha256-prefix>": {
        "members": ["maxpool", "upsample", "sha_like"],
        "ratios": [2, 1, 4], "variant": 0, "vmem_cap": null,
        "predicted_s": 1.2e-4, "measured_s": 1.3e-4, "delta_pct": 8.3,
        "mode": "costmodel"}},
     "meta": {"<sha256-prefix>": {"last_used": 7, "uses": 3}},
     "clock": 9}

``meta``/``clock`` are the LRU + staleness side table (entries themselves
stay exactly what the search stored); ``max_entries`` bounds the table with
least-recently-used eviction.  ``autotuner.search(cache=...)`` and
``planner.plan(cache=...)`` consult it; ``default_cache()`` resolves the
shared on-disk location (``$REPRO_SCHEDULE_CACHE`` or
``~/.cache/repro/schedule_cache.json``; ``$REPRO_SCHEDULE_CACHE_MAX``
bounds it, default 512).  ``python -m repro.tools cache-inspect`` dumps
entries, cm-vs-measured deltas, and stale-signature stats.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
from pathlib import Path
from typing import Optional, Sequence

import jax.numpy as jnp

from repro.core.op_spec import OpSpec

CACHE_VERSION = 4      # v4: signatures carry the mesh-axis tag (tensor-
#                        parallel plans tune shard-local operand shapes and
#                        must never resolve a single-device schedule)

_DEFAULT: Optional["ScheduleCache"] = None


def bundle_signature(ops: Sequence[OpSpec], *, vmem_budget: int,
                     mode: str = "costmodel", mesh_tag: str = "") -> str:
    """Exact identity of a tuning problem.  Includes everything the search
    outcome can depend on; excludes anything it cannot (body closures).

    ``mesh_tag`` names the SPMD context a sharded plan tunes for (e.g.
    ``"model:4"`` — the mesh axis and its extent).  Per-shard operand
    shapes alone already differ from the single-device plan, but two
    different meshes can produce identical shard-local shapes (8 heads on
    2 shards vs 4 heads unsharded), so the tag is part of the identity."""
    parts = [f"v{CACHE_VERSION}", mode, str(int(vmem_budget))]
    if mesh_tag:
        parts.append(f"mesh[{mesh_tag}]")
    for op in ops:
        operands = ",".join(
            "{}:{}:{}".format("x".join(map(str, o.shape)),
                              jnp.dtype(o.dtype).name,
                              "x".join(map(str, o.block_shape)))
            for o in (*op.inputs, *op.outputs))
        # a stitched chain (core/stitch.py) tunes differently from the
        # unstitched op set — same operands, different traffic and VMEM
        # residency — so the chain structure is part of the identity
        chain = f"|c[{'>'.join(op.chain)}]+{int(op.extra_vmem_bytes)}" \
            if op.chain else ""
        parts.append(f"{op.name}|g{op.grid}|f{op.flops:.6g}"
                     f"|h{op.hbm_bytes:.6g}|{operands}{chain}")
    return hashlib.sha256(";".join(parts).encode()).hexdigest()[:32]


class ScheduleCache:
    """In-memory dict with optional JSON persistence, hit/miss stats, a
    size bound with LRU eviction, and per-entry usage metadata.

    ``max_entries`` bounds the table: on ``put`` the least-recently-used
    entries are evicted first (usage rides in a side table, NOT inside the
    entries — entry dicts stay exactly what callers stored).  The usage
    metadata (a monotonic ``clock``, per-key ``last_used``/``uses``)
    persists with the file so ``repro.tools cache-inspect`` can report
    stale signatures — entries no plan has consulted since they were
    recorded (the bundle shape changed and the old key is dead weight)."""

    def __init__(self, path: Optional[os.PathLike | str] = None,
                 max_entries: Optional[int] = None):
        self.path = Path(path) if path else None
        self.max_entries = max_entries
        self.entries: dict[str, dict] = {}
        self.meta: dict[str, dict] = {}       # key -> {last_used, uses}
        self.clock = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._defer = False
        self._dirty = False
        if self.path is not None:
            self.load()

    # ------------------------------------------------------------------
    def _touch(self, key: str, used: bool) -> None:
        self.clock += 1
        m = self.meta.setdefault(key, {"last_used": 0, "uses": 0})
        m["last_used"] = self.clock
        if used:
            m["uses"] = m.get("uses", 0) + 1
            # hit-side usage persists at the next save: a pure-hit replan
            # inside batched() (planner.plan) flushes once on exit
            if self._defer:
                self._dirty = True

    def get(self, key: str) -> Optional[dict]:
        entry = self.entries.get(key)
        if entry is None:
            self.misses += 1
        else:
            self.hits += 1
            self._touch(key, used=True)
        return entry

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = entry
        self._touch(key, used=False)
        if self.max_entries is not None:
            while len(self.entries) > self.max_entries:
                victim = min(
                    (k for k in self.entries if k != key),
                    key=lambda k: self.meta.get(k, {}).get("last_used", 0))
                del self.entries[victim]
                self.meta.pop(victim, None)
                self.evictions += 1
        if self._defer:
            self._dirty = True
        elif self.path is not None:
            self.save()

    @contextlib.contextmanager
    def batched(self):
        """Defer disk writes until the block exits — one save for a whole
        plan()/search() burst instead of a full-file rewrite per put()."""
        prev = self._defer
        self._defer = True
        try:
            yield self
        finally:
            self._defer = prev
            if self._dirty and not self._defer:
                self._dirty = False
                self.save()

    def __len__(self) -> int:
        return len(self.entries)

    # ------------------------------------------------------------------
    def load(self) -> None:
        if self.path is None or not self.path.exists():
            return
        try:
            blob = json.loads(self.path.read_text())
        except (json.JSONDecodeError, OSError):
            return                            # corrupt cache == empty cache
        if blob.get("version") != CACHE_VERSION:
            return                            # stale schema: discard
        self.entries.update(blob.get("entries", {}))
        self.meta.update(blob.get("meta", {}))
        self.clock = max(self.clock, int(blob.get("clock", 0)))

    def save(self) -> None:
        if self.path is None:
            return
        self.path.parent.mkdir(parents=True, exist_ok=True)
        # merge concurrent writers: keys are content-addressed, so entries
        # another process added since our load are kept (ours win on clash)
        merged = dict(self.entries)
        merged_meta = dict(self.meta)
        clock = self.clock
        try:
            blob = json.loads(self.path.read_text())
            if blob.get("version") == CACHE_VERSION:
                merged = {**blob.get("entries", {}), **self.entries}
                merged_meta = {**blob.get("meta", {}), **self.meta}
                clock = max(clock, int(blob.get("clock", 0)))
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            pass
        merged_meta = {k: m for k, m in merged_meta.items() if k in merged}
        if self.max_entries is not None:          # bound survives the merge:
            while len(merged) > self.max_entries:  # evicted keys stay evicted
                victim = min(merged,
                             key=lambda k: merged_meta.get(k, {})
                             .get("last_used", 0))
                del merged[victim]
                merged_meta.pop(victim, None)
        tmp = self.path.with_suffix(f".tmp.{os.getpid()}")   # no writer races
        tmp.write_text(json.dumps(
            {"version": CACHE_VERSION, "entries": merged,
             "meta": merged_meta, "clock": clock},
            indent=1, sort_keys=True))
        tmp.replace(self.path)                # atomic on POSIX
        self.entries = merged
        self.meta = merged_meta
        self.clock = clock

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Aggregate view for ``repro.tools cache-inspect``."""
        deltas = [e["delta_pct"] for e in self.entries.values()
                  if isinstance(e, dict) and e.get("delta_pct") is not None]
        stale = [k for k in self.entries
                 if self.meta.get(k, {}).get("uses", 0) == 0]
        return {
            "path": str(self.path) if self.path else None,
            "entries": len(self.entries),
            "measured": sum(1 for e in self.entries.values()
                            if isinstance(e, dict)
                            and e.get("measured_s") is not None),
            "stale_never_reused": len(stale),
            "mean_abs_delta_pct": (sum(abs(d) for d in deltas) / len(deltas)
                                   if deltas else None),
            "max_abs_delta_pct": (max(abs(d) for d in deltas)
                                  if deltas else None),
            "clock": self.clock,
        }


def default_cache() -> ScheduleCache:
    """Process-wide cache at $REPRO_SCHEDULE_CACHE (or
    ``<checkout>/.schedule_cache.json``), size-bounded by
    $REPRO_SCHEDULE_CACHE_MAX (LRU, default 512)."""
    global _DEFAULT
    if _DEFAULT is None:
        path = os.environ.get(
            "REPRO_SCHEDULE_CACHE",
            str(Path(__file__).resolve().parents[3]
                / ".schedule_cache.json"))
        bound = int(os.environ.get("REPRO_SCHEDULE_CACHE_MAX", "512"))
        _DEFAULT = ScheduleCache(path, max_entries=bound or None)
    return _DEFAULT
