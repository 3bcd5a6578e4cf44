"""From a profiler trace to per-step device times, busy and idle time, and
idle gaps attributed to what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps a
small normalized form (JSON-serializable; the test fixture is one):

    {"modules": [[name, start_ns, dur_ns], ...],       # chip 0: programs
     "ops":     [[name, start_ns, dur_ns, pallas], ...], # chip 0: operations
     "chip_busy": [[[start_ns, end_ns], ...], ...],    # every chip: merged
                                                       # operation intervals
     "host":    [[name, start_ns, dur_ns], ...]}       # bench.* host spans

on one clock.  ``reduce`` turns it and the harness's per-step records into
the numbers the per-layer metrics read.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

STEP_MODULE = "jit_step"       # the jitted continuous-batching step
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")            # and their -start/-done


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _opcode(text: str) -> str:
    """The opcode of an HLO instruction's text: ``%x = <shape> op(...)``."""
    _, _, rest = text.partition(" = ")
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r"([a-z][a-z0-9_-]*)\(", rest[i + 1:])
            if m:
                return m.group(1)
    return ""


def _short(text: str) -> str:
    """``%name = shape opcode`` of an op's HLO text, layouts dropped."""
    name, _, rest = text.partition(" = ")
    op = _opcode(text)
    shape = rest.split(f" {op}(", 1)[0] if op else rest
    shape = re.sub(r"\{[^{}]*\}", "", re.sub(r"\{[^{}]*\{[^{}]*\}[^{}]*\}",
                                            "", shape))
    return f"{name} = {shape[:60]} {op}".strip()


def is_collective(short: str) -> bool:
    """Whether an op (as ``load`` names it) exchanges data between chips."""
    op = short.rsplit(" ", 1)[-1]
    return op.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def _merged(events) -> list[list[int]]:
    """Merged [start, end) intervals of a device's leaf operations."""
    return [[s, e] for s, e in union(
        [(None, int(ev.start_ns), int(ev.duration_ns)) for ev in events
         if _opcode(ev.name) not in CONTAINERS], 0, 2 ** 63)]


def load(trace_dir: str, chips: int = 1) -> dict:
    """Normalized events of the first TPU device, the busy intervals of
    the first ``chips`` TPU devices, and the host spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"modules": [], "ops": [], "chip_busy": [], "host": []}
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    if devices:
        for line in devices[0].lines:
            if line.name == "XLA Modules":
                out["modules"] = [[e.name, int(e.start_ns),
                                   int(e.duration_ns)] for e in line.events]
            elif line.name == "XLA Ops":
                # leaf operations only: a loop's own event spans its body
                out["ops"] = [[_short(e.name), int(e.start_ns),
                               int(e.duration_ns), PALLAS_TARGET in e.name]
                              for e in line.events
                              if _opcode(e.name) not in CONTAINERS]
    for plane in devices[:chips]:
        out["chip_busy"].append(next(
            (_merged(line.events) for line in plane.lines
             if line.name == "XLA Ops"), []))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    out["host"].append([e.name, int(e.start_ns),
                                        int(e.duration_ns)])
    return out


def describe(trace_dir: str, limit: int = 25) -> dict:
    """Plane and line names with a few events and their stats: what a
    reader of the reduction needs to see once."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    pd = ProfileData.from_file(paths[-1])
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "first": [[e.name, int(e.start_ns), int(e.duration_ns),
                           {k: str(v)[:80] for k, v in _stats(e).items()}]
                          for e in evs[:limit]]}
        out[plane.name] = lines
    return out


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d, *_ in intervals
                 if s < hi and s + d > lo)
    merged: list[list[int]] = []
    for s, e in ivs:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def idle_gaps(busy: list[tuple[int, int]], lo: int, hi: int):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _phase(name: str) -> str:
    return re.sub(r"\.\d+$", "", name[len("bench."):])


def attribute(gaps, host) -> dict:
    """Seconds of device idle time under each host phase (the phase span
    that overlaps a gap most; ``none`` where no span does)."""
    spans = sorted((s, s + d, _phase(n)) for n, s, d in host
                   if n != "bench.window")
    starts = [s for s, _, _ in spans]
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        i = max(0, int(np.searchsorted(starts, g0)) - 1)
        best, name = 0, "none"
        while i < len(spans) and spans[i][0] < g1:
            s, e, ph = spans[i]
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, name = ov, ph
            i += 1
        out[name] = out.get(name, 0.0) + (g1 - g0) * 1e-9
    return out


def _align(mods, recs) -> int:
    """Offset of ``recs[0]`` in ``mods``.  Host and device clocks in a trace
    can differ by a millisecond, so start times cannot place the first
    module; the last traced dispatch's module is the last one the trace
    holds (the window closes after its logits reached the host), so the
    records align from the end.  Each step variant is its own executable:
    an alignment that gives one module name two chunk counts is wrong, and
    the nearest consistent one is taken."""
    tail = len(mods) - len(recs)
    for off in sorted(range(tail - 3, tail + 4), key=lambda o: abs(o - tail)):
        seen: dict[str, int] = {}
        pairs = [(mods[off + i][2], r["n"]) for i, r in enumerate(recs)
                 if 0 <= off + i < len(mods)]
        if pairs and all(seen.setdefault(m, n) == n for m, n in pairs):
            return off
    return None


def match_steps(events: dict, records: list[dict]) -> list[dict]:
    """Each traced step module matched to the harness's record of the
    dispatch that launched it (modules run in dispatch order), with its
    device time and the parts of it spent in Pallas launches and in
    collectives."""
    disp = sorted(int(n.rsplit(".", 1)[1]) for n, _, _ in events["host"]
                  if n.startswith("bench.dispatch."))
    if not disp:
        return []
    recs = records[disp[0]:disp[-1] + 1]
    mods = sorted((s, d, n) for n, s, d in events["modules"]
                  if n.startswith(STEP_MODULE))
    off = _align(mods, recs)
    if off is None:
        return []
    pallas = _sorted_ops(events, lambda name, p: p)
    coll = _sorted_ops(events, lambda name, p: is_collective(name))
    steps = []
    for i, rec in enumerate(recs):
        if not 0 <= off + i < len(mods):
            continue
        s, d, _ = mods[off + i]
        steps.append(dict(rec, device_s=d * 1e-9,
                          pallas_s=_within(pallas, s, s + d),
                          collective_s=_within(coll, s, s + d)))
    return steps


def _sorted_ops(events: dict, keep) -> tuple[list, list]:
    """(starts, (start, dur) pairs) of chip 0's ops that ``keep`` takes."""
    ops = sorted((s, d) for name, s, d, p in events["ops"] if keep(name, p))
    return [s for s, _ in ops], ops


def _within(sorted_ops, lo: int, hi: int) -> float:
    """Seconds of the ops that start in [lo, hi), cut at ``hi``."""
    starts, ops = sorted_ops
    j = int(np.searchsorted(starts, lo))
    ns = 0
    while j < len(ops) and ops[j][0] < hi:
        ns += min(ops[j][0] + ops[j][1], hi) - ops[j][0]
        j += 1
    return ns * 1e-9


def reduce(events: dict, records: list[dict]) -> dict:
    """Window, busy time (chip 0's, and every chip's), idle attribution,
    top device ops and matched steps."""
    win = [(s, s + d) for n, s, d in events["host"] if n == "bench.window"]
    if win:
        lo, hi = win[0]
    else:
        ts = [s for _, s, _, *_ in events["ops"] + events["modules"]]
        te = [s + d for _, s, d, *_ in events["ops"] + events["modules"]]
        lo, hi = min(ts), max(te)
    dev = events["ops"] or [m + [False] for m in events["modules"]]
    busy = union(dev, lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    gaps = idle_gaps(busy, lo, hi)
    per_op: dict[str, float] = {}
    for name, s, d, *_ in events["ops"]:
        if s >= lo and s < hi:
            per_op[name] = per_op.get(name, 0.0) + d * 1e-9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(attribute(gaps, events["host"]).items(),
                  key=lambda kv: -kv[1])[:10]
    chip_busy_s = [sum(e - s for s, e in
                       union([[None, s, e - s] for s, e in ivs], lo, hi))
                   * 1e-9 for ivs in events.get("chip_busy", [])]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
            "chip_busy_s": chip_busy_s,
            "steps": match_steps(events, records),
            "device_ops": [[n, v] for n, v in top_ops],
            "idle_gaps": [[n, v] for n, v in idle]}
