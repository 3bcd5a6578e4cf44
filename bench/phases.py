#!/usr/bin/env python3
"""The program's own spans and launch names in a profiler trace: the serve
loop's host phases put on the device trace's clock, and the per-layer
numbers read from them.

The serve engine marks each step it dispatches with a ``serve.step`` span
whose children are its host phases (``serve.admit``, ``serve.stage``,
``serve.dispatch``, ``serve.sync``, ``serve.sample``,
``serve.first_token``), and names each Pallas launch after its member ops
(``kernel_metadata={"launch": "a+b"}`` in the custom call's op text).
``bench/trace.load`` keeps neither; ``load`` here reads them from the same
trace:

    {"program":  [[name, start_ns, dur_ns, {arg: value}], ...],
     "launches": {op: launch}}   # op as bench/trace.load names it

``align`` finds the device→host clock offset that puts every step's
``jit_step`` module inside its host window, ``idle_by_phase`` splits the
device's idle time over the host phases on that clock, and
``host_loop_ms``, ``sync_gap_ms`` and ``attention_share`` read one number
each.  A trace of a program without these spans or names holds none of
them, and every reader then returns ``None``.

As a tool, it runs one cell as ``bench/run.py --trace 1`` does, keeps the
program's spans beside the events, and prints what it read:

    python3 bench/phases.py --workload granite2b-decode --seed 7 \
        --seconds 40 [--save DIR]
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import json
import os
import statistics
import sys
from pathlib import Path

if __package__ in (None, ""):
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import trace  # noqa: E402

PREFIX = "serve."
STEP = "serve.step"
ATTENTION = "decode_attn"        # the decode attention op's name prefix


def _launch(text: str):
    """The ``launch`` of a custom call's ``kernel_metadata={...}``."""
    i = text.find("kernel_metadata=")
    if i < 0:
        return None
    i += len("kernel_metadata=")
    depth, in_str, esc = 0, False, False
    for j in range(i, len(text)):
        ch = text[j]
        if in_str:
            if esc:
                esc = False
            elif ch == "\\":
                esc = True
            elif ch == '"':
                in_str = False
        elif ch == '"':
            in_str = True
        elif ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                try:
                    meta = json.loads(text[i:j + 1])
                except ValueError:
                    return None
                return meta.get("launch") if isinstance(meta, dict) \
                    else None
    return None


def load(trace_dir: str) -> dict:
    """The program's spans (host) and the launch name of each Pallas op of
    the first TPU device, from the ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"program": [], "launches": {}}
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    for line in (devices[0].lines if devices else ()):
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            if trace.PALLAS_TARGET in e.name:
                launch = _launch(e.name)
                if launch:
                    out["launches"][trace._short(e.name)] = launch
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    args = {k: v for k, v in trace._stats(e).items()
                            if not k.startswith("_")}
                    out["program"].append([e.name, int(e.start_ns),
                                           int(e.duration_ns), args])
    out["program"].sort(key=lambda p: (p[1], -p[2]))
    return out


def steps(events: dict) -> list[dict]:
    """Each ``serve.step`` span with its child phases in order:
    ``{"step", "start", "end", "phases": [[name, start, end, args]],
    "dispatch": [start, end, args] or None, "syncs": [[start, end]]}``."""
    spans = events.get("program") or []
    out = []
    for name, s, d, args in spans:
        if name == STEP:
            out.append({"step": args.get("step_num"), "start": s,
                        "end": s + d, "phases": [], "dispatch": None,
                        "syncs": []})
    starts = [st["start"] for st in out]
    for name, s, d, args in spans:
        if name == STEP:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s + d > out[i]["end"]:
            continue                     # outside every step
        st = out[i]
        st["phases"].append([name[len(PREFIX):], s, s + d, args])
        if name == "serve.dispatch":
            st["dispatch"] = [s, s + d, args]
        elif name == "serve.sync":
            st["syncs"].append([s, s + d])
    return out


def align(events: dict, program_steps: list[dict]):
    """One device→host clock offset for the trace.

    Every step that dispatched is paired, in order, with a ``jit_step``
    module (a dispatch the trace holds launched a module it holds); a
    module starts after its ``serve.dispatch`` starts and ends before the
    step's first ``serve.sync`` (the logits' copy) ends, so each pair
    bounds the offset from both sides.  Of the pairings whose bounds leave
    an interval, and that give each step variant one chunk count, the one
    needing the least offset wins: a pairing one step off needs a step's
    length.  Returns ``{"offset_ns", "width_ns", "lo_ns", "hi_ns",
    "pairs": [(step, [name, start, dur])]}``, or ``None``."""
    disp = [st for st in program_steps if st["dispatch"] and st["syncs"]]
    mods = sorted(([n, s, d] for n, s, d in events.get("modules", ())
                   if n.startswith(trace.STEP_MODULE)),
                  key=lambda m: m[1])
    best = None
    for k in range(len(mods) - len(disp) + 1 if disp else 0):
        pairs = list(zip(disp, mods[k:]))
        seen: dict = {}
        if any(seen.setdefault(m[0], st["dispatch"][2].get("chunks"))
               != st["dispatch"][2].get("chunks") for st, m in pairs):
            continue
        lo = max(st["dispatch"][0] - m[1] for st, m in pairs)
        hi = min(st["syncs"][0][1] - (m[1] + m[2]) for st, m in pairs)
        if lo > hi:
            continue
        need = 0 if lo <= 0 <= hi else min(abs(lo), abs(hi))
        if best is None or need < best[0]:
            best = (need, lo, hi, pairs)
    if best is None:
        return None
    _, lo, hi, pairs = best
    return {"offset_ns": (lo + hi) / 2, "width_ns": hi - lo, "lo_ns": lo,
            "hi_ns": hi, "pairs": pairs}


def _busy(events: dict, lo: float, hi: float):
    dev = events.get("ops") or [m + [False] for m in events["modules"]]
    return trace.union(dev, lo, hi)


def _overlap(s: float, e: float, a: float, b: float) -> float:
    """Length of [s, e) ∩ [a, b)."""
    return max(0.0, min(e, b) - max(s, a))


def idle_by_phase(events: dict, aligned) -> dict:
    """Seconds of device idle time under each host phase, on the aligned
    clock, between the first and the last matched module.  A gap's time is
    split over the phases it overlaps; time inside a ``serve.step`` but in
    none of its phases reads ``step``, time outside every step ``none``."""
    if not aligned:
        return {}
    off = aligned["offset_ns"]
    mods = [m for _, m in aligned["pairs"]]
    lo, hi = mods[0][1], mods[-1][1] + mods[-1][2]
    out: dict[str, float] = {}

    def add(name, ns):
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns * 1e-9

    prog = steps(events)
    for g0, g1 in trace.idle_gaps(_busy(events, lo, hi), lo, hi):
        g0, g1 = g0 + off, g1 + off
        outside = g1 - g0
        for st in prog:
            in_step = _overlap(st["start"], st["end"], g0, g1)
            if not in_step:
                continue
            outside -= in_step
            for name, s, e, _ in st["phases"]:
                t = _overlap(s, e, g0, g1)
                add(name, t)
                in_step -= t
            add("step", in_step)
        add("none", outside)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def host_loop_ms(events: dict):
    """Slot manager: median over the traced steps that dispatched of the
    ``serve.step`` span less its ``serve.sync`` children — the host's own
    work per step, in ms."""
    t = [(st["end"] - st["start"] - sum(e - s for s, e in st["syncs"]))
         for st in steps(events) if st["dispatch"]]
    return 1e-6 * statistics.median(t) if t else None


def sync_gap_ms(events: dict, aligned):
    """Device: median over the matched steps of the device's idle time
    inside the step's ``serve.sync`` spans on the aligned clock, in ms —
    the logits' trip to the host."""
    if not aligned:
        return None
    off = aligned["offset_ns"]
    t = []
    for st, _ in aligned["pairs"]:
        lo, hi = st["syncs"][0][0], st["syncs"][-1][1]
        busy = [(s + off, e + off)
                for s, e in _busy(events, lo - off, hi - off)]
        t.append(sum(e - s - sum(_overlap(b0, b1, s, e) for b0, b1 in busy)
                     for s, e in st["syncs"]))
    return 1e-6 * statistics.median(t)


def _pallas_in(events: dict, mod) -> list:
    s0, e0 = mod[1], mod[1] + mod[2]
    return [op for op in events.get("ops", ())
            if op[3] and s0 <= op[1] < e0]


def attention_share(events: dict, aligned):
    """Kernels and glue: device time of the matched steps' Pallas launches
    that hold a decode attention member, over the steps' device time, in
    %."""
    launches = events.get("launches") or {}
    if not aligned or not launches:
        return None
    total = att = 0.0
    for _, mod in aligned["pairs"]:
        total += mod[2]
        for name, _, d, _ in _pallas_in(events, mod):
            members = launches.get(name, "").split("+")
            if any(m.startswith(ATTENTION) for m in members):
                att += d
    return 100.0 * att / total if total > 0 else None


def unnamed_launches(events: dict, aligned) -> int:
    """Pallas ops inside the matched steps with no launch name."""
    launches = events.get("launches") or {}
    return sum(1 for _, mod in (aligned or {}).get("pairs", ())
               for op in _pallas_in(events, mod) if op[0] not in launches)


def report(events: dict) -> dict:
    """Everything this module reads from one trace."""
    prog = steps(events)
    al = align(events, prog)
    idle = idle_by_phase(events, al)
    phase_ms: dict[str, list] = {}
    for st in prog:
        if not st["dispatch"]:
            continue
        per: dict[str, float] = {}
        for name, s, e, _ in st["phases"]:
            per[name] = per.get(name, 0.0) + (e - s) * 1e-6
        for name, v in per.items():
            phase_ms.setdefault(name, []).append(v)
    return {"steps": len(prog),
            "dispatched": sum(1 for st in prog if st["dispatch"]),
            "matched": len(al["pairs"]) if al else 0,
            "offset_ms": al["offset_ns"] * 1e-6 if al else None,
            "offset_width_ms": al["width_ns"] * 1e-6 if al else None,
            "phase_ms_median": {k: statistics.median(v)
                                for k, v in phase_ms.items()},
            "idle_by_phase": idle,
            "idle_under_program": (1 - idle.get("none", 0.0)
                                   / sum(idle.values())) if idle else None,
            "unnamed_launches": unnamed_launches(events, al),
            "host_loop_ms": host_loop_ms(events),
            "sync_gap_ms": sync_gap_ms(events, al),
            "attention_share": attention_share(events, al)}


@contextlib.contextmanager
def keeping_program_spans():
    """While open, ``bench/trace.load`` also reads the program's spans and
    launch names (``load``), and the yielded dict keeps the ``events`` and
    ``records`` that a traced run of ``bench/run.py`` reduces."""
    kept: dict = {}
    base_load, base_reduce = trace.load, trace.reduce

    def load_all(trace_dir, *args):
        kept["events"] = dict(base_load(trace_dir, *args), **load(trace_dir))
        return kept["events"]

    def reduce_kept(events, records):
        kept["records"] = records
        return base_reduce(events, records)

    trace.load, trace.reduce = load_all, reduce_kept
    try:
        yield kept
    finally:
        trace.load, trace.reduce = base_load, base_reduce


def main(argv=None) -> int:
    """Run ``bench/run.py`` with ``--trace 1``, keeping the program's spans
    and launch names, then print what this module reads from them as one
    ``[phases]`` JSON line."""
    import argparse
    import gzip

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--save", default=None, metavar="DIR",
                    help="write <cell>.program.json.gz (events, program "
                         "spans, launches and the harness's records)")
    args, rest = ap.parse_known_args(argv)
    with keeping_program_spans() as kept:
        rc = run.main(["--workload", args.workload, *rest, "--trace", "1"])
    if rc or "events" not in kept:
        return rc or 1
    print("[phases] " + json.dumps(report(kept["events"])), flush=True)
    if args.save:
        Path(args.save).mkdir(parents=True, exist_ok=True)
        with gzip.open(Path(args.save) / f"{args.workload}.program.json.gz",
                       "wt") as fh:
            json.dump(kept, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
