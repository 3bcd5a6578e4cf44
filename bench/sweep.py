#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell on the chip, to find the
knee: the highest rate whose backlog does not grow over the window.

    python bench/sweep.py --workload granite2b-chat --rates 3,4,5,6 \
        --seconds 30 --seed 11

One process builds the engine once and serves the cell's traffic at each
rate in turn, each with its own ramp and window.  Per rate it prints the
requests due, those still waiting for a first token when the window opens
and when it closes, the tokens/s completed against those offered, and the
TTFT and token-gap percentiles.  The knee is read from these lines once,
when a cell is defined; the cell then fixes its rate in
``bench/cells/<workload>.json``.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def waiting(requests, t: float) -> int:
    """Requests due before ``t`` with no first token by ``t``."""
    return sum(1 for r in requests if r.due < t
               and not (r.token_times and r.token_times[0] < t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from bench import measure, spec, traffic
    from bench.openloop import OpenLoop
    from bench.run import build, check_devices, log, warm
    from repro import compile_cache

    cell = spec.load_cell(args.workload)
    check_devices(cell, spec.load_json(spec.BENCH_DIR / "peaks.json"))
    compile_cache.enable()
    _, _, engine = build(cell, args.seed)
    warm(engine, args.seed)
    mix = cell.traffic
    for rate in [float(r) for r in args.rates.split(",")]:
        planned = traffic.generate(mix, args.seed, args.seconds,
                                   engine.cfg.vocab_size, rate=rate)
        loop = OpenLoop(engine, planned, seconds=args.seconds,
                        ramp_s=mix["ramp_s"])
        loop.serve()
        reqs, o, c = loop.requests, loop.open, loop.close
        offered = sum(p.max_new for p in planned
                      if o <= loop.t0 + p.due_s < c) / args.seconds
        row = {"rate": rate,
               "due": sum(1 for r in reqs if o <= r.due < c),
               "waiting_at_open": waiting(reqs, o),
               "waiting_at_close": waiting(reqs, c),
               "offered_tokens_per_s": offered}
        for name in ("tokens_per_s", "ttft_p50_ms", "ttft_p90_ms",
                     "itl_p95_ms"):
            row[name] = measure.end_to_end(name, reqs, o, c, 0.0)
        row["occupancy_pct"] = 100.0 * (
            lambda w: w["slot_steps"] / max(1, engine.batch
                                            * w["decode_steps"]))(
            loop.window_counters())
        log("[sweep] " + json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
