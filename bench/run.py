#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload granite2b-chat --seed 7 --seconds 40 \
        --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.  A
run draws the weights on the device from ``--seed``, builds the program's
serve engine (continuous batching, chunked prefill, the planned and fused
Pallas step), warms every step variant the traffic uses, serves the
traffic open-loop for ``--seconds`` of measured window, and checks what it
served against the float32 reference.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` records a profiler trace over the
window's last seconds and reports the per-layer metrics.

The last line of stdout is one JSON object.  Exits non-zero, printing no
result, when JAX finds no TPU, fewer chips than the cell asks for, or a
chip with no row in ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

# the TPU runtime otherwise logs to a fixed directory outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

TRACE_S = 3.0            # traced seconds, at the end of the window
RETIRED_OK = ("max_new", "eos")


class NoChip(SystemExit):
    """This machine cannot run the cell: exit 2 with no result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check_devices(cell, peaks: dict):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"bench: {cell.name} needs {cell.chips} chips, found "
                     f"{len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"bench: no peaks for device kind "
                     f"{devs[0].device_kind!r} in bench/peaks.json")
    return devs


def build(cell, seed: int):
    """Weights and the serve engine as the program builds it.  A cell on
    several chips serves the model tensor-parallel over a one-axis mesh
    (``model``) of the first ``chips`` devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.schedule_cache import default_cache
    from repro.serve.engine import PrefillBudget, ServeEngine

    from bench import model

    cfg = model.model_config(cell.config)
    serve = cell.config["serve"]
    mesh = (Mesh(np.array(jax.devices()[:cell.chips]), ("model",))
            if cell.chips > 1 else None)
    t = time.perf_counter()
    params = jax.block_until_ready(model.init_weights(cell.config, seed,
                                                      mesh=mesh))
    log(f"[weights] {cfg.name}: {model.Shape(cell.config).params:,} params "
        f"drawn on {cell.chips} device(s) in "
        f"{time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    engine = ServeEngine(cfg, params, batch=serve["batch"],
                         max_len=serve["cache_rows"], plan_fusion=True,
                         schedule_cache=default_cache(),
                         scheduling="continuous",
                         prefill_budget=PrefillBudget(), mesh=mesh)
    if not engine.executed:
        raise RuntimeError(f"{cfg.name}: the step is not executed through "
                           "the fused program")
    log(f"[engine] batch {engine.batch}, cache rows {engine.cache_len}, "
        f"prefill chunk rows {engine.chunk_rows()} (derived), tensor "
        f"parallel over {engine.tp_shards}, planned in "
        f"{time.perf_counter() - t:.2f}s")
    return cfg, params, engine


def warm(engine, seed: int) -> None:
    """Compile every step variant the traffic uses (0, 1 and 2 chunks) with
    one short serve: two prompts chunk together at step 0, both decode at
    step 1, and a third prompt chunks beside them at step 2."""
    import numpy as np
    from repro.serve.engine import Request

    P = min(engine.chunk_rows(), engine.cache_len // 2)
    rng = np.random.default_rng(seed % 2 ** 64)
    V = engine.cfg.vocab_size
    t = time.perf_counter()
    engine.run([Request(rid=i, prompt=rng.integers(0, V, P, np.int32),
                        max_new_tokens=4, arrival=at)
                for i, at in enumerate((0, 0, 2))])
    missing = {0, 1, 2} - set(engine._cb_steps)
    if missing:
        raise RuntimeError(f"warm-up left step variants {missing} cold")
    log(f"[warm] step variants {sorted(engine._cb_steps)} ready in "
        f"{time.perf_counter() - t:.2f}s")
    for n, info in sorted(engine.cb_program_info.items()):
        log(f"[program] {n} chunk(s): {info['fused_launches']} fused of "
            f"{info['total_launches']} launches per layer; interpret "
            f"{info['interpret']}")


def _bytes_on(leaves, dev) -> int:
    """Bytes of the arrays ``leaves`` that lie on device ``dev``."""
    return sum(sh.data.nbytes for x in leaves for sh in x.addressable_shards
               if sh.device == dev)


def memory_line(cell, params, engine) -> None:
    """Per chip: the weights drawn, the arrays of the step's own copy of
    them where the engine keeps one, the K/V cache, and each chip's memory
    in use."""
    import jax
    from bench import model
    devs = jax.devices()[:cell.chips]
    drawn = jax.tree_util.tree_leaves(params)
    ids = {id(x) for x in drawn}
    w = _bytes_on(drawn, devs[0])
    step = _bytes_on([x for x in jax.tree_util.tree_leaves(
        engine._step_params) if id(x) not in ids], devs[0])
    kv = model.Shape(cell.config).cache_bytes(
        engine.batch, engine.cache_len) // engine.tp_shards
    stats = [d.memory_stats() or {} for d in devs]
    log(f"[memory] per chip: weights {w:,} B, the step's copy {step:,} B, "
        f"KV cache {kv:,} B; in use "
        f"{[s.get('bytes_in_use', 0) for s in stats]} B, peak "
        f"{[s.get('peak_bytes_in_use', 0) for s in stats]} B of "
        f"{stats[0].get('bytes_limit', 0):,} B")


def serve(cell, engine, args):
    from bench import traffic
    from bench.openloop import OpenLoop

    mix = cell.traffic
    planned = traffic.generate(mix, args.seed, args.seconds,
                               engine.cfg.vocab_size,
                               rate=cell.params.get("rate_per_s"))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    loop = OpenLoop(engine, planned, seconds=args.seconds,
                    ramp_s=mix.get("ramp_s"),
                    open_when_started=(engine.batch
                                       if mix["kind"] == "backlog" else None),
                    trace_s=min(TRACE_S, args.seconds / 2) if args.trace
                    else 0.0,
                    trace_dir=trace_dir)
    log(f"[traffic] {cell.traffic_name}: {len(planned)} requests planned"
        + (f" at {cell.params['rate_per_s']} req/s"
           if "rate_per_s" in cell.params else ""))
    loop.serve()
    return loop, trace_dir


def per_layer(cell, loop, trace_dir, args, peaks) -> tuple[dict, dict]:
    from bench import model, trace

    events = trace.load(trace_dir, cell.chips)
    if args.save_trace:
        Path(args.save_trace).mkdir(parents=True, exist_ok=True)
        with open(Path(args.save_trace) / f"{cell.name}.events.json",
                  "w") as fh:
            json.dump({"events": events, "records": loop.steps}, fh)
        with open(Path(args.save_trace) / f"{cell.name}.describe.json",
                  "w") as fh:
            json.dump(trace.describe(trace_dir), fh)
    red = trace.reduce(events, loop.steps)
    for st in red["steps"]:
        st["flops"], st["bytes"] = model.step_work(cell.config, st["pos"],
                                                   st["chunks"])
    ctx = {"counters": loop.window_counters(), "batch": loop.engine.batch,
           "steps": red["steps"], "window_s": red["window_s"],
           "busy_s": red["busy_s"], "chip_busy_s": red["chip_busy_s"],
           "chips": cell.chips, "peaks": peaks}
    log(f"[trace] {red['window_s']:.3f}s traced, {len(red['steps'])} steps "
        f"matched of {len(loop.steps)} dispatched, busy "
        f"{red['busy_s']:.3f}s (every chip: {red['chip_busy_s']})")
    metrics = {}
    for m in cell.per_layer:
        value = spec.load_metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def correctness(cell, params, loop, rows: int, args):
    """Compare a sample of the finished requests with the reference."""
    from bench import reference

    p = cell.params
    sample = reference.pick_sample(loop.requests, args.seed,
                                   p.get("sample_tokens", 512),
                                   p.get("sample_requests", 8))
    t = time.perf_counter()
    res = reference.compare(cell.config, params, sample,
                            rows, control=args.control)
    log(f"[reference] {res['requests']} requests, {res['positions']} served "
        f"tokens compared in {time.perf_counter() - t:.2f}s; reference "
        f"agrees on top-1 at {res['agree_top1']}; gap mean "
        f"{res['logit_gap_mean']!r}, widest {res['logit_gap_max']!r}"
        + (f"; int8 control gap mean {res['control_logit_gap_mean']!r}, "
           f"widest {res['control_logit_gap_max']!r}" if args.control
           else ""))
    return res


def run(cell, args, peaks: dict, devs) -> dict:
    """One run on ``devs``; ``peaks`` is the chip's row of the peaks
    table."""
    import jax
    import numpy as np
    from repro import compile_cache

    from bench import measure

    log(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, params, engine = build(cell, args.seed)
    warm(engine, args.seed)
    memory_line(cell, params, engine)
    loop, trace_dir = serve(cell, engine, args)
    setup_s = loop.open - T_PROCESS
    chip_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs[:cell.chips]]
    peak = max(chip_peaks)
    reqs = loop.requests
    due = [r for r in reqs if r.due < loop.close]
    started = [r for r in reqs if r.token_times]
    finished = [r for r in reqs if r.done]
    reasons = {rid: why for _, rid, why in engine.stats.retirements}
    failed = sum(1 for r in finished if reasons.get(r.rid) not in RETIRED_OK)
    lag_med, lag_max = loop.release_lag()
    log(f"[requests] due {len(due)}, started {len(started)}, finished "
        f"{len(finished)}, failed {failed}; release lag median "
        f"{lag_med * 1e3:.3f} ms, max {lag_max * 1e3:.3f} ms")
    log(f"[window] {args.seconds}s from {setup_s:.2f}s after start; "
        f"compiles inside it: {loop.compiles_in_window}; counters "
        f"{loop.window_counters()}")
    ttft = measure.ttft_samples(reqs, loop.open, loop.close)
    if ttft:
        log(f"[ttft] {len(ttft)} requests due in the window, median "
            f"{np.median(ttft) * 1e3:.1f} ms")
    log(f"[memory] peak {peak:,} B in use (every chip: {chip_peaks})")

    result = {"correct": False, "attempted": len(due), "failed": failed,
              "metrics": {}}
    red = None
    if args.trace:
        result["metrics"], red = per_layer(cell, loop, trace_dir, args,
                                           peaks)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {
                "value": measure.end_to_end(m["name"], reqs, loop.open,
                                            loop.close, setup_s),
                "unit": m["unit"]}
    dev = devs[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devs), "memory_peak_bytes": int(peak)}
    if red is not None:
        busy = red["chip_busy_s"] or [red["busy_s"]]
        result["device"].update(busy_s=sum(busy) / len(busy),
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}

    # the program's state goes before the reference runs
    rows = engine.cache_len
    loop.engine = None
    del engine
    gc.collect()
    res = correctness(cell, params, loop, rows, args)
    limit = float(cell.params["logit_gap_mean_limit"])
    compared = {"logit_gap_mean": {"value": res["logit_gap_mean"],
                                   "limit": limit}}
    if args.control:
        compared["control_logit_gap_mean"] = {
            "value": res["control_logit_gap_mean"], "limit": limit}
    result["correct"] = bool(res["requests"] > 0
                             and res["logit_gap_mean"] <= limit)
    result["compared"] = compared
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0,
                    help="also read the int8 control's widest gap (a "
                         "reading for setting the limit; not part of a "
                         "benchmark run)")
    ap.add_argument("--save-trace", default=None, metavar="DIR",
                    help="with --trace 1, keep the normalized trace and "
                         "its layout in DIR")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")
    try:
        devs = check_devices(cell, peaks)
    except NoChip as e:
        print(e.code, file=sys.stderr)
        return 2
    result = run(cell, args, peaks[devs[0].device_kind], devs)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
