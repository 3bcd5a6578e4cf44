#!/usr/bin/env python3
"""Bring-up check on a TPU: serve granite-3-2b at its published widths and
full depth (40 layers, d_model 2048, 32 heads / 8 KV heads x 64, d_ff 8192,
vocab 49155, bf16) through the executed fusion path, and check what it
serves.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # tensor-parallel over 4 chips, compared
                                    # token for token with one chip

The engine is built exactly as ``python -m repro.launch.serve --arch
granite-3-2b --scale full --plan-fusion`` builds it (continuous scheduling,
chunked prefill, the planned and fused Pallas decode program), with random
weights and prompts drawn from ``--seed``.  Every phase runs in this one
process, which owns the chip.

One chip: serve the trace twice (the first run compiles every step variant
it meets, the second is warm and must reproduce the first token for token),
check that every request got its full token budget and every logit is
finite, and check each request's first-token logits against ``lm.prefill``
run in float32 on the same prompts.  ``--tp 4``: serve the trace
tensor-parallel over four chips and on one chip, and require identical token
streams; nothing else runs.

The last line of stdout is one JSON object; ``"ok": true`` appears only when
every check passed.  Exits non-zero, printing no result, when JAX finds no
TPU, when the checkout's ``src/`` is missing, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Relative L2 error allowed between the served first-token logits and the
# float32 reference.  The served path keeps weights, activations and the KV
# cache in bfloat16 (8-bit mantissa, relative rounding 2^-9 ~ 0.2%); each of
# the 40 layers rounds its attention output, its FFN output and the residual
# stream, about 120 roundings that add like a random walk: sqrt(120) * 2^-9
# ~ 2.1%.  5% leaves room for the softmax and the 49155-wide unembedding,
# while a wrong kernel (a dropped head, a misplaced chunk, a stale cache row)
# moves the logits by far more than that.
REL_L2_BOUND = 0.05

# the served trace: 10 requests of 300-token prompts (two prefill chunks
# each), 16 new tokens each, 8 slots, a 256-row chunk budget
BATCH, REQUESTS, PROMPT_LEN, MAX_NEW, CHUNK_ROWS = 8, 10, 300, 16, 256


def _serve_args(args):
    from repro.launch import serve as launcher
    argv = ["--arch", "granite-3-2b", "--scale", "full", "--plan-fusion",
            "--scheduling", "continuous", "--batch", str(BATCH),
            "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
            "--max-new", str(MAX_NEW), "--chunk-rows", str(CHUNK_ROWS),
            "--coresident-chunks", "2", "--seed", str(args.seed)]
    if args.tp > 1:
        argv += ["--mesh-shape", str(args.tp)]
    ap = launcher.make_parser()
    sargs = ap.parse_args(argv)
    launcher.check_args(ap, sargs)
    return launcher, sargs


def _record_logits(engine):
    """Wrap the engine's sampler to keep each request's first-token logits
    and count non-finite logits over every sampled token."""
    import numpy as np
    first, bad = {}, [0]
    sample = engine._sample

    def recording(logits, req):
        logits = np.asarray(logits, np.float32)
        bad[0] += int((~np.isfinite(logits)).sum())
        first.setdefault(req.rid, logits.copy())
        return sample(logits, req)

    engine._sample = recording
    return first, bad


def _run(engine, launcher, cfg, sargs):
    reqs = launcher.build_requests(cfg, sargs)
    t0 = time.perf_counter()
    engine.run(reqs)
    return reqs, time.perf_counter() - t0


def _check_program(engine, checks):
    checks["executed"] = bool(engine.executed)
    info = engine.cb_program_info
    checks["compiled_not_interpreted"] = bool(info) and not any(
        v["interpret"] for v in info.values())
    for n in sorted(info):
        print(f"[program] {n} prefill chunk(s): "
              f"{info[n]['fused_launches']} fused of "
              f"{info[n]['total_launches']} launches per layer — "
              + "; ".join(s["members"] for s in info[n]["steps"]))


def _check_served(reqs, bad, checks):
    short = [r.rid for r in reqs if len(r.out_tokens) != r.max_new_tokens]
    checks["every_request_complete"] = not short
    checks["logits_finite"] = bad[0] == 0
    print(f"[served] {len(reqs)} requests, "
          f"{sum(len(r.out_tokens) for r in reqs)} tokens; incomplete: "
          f"{short or 'none'}; non-finite logits: {bad[0]}")


def one_chip(args, checks):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm

    launcher, sargs = _serve_args(args)
    t0 = time.perf_counter()
    cfg, params = launcher.load_model(sargs)
    jax.block_until_ready(params)
    print(f"[model] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads / {cfg.num_kv_heads} KV "
          f"heads x {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}, {lm.count_params(cfg):,} params "
          f"(init {time.perf_counter() - t0:.1f}s)")
    engine = launcher.build_engine(sargs, cfg, params)
    print(f"[engine] batch {engine.batch}, cache length {engine.cache_len}, "
          f"prefill chunk rows {engine.chunk_rows()}, executed "
          f"{engine.executed}")
    first, bad = _record_logits(engine)

    reqs1, t_first = _run(engine, launcher, cfg, sargs)
    first.clear()
    reqs, t_warm = _run(engine, launcher, cfg, sargs)
    tokens = sum(len(r.out_tokens) for r in reqs)
    print(f"[timing] run 1 (compiles each step variant it meets) "
          f"{t_first:.2f}s, run 2 (warm) {t_warm:.2f}s: compile ~"
          f"{t_first - t_warm:.2f}s; run 2 served {tokens} tokens "
          f"({tokens / t_warm:.1f} tok/s, this check's wall clock)")
    _check_program(engine, checks)
    _check_served(reqs, bad, checks)
    checks["warm_run_reproduces_first"] = (
        [r.out_tokens for r in reqs] == [r.out_tokens for r in reqs1])
    st = engine.stats
    print(f"[slots] {st.prefill_chunks} prefill chunks "
          f"({st.fused_prefill_chunks} fused with decode work), "
          f"{st.decode_steps} decode steps, {st.mixed_steps} mixed")

    # float32 reference: the plain full-sequence forward on the same prompts
    t0 = time.perf_counter()
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    by_len = {}
    for r in reqs:
        by_len.setdefault(len(r.prompt), []).append(r)
    ref = {}
    with jax.default_matmul_precision("highest"):
        for plen, group in sorted(by_len.items()):
            toks = jnp.asarray(np.stack([r.prompt for r in group]))
            _, logits = jax.jit(
                lambda p, t: lm.prefill(cfg32, p, {"tokens": t},
                                        max_len=plen,
                                        compute_dtype=jnp.float32))(
                params, toks)
            for r, row in zip(group, np.asarray(logits, np.float32)):
                ref[r.rid] = row
    errs, same_top1 = [], 0
    for r in reqs:
        got, want = first[r.rid], ref[r.rid]
        errs.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
        same_top1 += int(got.argmax() == want.argmax())
    worst = max(errs)
    checks["first_token_logits_match_fp32_prefill"] = worst <= REL_L2_BOUND
    print(f"[reference] lm.prefill float32 ({time.perf_counter() - t0:.1f}s): "
          f"first-token logits relative L2 error max {worst:.3e}, mean "
          f"{sum(errs) / len(errs):.3e} (bound {REL_L2_BOUND}); top-1 agrees "
          f"on {same_top1}/{len(reqs)}")


def tensor_parallel(args, checks):
    import jax

    launcher, sargs = _serve_args(args)
    cfg, params = launcher.load_model(sargs)
    mesh = launcher.build_mesh(sargs)
    print(f"[model] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}; {args.tp}-way tensor parallel over "
          f"{[d.id for d in mesh.devices.flat]}")
    tp = launcher.build_engine(sargs, cfg, params, mesh)
    one = launcher.build_engine(sargs, cfg, params)
    _, bad = _record_logits(tp)
    reqs_tp, t_tp = _run(tp, launcher, cfg, sargs)
    reqs_one, t_one = _run(one, launcher, cfg, sargs)
    print(f"[timing] {args.tp} chips {t_tp:.2f}s, one chip {t_one:.2f}s "
          "(each run compiles its step variants)")
    _check_program(tp, checks)
    _check_served(reqs_tp, bad, checks)
    diverged = [a.rid for a, b in zip(reqs_tp, reqs_one)
                if a.out_tokens != b.out_tokens]
    checks["tp_streams_match_one_chip"] = not diverged
    print(f"[tp] token streams identical to one chip on "
          f"{len(reqs_tp) - len(diverged)}/{len(reqs_tp)} requests"
          + (f"; diverged: {diverged}" if diverged else ""))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="serve tensor-parallel over this many chips and "
                         "compare with one chip (runs only that phase)")
    ap.add_argument("--seed", type=int, default=0,
                    help="draws the weights and the prompts")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.tp:
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro import compile_cache
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {compile_cache.enable()}")

    checks: dict[str, bool] = {}
    try:
        (tensor_parallel if args.tp > 1 else one_chip)(args, checks)
    except Exception as e:                  # any phase that raised fails
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAIL ({type(e).__name__}: {e})", file=sys.stderr)
        return 1
    failed = [k for k, v in checks.items() if not v]
    print(f"[checks] {checks}")
    if failed:
        print(f"chip_smoke: FAIL {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
