"""A whole run of the harness at a CPU test size, in interpret mode: the
chip check skipped, everything else as on the chip.  Checks the hooks
(window, token times, counters, spans), that ``correct`` holds for the
program as it is, and that it comes out false with the timed path broken
underneath: a step that returns its state unchanged, half of the slots
left uncomputed, a token altered where it is produced.  And that the command itself refuses a CPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import bench.run as R
from bench import spec

FIX = spec.ROOT / "tests" / "bench" / "fixtures"
# The limit at this test size, set from readings on the CPU with
# the sample below: over 16 seeds, sound runs of the program read a mean
# gap of at most 2.05e-5 and the int8 control, on the same served tokens,
# at least 4.52e-5; a wrong token reads ~1e-2 and more.
TINY_LIMIT = 3.2e-5


def _cell(name="granite2b-chat", limit=TINY_LIMIT):
    bm = spec.load_benchmark()
    return spec.Cell(
        name=name, config_name="tiny-granite", traffic_name="tiny-chat",
        chips=1, config=spec.load_json(FIX / "tiny-granite.json"),
        traffic=spec.load_json(FIX / "tiny-chat.json"),
        params={"rate_per_s": 8.0, "logit_gap_mean_limit": limit,
                "sample_tokens": 300, "sample_requests": 40},
        end_to_end=[m for m in bm["end_to_end"] if spec._applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if spec._applies(m, name)])


def _run(trace=0, seed=2 ** 31 + 77, seconds=3.0, control=0):
    args = R.parse(["--workload", "x", "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", str(trace), "--control",
                    str(control)])
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")["TPU v5 lite"]
    return R.run(_cell(), args, peaks, jax.devices())


def _break(monkeypatch, fault):
    build = R.build

    def broken_build(cell, seed):
        cfg, params, engine = build(cell, seed)
        if fault == "token":
            sample = engine._sample

            def altered(logits, req):
                tok = sample(logits, req)
                return (tok + 1) % cfg.vocab_size \
                    if len(req.out_tokens) == 2 else tok
            engine._sample = altered
        elif fault == "state":
            cb_step = engine._cb_step

            def unchanged(n):
                fn = cb_step(n)

                def call(params, cache, *a, **kw):
                    out = fn(params, cache, *a, **kw)
                    return (out[0], cache) + tuple(out[2:])
                return call
            engine._cb_step = unchanged
        elif fault == "half":
            cb_step = engine._cb_step

            def half(n):
                fn = cb_step(n)

                def call(*a, **kw):
                    out = fn(*a, **kw)
                    lg = out[0]
                    h = lg.shape[0] // 2
                    # the second half of the slots gets the first half's
                    # logits: half of the batch is never computed
                    lg = lg.at[h:].set(lg[:lg.shape[0] - h])
                    return (lg,) + tuple(out[1:])
                return call
            engine._cb_step = half
        return cfg, params, engine
    monkeypatch.setattr(R, "build", broken_build)


def test_run_end_to_end_metrics_and_hooks(capsys):
    res = _run(trace=0)
    out = capsys.readouterr().out
    assert res["correct"] is True
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert "compiles inside it: {'traces': 0, 'backend_compiles': 0}" in out
    assert "release lag median" in out and "[ttft]" in out
    c = res["compared"]["logit_gap_mean"]
    assert c["value"] <= c["limit"] == TINY_LIMIT


def test_traced_run_reports_counters_and_spans():
    res = _run(trace=1)
    m = res["metrics"]
    assert m["fused_chunk_share"]["value"] == 100.0
    assert "occupancy" not in m          # listed for the decode cell only
    # the CPU trace has no TPU plane: device metrics stay silent
    assert "decode_step_ms" not in m and "step_mfu" not in m
    assert res["device"]["window_s"] > 0
    idle = dict(res["breakdown"]["idle_gaps"])
    assert set(idle) <= {"schedule", "dispatch", "sync", "sample", "none"}
    assert res["correct"] is True


@pytest.mark.parametrize("fault", ["token", "state", "half"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    res = _run(trace=0)
    assert res["correct"] is False
    c = res["compared"]["logit_gap_mean"]
    assert c["value"] > c["limit"]


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(spec.BENCH_DIR / "run.py"),
                        "--workload", "granite2b-chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
