"""End-to-end behaviour of the public API surface (the paper's system):
OpSpec -> planner -> autotuner -> generated fused kernel -> numerics,
plus the CLI entry points."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    return env


def test_whole_pipeline_plan_build_run(rng):
    """The README quickstart, as a test: describe two kernels, let the
    planner decide, build the fused kernel, check numerics + prediction."""
    from repro.core import planner
    from repro.kernels import paper_suite as ps

    eth, mk_e, ref_e = ps.make_ethash_like(R_dag=2048, bm=256)
    bl, mk_b, ref_b = ps.make_blake_like(R=1024, bm=256)
    plan = planner.plan([planner.GraphOp(eth), planner.GraphOp(bl)])
    assert len(plan.fused) == 1
    decision = plan.fused[0]
    assert decision.predicted_speedup_pct > 10.0   # paper: +15.9..65.8%

    fused = decision.result.build(interpret=True)
    xa, xb = mk_e(rng), mk_b(jax.random.PRNGKey(1))
    outs = fused(*xa, *xb)
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.asarray(ref_e(*xa)), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(outs[1], np.float32),
                               np.asarray(ref_b(*xb), np.float32),
                               rtol=1e-4, atol=1e-4)


def test_train_cli_smoke(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "granite-3-2b",
         "--scale", "smoke", "--steps", "6", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path), "--ckpt-every", "3", "--log-every", "2"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert out.returncode == 0, out.stderr[-2000:]
    assert "final loss" in out.stdout
    assert list(tmp_path.glob("step_*"))


def test_serve_cli_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "recurrentgemma-2b", "--requests", "3", "--prompt-len", "8",
         "--max-new", "4", "--batch", "2"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert out.returncode == 0, out.stderr[-2000:]
    assert "served 3 requests" in out.stdout


def test_peaks_are_keyed_by_device_kind():
    """The CPU plans for the v5e row; a TPU kind with no row raises instead
    of borrowing another chip's peaks."""
    from types import SimpleNamespace

    from repro.distributed import hlo_analysis as ha
    assert ha.chip() is ha.CHIPS[ha.PLANNING_TARGET]
    assert ha.PEAK_FLOPS == ha.CHIPS["TPU v5 lite"].peak_flops
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert ha.chip(v5e).hbm_bw == 819e9
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    try:
        ha.chip(unknown)
    except ValueError as e:
        assert "TPU v99" in str(e)
    else:
        raise AssertionError("an unknown TPU kind must raise")


def test_compile_cache_directory(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to the fixed directory inside the checkout."""
    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", was)
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(Path(__file__).resolve().parents[1]
                           / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
