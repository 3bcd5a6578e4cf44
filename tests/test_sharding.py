"""Sharding-rule resolution properties (hypothesis) + an 8-fake-device
mini dry-run in a subprocess (train + decode compile on a (2,2,2) pod mesh,
incl. the int8 pod-compressed gradient path)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (see "
                           "requirements.txt)")
from hypothesis import given, settings, strategies as st

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# resolve_pspec properties (no devices needed beyond 1)
# ---------------------------------------------------------------------------
def _mesh_1d():
    import jax
    return jax.make_mesh((1,), ("data",))


def test_resolve_drops_nondivisible():
    import jax
    from repro.distributed.sharding import resolve_pspec
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 8}

    spec = resolve_pspec(("batch", "ffn"), (6, 64), FakeMesh(),
                         {"batch": "data", "ffn": "model"})
    assert spec[0] is None          # 6 % 4 != 0 -> replicated
    assert spec[1] == "model"       # 64 % 8 == 0


@settings(max_examples=40, deadline=None)
@given(b=st.integers(1, 64), f=st.integers(1, 128),
       data=st.sampled_from([2, 4, 8]), model=st.sampled_from([2, 8, 16]))
def test_resolve_never_overassigns(b, f, data, model):
    from repro.distributed.sharding import resolve_pspec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": data, "model": model}

    spec = resolve_pspec(("batch", "ffn", "act_ffn"), (b, f, f), FakeMesh(),
                         {"batch": "data", "ffn": "model",
                          "act_ffn": "model"})
    used = [s for s in spec if s is not None]
    flat = []
    for s in used:
        flat.extend(s if isinstance(s, tuple) else [s])
    assert len(flat) == len(set(flat))            # no axis used twice
    for name, dim in zip(spec, (b, f, f)):
        if name == "data":
            assert dim % data == 0
        if name == "model":
            assert dim % model == 0


def test_pod_rules_remap():
    from repro.distributed.sharding import rules_for
    from repro.configs import get_config

    class PodMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 2, "model": 2}

    rules = rules_for(get_config("granite-3-2b"), PodMesh())
    assert rules["batch"] == ("pod", "data")
    rules_ds = rules_for(get_config("deepseek-v2-236b"), PodMesh())
    assert rules_ds["expert"] == ("pod", "data")  # moe-huge FSDP experts


# ---------------------------------------------------------------------------
# subprocess mini dry-run on 8 fake devices
# ---------------------------------------------------------------------------
MINI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_debug_mesh
    from repro.launch import input_specs as ispecs
    from repro.launch.dryrun import build_cell
    from repro.configs.base import ShapeConfig
    from repro.distributed.hlo_analysis import analyze_compiled

    mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config({arch!r}).reduced()
    shape = ShapeConfig("t", 64, 8, {kind!r})
    rules = shd.rules_for(cfg, mesh)
    with shd.use_sharding(mesh, rules):
        fn, args, in_sh, donate = build_cell(cfg, shape, mesh)
        compiled = jax.jit(fn, in_shardings=in_sh,
                           donate_argnums=donate).lower(*args).compile()
    r = analyze_compiled(compiled, mesh.size)
    print("RESULT", r.flops > 0, r.coll_bytes >= 0)
""")


@pytest.mark.parametrize("arch,kind", [
    ("granite-3-2b", "train"),
    ("deepseek-v2-236b", "train"),
    ("recurrentgemma-2b", "decode"),
    ("phi3.5-moe-42b-a6.6b", "decode"),
    ("xlstm-1.3b", "prefill"),
])
def test_mini_multipod_compile(arch, kind):
    code = MINI.format(src=SRC, arch=arch, kind=kind)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert "RESULT True True" in out.stdout, out.stderr[-3000:]


def test_pod_compressed_grads_compile():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, {src!r})
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.dryrun import build_cell
        from repro.configs.base import ShapeConfig
        from repro.models import lm
        from repro.models.base import abstract_params, logical_axes
        from repro.train.train_loop import TrainConfig, make_train_step
        from repro.train import optimizer as opt_mod

        mesh = make_debug_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("granite-3-2b").reduced()
        rules = shd.rules_for(cfg, mesh)
        specs = lm.param_specs(cfg)
        pa = abstract_params(specs, jnp.bfloat16)
        ps = shd.sharding_tree(pa, logical_axes(specs), mesh, rules)
        tcfg = TrainConfig(remat=False, compression="int8_pod")
        step = make_train_step(cfg, tcfg, mesh)
        oa = opt_mod.OptState(
            m=jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), pa),
            v=jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), pa),
            count=jax.ShapeDtypeStruct((), jnp.int32))
        batch = {{"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}}
        with shd.use_sharding(mesh, rules):
            c = jax.jit(step).lower(pa, oa, batch,
                                    jax.ShapeDtypeStruct((), jnp.int32)).compile()
        txt = c.as_text()
        assert "s8" in txt, "int8 not on the wire"
        print("RESULT OK")
    """).format(src=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert "RESULT OK" in out.stdout, out.stderr[-3000:]


def test_collective_parser():
    from repro.distributed.hlo_analysis import collective_bytes
    hlo = """
      %all-reduce.1 = f32[1024,256]{1,0} all-reduce(%x), replica_groups=[16,2]<=[32]
      %ag = bf16[64,128]{1,0} all-gather(%y), replica_groups={{0,1,2,3}}
      %cp-start = (f32[128]{0}) collective-permute-start(%z)
      %noise = f32[8]{0} add(%a, %b)
    """
    st = collective_bytes(hlo, 32)
    assert st.by_kind["all-reduce"] == pytest.approx(2 * 1024 * 256 * 4 * 0.5)
    assert st.by_kind["all-gather"] == pytest.approx(64 * 128 * 2 * 0.75)
    assert st.count == 3


def test_collective_counts_and_dtypes():
    """What the tensor-parallel engine reads off a compiled step: the
    collectives by kind (an async pair once), and the element types each
    reads and writes (the TPU compiler folds a psum's rounding to bf16
    into the all-reduce, which then reads fp32 and writes bf16)."""
    from repro.distributed.hlo_analysis import (collective_counts,
                                                collective_dtypes)
    hlo = """
      %fusion.76 = f32[32,4096]{1,0:T(8,128)S(1)} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/shard_map/while/body/tp_attn_out_psum/dot_general" stack_frame_id=65}
      %all-reduce.2 = bf16[32,4096]{1,0:T(8,128)(2,1)S(1)} all-reduce(%fusion.76), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%sum
      %b = f32[8]{0} parameter(1)
      %ars = (f32[8]{0}, f32[8]{0}) all-reduce-start(%b), replica_groups={{0,1}}
      %ard = f32[8]{0} all-reduce-done(%ars)
      %y = bf16[16,128]{1,0} parameter(0)
      ROOT %ag = bf16[64,128]{1,0} all-gather(%y), replica_groups={{0,1,2,3}}
      %noise = s32[8]{0} add(%a, %b), metadata={op_name="jit(step)/tp_ffn_out_psum/add"}
    """
    assert collective_counts(hlo) == {"all-reduce": 2, "all-gather": 1,
                                      "reduce-scatter": 0, "all-to-all": 0,
                                      "collective-permute": 0}
    assert collective_dtypes(hlo) == ["bf16->bf16", "f32->bf16",
                                      "f32->f32"]
