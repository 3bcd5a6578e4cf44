"""Ahead-of-time compiles for a TPU v5e chip at granite-3-2b's published
widths: every kernel of the serve path and every launch of the planned
decode program, compiled by the TPU compiler against a described (not
attached) ``v5e:2x2`` topology.  Interpret mode never checks VMEM limits,
tiling or what the Mosaic compiler can lower; these do, at no chip time.

Nothing runs: each test asserts that the compiled module holds the Pallas
kernel (``tpu_custom_call``) and that its memory analysis fits one chip.
The topology is described inside a module fixture, so only the worker that
runs this file loads the TPU compiler.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import stitch
from repro.models import lm
from repro.serve.engine import PrefillBudget, ServeEngine

HBM_BYTES = 16 * 10 ** 9        # one v5e chip

# the chip_smoke.py serving shape: batch 8, 300-token prompts + 16 new
# tokens (max_len 325 -> a 384-row cache), 256-row chunk budget, two
# co-resident chunks
SMOKE = dict(batch=8, max_len=325,
             prefill_budget=PrefillBudget(chunk_rows=256,
                                          max_coresident_chunks=2))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("granite-3-2b")
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    return ServeEngine(cfg, params, plan_fusion=True, **SMOKE)


def _compile(fn, ops, sharding):
    args = [jax.ShapeDtypeStruct(o.shape, o.dtype, sharding=sharding)
            for op in ops for o in op.inputs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES
    return compiled


def _graph_op(engine, prefix, **kw):
    return next(g.op for g in engine.decode_graph(**kw)
                if g.op.name.startswith(prefix))


def _unstitched(engine):
    engine.stitch_epilogues = False
    try:
        return {g.op.name: g.op for g in engine.decode_graph()}
    finally:
        engine.stitch_epilogues = True


def _kernel(engine, which):
    """The OpSpec a case compiles, taken from the engine's decode graph."""
    from repro.kernels.decode_attention import decode_attention_op
    if which == "decode_attention_B8_S2048":
        cfg = engine.cfg
        return decode_attention_op(
            B=8, S=2048, H=cfg.num_heads, Hkv=cfg.num_kv_heads,
            D=cfg.resolved_head_dim, dtype=jnp.bfloat16,
            ck=engine._kv_chunk(2048), dynamic_length=True)
    if which == "prefill_chunk":
        return _graph_op(engine, "prefill_attn", prefill_chunks=1)
    ops = _unstitched(engine)
    if which == "chain_norm1_qkv":
        return stitch.stitch(ops["decode_norm1"], ops["qkv_proj"], "x")
    if which == "chain_ffn_act":
        return stitch.stitch(ops["ffn_proj"], ops["decode_act"], "h")
    return ops[which]


@pytest.mark.parametrize("which", [
    "decode_attention_B8_S2048", "prefill_chunk", "qkv_proj", "ffn_proj",
    "decode_norm1", "decode_act", "chain_norm1_qkv", "chain_ffn_act"])
def test_kernel_compiles_for_v5e(one_chip, engine, which):
    from repro.core import hfuse
    op = _kernel(engine, which)
    _compile(hfuse.run_single(op), (op,), one_chip)


def test_shapes_are_published_widths(engine):
    ops = {g.op.name: g.op for g in engine.decode_graph(prefill_chunks=1)}
    assert ops["ffn_proj"].inputs[1].shape == (2048, 16384)   # [gate | up]
    assert ops["qkv_proj"].inputs[1].shape == (2048, 3072)
    pf = next(op for n, op in ops.items() if n.startswith("prefill_attn"))
    C = engine.chunk_rows()
    assert pf.inputs[1].shape == (32, C, 64) and 300 > C >= 128


def test_planned_decode_program_compiles_for_v5e(one_chip, engine):
    """Every launch of the program the engine executes with two prefill
    chunks riding the decode step — fused bundles included."""
    prog = engine.build_decode_program(prefill_chunks=2, interpret=False)
    assert prog.n_fused >= 1 and not prog.interpret
    for step in prog.steps:
        _compile(step.call, step.ops, one_chip)


def test_fused_launch_is_named_in_compiled_text(one_chip, engine):
    """The compiled custom call of a fused bundle is named after its
    members and carries them in ``kernel_metadata``, which the device
    trace's op text keeps."""
    import json
    import re
    prog = engine.build_decode_program(prefill_chunks=1, interpret=False)
    step = next(s for s in prog.steps if s.fused)
    text = _compile(step.call, step.ops, one_chip).as_text()
    launch = "+".join(step.members)
    assert f'"launch":{json.dumps(launch)}' in text
    name = re.sub(r"\W", "_", launch, flags=re.ASCII)
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call\(", text)


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = [a-z0-9]+\[([0-9,]*)\]\S* "
                    r"([\w\-]+)\(([^)]*)\)")


def _instructions(text):
    """name -> (result dims, opcode, operand names) of every instruction
    of a compiled module's text, fused computations included."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            dims = tuple(int(x) for x in m.group(2).split(",") if x)
            out[m.group(1)] = (dims, m.group(3),
                               re.findall(r"%([\w.\-]+)", m.group(4)))
    return out


@pytest.mark.parametrize("n", [0, 1])
def test_cb_step_keeps_kv_cache_in_place(one_chip, engine, monkeypatch, n):
    """The continuous step with ``n`` chunks at granite-3-2b's widths and
    depth, as the chip compiles it: no operation reads or writes a whole
    layer's K/V block (the slice, relayout and write-back the layer scan
    made of it), none copies or slices the whole stacked cache, and the
    cache arguments are donated to the cache the step returns."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine._cb_steps.pop(n, None)
    try:
        step = engine._cb_step(n)
    finally:
        engine._cb_steps.pop(n, None)
    assert engine.cb_program_info[n]["kv_in_place"]
    cfg, B, S = engine.cfg, engine.batch, engine.cache_len
    L, Hkv, D = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim

    def sd(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sd, engine.params)
    cache = jax.tree.map(sd, jax.eval_shape(engine._init_slot_cache_local))
    i32 = jnp.int32
    kw = {}
    if n:
        C = engine.chunk_rows()
        kw = {k: sd(jax.ShapeDtypeStruct(s, i32)) for k, s in (
            ("ch_slots", (n,)), ("ch_offs", (n,)), ("ch_valid", (n,)),
            ("ch_tokens", (n, C)))}
    text = step.lower(params, cache, sd(jax.ShapeDtypeStruct((B,), i32)),
                      sd(jax.ShapeDtypeStruct((B,), jnp.bool_)),
                      **kw).compile().as_text()
    assert f"decode_attn_B{B}_S{S}_H{cfg.num_heads}kv{Hkv}_L{L}" in text
    layer = {(B, S, Hkv, D), (1, B, S, Hkv, D), (B, S, Hkv * D),
             (1, B, S, Hkv * D)}
    whole = {(L, B, S, Hkv, D), (L, B, S, Hkv * D)}
    ins = _instructions(text)
    assert len(ins) > 100
    moves = [name for name, (dims, _op, args) in ins.items()
             if dims in layer or any(ins.get(a, ((),))[0] in layer
                                     for a in args)]
    moves += [name for name, (dims, op, _args) in ins.items()
              if dims in whole and op in ("copy", "copy-start",
                                          "copy-done", "dynamic-slice")]
    assert not moves
    n_params = len(jax.tree.leaves(params))
    # the module's first line: input_output_alias={ {out}: (arg, {}, ..
    header = text.splitlines()[0]
    donated = {int(p) for p in re.findall(r"\{\d+\}: \((\d+), ", header)}
    assert donated == set(range(n_params,
                                n_params + len(jax.tree.leaves(cache))))
