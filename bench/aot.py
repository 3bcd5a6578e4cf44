#!/usr/bin/env python3
"""Compile a configuration's serve steps for a described TPU v5e, with no
chip attached, and print each step's memory analysis.

    JAX_PLATFORMS=cpu python bench/aot.py granite-3-2b [--batch 24]
    JAX_PLATFORMS=cpu python bench/aot.py granite-3.0-8b --chips 4

Builds the engine exactly as ``bench/run.py`` does, from the configuration
file, with parameter shapes only, and compiles the jitted continuous step
with 0, 1 and 2 prompt chunks; with ``--chips 4``, tensor-parallel over a
mesh of four described chips (the engine's own placement of its weights
is skipped: there is nothing to place).  Nothing runs: the numbers say
whether the step fits a chip's 16 GB, not how fast it is.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--chunks", default="0,1,2")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding
    from repro.models import lm
    from repro.serve.engine import PrefillBudget, ServeEngine

    from bench import model, spec

    jax.config.update("jax_enable_compilation_cache", False)
    c = spec.load_json(spec.BENCH_DIR / "configs" / f"{args.config}.json")
    cfg = model.model_config(c)
    batch = args.batch or c["serve"]["batch"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = None
    if args.chips > 1:
        import numpy as np
        mesh = Mesh(np.array(topo.devices[:args.chips]), ("model",))
        ServeEngine._tp_permuted_params = lambda self: self.params
        ServeEngine._tp_place = lambda self, tree, specs: tree
    # the engine picks interpret mode from the default backend: compile
    # the chip's path
    jax.default_backend = lambda: "tpu"
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    engine = ServeEngine(cfg, params, batch=batch,
                         max_len=c["serve"]["cache_rows"], plan_fusion=True,
                         scheduling="continuous",
                         prefill_budget=PrefillBudget(), mesh=mesh)
    C = engine.chunk_rows()

    def placed(tree, specs=None):
        if mesh is None:
            dev = SingleDeviceSharding(topo.devices[0])
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=dev), tree)
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    def sd(x):
        return placed(x, PartitionSpec())

    p_sd = placed(params, mesh and engine._tp_param_specs(params))
    cache = placed(jax.eval_shape(engine._init_slot_cache_local),
                   mesh and engine._tp_cache_specs())
    B = engine.batch
    i32 = jnp.int32
    print(f"{cfg.name}: batch {B}, cache rows {engine.cache_len}, chunk "
          f"rows {C}, params {model.Shape(c).params:,}, tensor parallel "
          f"over {engine.tp_shards}; per chip:")
    for n in [int(x) for x in args.chunks.split(",")]:
        step = engine._make_cb_step(n)
        kw = {}
        if n:
            kw = dict(ch_slots=sd(jax.ShapeDtypeStruct((n,), i32)),
                      ch_offs=sd(jax.ShapeDtypeStruct((n,), i32)),
                      ch_valid=sd(jax.ShapeDtypeStruct((n,), i32)),
                      ch_tokens=sd(jax.ShapeDtypeStruct((n, C), i32)))
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            p_sd, cache, sd(jax.ShapeDtypeStruct((B,), i32)),
            sd(jax.ShapeDtypeStruct((B,), jnp.bool_)), **kw).compile()
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"  {n} chunk(s): arguments {m.argument_size_in_bytes:,} B, "
              f"outputs {m.output_size_in_bytes:,} B, temp "
              f"{m.temp_size_in_bytes:,} B, aliased "
              f"{m.alias_size_in_bytes:,} B; total {total:,} B")
    return 0


if __name__ == "__main__":
    sys.exit(main())
