"""The model side of the yardstick: a configuration file turned into the
program's ``ModelConfig``, weights drawn from the seed, and the work a step
needs (operations and bytes), counted from the model's shapes alone.

Configuration files use the key names of the model's published
``config.json`` and hold the values as they are run.  Whatever depends on
the architecture lives in ``bench/archs/<model_type>.py``, found by the
file's ``model_type``; adding an architecture is adding that file.  It
gives:

    Shape(c)                        sizes: ``params``, ``cache_bytes(batch,
                                    rows)``
    model_config(c)                 the program's ``ModelConfig``; refuses
                                    values the program cannot run
    step_work(c, active_pos, chunks)  (FLOPs, bytes) one serve step needs
    forward(c, params, tokens, int8)  the float32 reference's logits,
                                    importing nothing of the program
    leaf_std(names, shape)          optional: the standard deviation of a
                                    leaf that ``init_weights``'s rules do
                                    not know (such as a router), or None
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import numpy as np

ARCH_DIR = Path(__file__).resolve().parent / "archs"


def arch(c: dict):
    """The module of ``c``'s architecture: ``bench/archs/<model_type>.py``."""
    kind = c.get("model_type")
    path = ARCH_DIR / f"{kind}.py"
    if not kind or not path.is_file():
        raise ValueError(f"{c.get('name')}: no architecture module for "
                         f"model_type {kind!r}; add {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_arch_{kind.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def Shape(c: dict):
    return arch(c).Shape(c)


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    return arch(c).model_config(c)


def step_work(c: dict, active_pos, chunks) -> tuple[float, float]:
    """(FLOPs, bytes) one serve step of ``c`` needs (see the arch module)."""
    return arch(c).step_work(c, active_pos, chunks)


def seed_words(seed: int) -> tuple[int, int]:
    """Two 31-bit words from any whole number, for PRNG keys."""
    w = np.random.SeedSequence(seed % 2 ** 64).generate_state(2)
    return int(w[0] >> 1), int(w[1] >> 1)


def draw_spec(name: str, ndim: int, axis: str):
    """Where a leaf is drawn on a mesh: the program's tensor-parallel
    placement (``tp_param_pspec``), except that a column-sharded matrix is
    drawn sharded by rows.  The engine permutes those matrices' columns
    before it places them, and a permutation of a column-sharded array
    gathers the whole of it on every chip; of a row-sharded one it stays
    on each chip's rows."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import tp_param_pspec

    spec = tp_param_pspec(name, ndim, axis)
    if ndim >= 2 and len(spec) == ndim and spec[-1] == axis:
        return P(*([None] * (ndim - 2) + [axis, None]))
    return spec


def init_weights(c: dict, seed: int, mesh=None, axis: str = "model"):
    """Every weight of the program's parameter tree for configuration
    ``c``, drawn on the device from ``seed`` in one jitted call, in the type
    it is served in.

    Matrices are normal with variance 1/fan_in (output projections half
    that), and so is the embedding (1/hidden): with the program's
    sqrt(hidden) embedding scale the residual stream starts at unit RMS and
    the layers, not the input token's own embedding, decide the logits.
    The norm scales are small random offsets (the program applies
    ``1 + scale``), so the norms' weights are exercised too.  Stacked layer leaves are drawn one layer at a time,
    which bounds the random bits held at once to one layer's.

    With ``mesh``, every leaf is drawn straight into its place on the mesh
    (``draw_spec``); the values are those of the one-device draw, bit for
    bit.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    mod = arch(c)
    cfg = mod.model_config(c)
    hook = getattr(mod, "leaf_std", None)
    shapes = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    treedef = jax.tree_util.tree_structure(shapes)
    stacked = lm.layer_runs(cfg)[0].count > 1
    lo, hi = seed_words(seed)

    def names_of(path):
        return [getattr(k, "key", str(k)) for k in path]

    def leaf(key, path, sd):
        names = names_of(path)
        shape = sd.shape[1:] if stacked and names[0].startswith("run") \
            else sd.shape

        def draw(k):
            std = hook(names, shape) if hook else None
            if std is None and names[-1] == "scale":
                return 0.1 * jax.random.normal(k, shape, jnp.float32)
            if std is None and names[-1] == "embedding":
                std = 1.0 / shape[1]
            elif std is None:
                std = 1.0 / math.sqrt(shape[0])
                if names[-1] in ("w_o", "w_out"):
                    std /= math.sqrt(2.0)
            return jax.random.normal(k, shape, jnp.float32) * std

        if shape != sd.shape:
            return jax.lax.map(lambda k: draw(k).astype(sd.dtype),
                               jax.random.split(key, sd.shape[0]))
        return draw(key).astype(sd.dtype)

    def make():
        key = jax.random.fold_in(jax.random.key(lo), hi)
        keys = jax.random.split(key, len(paths))
        leaves = [leaf(k, p, sd) for k, p, sd in
                  zip(keys, paths, jax.tree_util.tree_leaves(shapes))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    if mesh is None:
        return jax.jit(make)()
    from jax.sharding import NamedSharding
    out = jax.tree_util.tree_unflatten(treedef, [
        NamedSharding(mesh, draw_spec(names_of(p)[-1], len(sd.shape), axis))
        for p, sd in zip(paths, jax.tree_util.tree_leaves(shapes))])
    return jax.jit(make, out_shardings=out)()
