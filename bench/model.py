"""The model side of the yardstick: a configuration file turned into the
program's ``ModelConfig``, weights drawn from the seed, and the work a step
needs (operations and bytes), counted from the model's shapes alone.

Configuration files use the key names of the model's published
``config.json`` and hold the values as they are run.  The program fixes
some of them (the embedding scale, the attention scale, the norm epsilon,
no residual or logit multiplier); ``model_config`` refuses a file whose
values the program cannot run.
"""
from __future__ import annotations

import math

import numpy as np

BF16_BYTES = 2
F32_BYTES = 4


class Shape:
    """The sizes that the work counts and the reference need."""

    def __init__(self, c: dict):
        self.d = int(c["hidden_size"])
        self.f = int(c["intermediate_size"])
        self.H = int(c["num_attention_heads"])
        self.Hkv = int(c["num_key_value_heads"])
        self.D = int(c.get("head_dim") or self.d // self.H)
        self.L = int(c["num_hidden_layers"])
        self.V = int(c["vocab_size"])
        self.gated = c["hidden_act"] in ("silu", "gelu")

    @property
    def layer_weights(self) -> int:
        """Matmul weight elements of one layer: QKV, W_o, FFN in and out."""
        f_in = 2 * self.f if self.gated else self.f
        return (self.d * (self.H + 2 * self.Hkv) * self.D
                + self.H * self.D * self.d + self.d * f_in + self.f * self.d)

    @property
    def params(self) -> int:
        """Every parameter: layers (with their two norms), the tied
        embedding, the final norm."""
        return (self.L * (self.layer_weights + 2 * self.d)
                + self.V * self.d + self.d)

    @property
    def kv_row_bytes(self) -> int:
        """Bytes of one cache row (K and V) in one layer."""
        return 2 * self.Hkv * self.D * BF16_BYTES


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.configs.base import ModelConfig

    s = Shape(c)
    fixed = {"embedding_multiplier": math.sqrt(s.d),
             "attention_multiplier": 1.0 / math.sqrt(s.D),
             "residual_multiplier": 1.0, "logits_scaling": 1.0,
             "rms_norm_eps": 1e-6}
    for key, want in fixed.items():
        if not math.isclose(float(c[key]), want, rel_tol=1e-9):
            raise ValueError(f"{c['name']}: {key}={c[key]} but the program "
                             f"runs {want}")
    if not c["tie_word_embeddings"]:
        raise ValueError(f"{c['name']}: untied embeddings are not served")
    return ModelConfig(
        name=c["name"], family="dense", num_layers=s.L, d_model=s.d,
        num_heads=s.H, num_kv_heads=s.Hkv, head_dim=s.D, d_ff=s.f,
        vocab_size=s.V, activation=c["hidden_act"], norm="rmsnorm",
        rope_theta=float(c["rope_theta"]), tie_embeddings=True,
        dtype=c["torch_dtype"], source=c["source"])


def seed_words(seed: int) -> tuple[int, int]:
    """Two 31-bit words from any whole number, for PRNG keys."""
    w = np.random.SeedSequence(seed % 2 ** 64).generate_state(2)
    return int(w[0] >> 1), int(w[1] >> 1)


def init_weights(cfg, seed: int):
    """Every weight of the program's parameter tree, drawn on the device
    from ``seed`` in one jitted call, in the type it is served in.

    Matrices are normal with variance 1/fan_in (output projections half
    that), and so is the embedding (1/hidden): with the program's
    sqrt(hidden) embedding scale the residual stream starts at unit RMS and
    the layers, not the input token's own embedding, decide the logits.
    The norm scales are small random offsets (the program applies
    ``1 + scale``), so the norms' weights are exercised too.  Stacked layer leaves are drawn one layer at a time,
    which bounds the random bits held at once to one layer's.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    shapes = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    treedef = jax.tree_util.tree_structure(shapes)
    stacked = lm.layer_runs(cfg)[0].count > 1
    lo, hi = seed_words(seed)

    def leaf(key, path, sd):
        names = [getattr(k, "key", str(k)) for k in path]
        shape = sd.shape[1:] if stacked and names[0].startswith("run") \
            else sd.shape

        def draw(k):
            if names[-1] == "scale":
                return 0.1 * jax.random.normal(k, shape, jnp.float32)
            if names[-1] == "embedding":
                std = 1.0 / shape[1]
            else:
                std = 1.0 / math.sqrt(shape[0])
                if names[-1] in ("w_o", "w_out"):
                    std /= math.sqrt(2.0)
            return jax.random.normal(k, shape, jnp.float32) * std

        if shape != sd.shape:
            return jax.lax.map(lambda k: draw(k).astype(sd.dtype),
                               jax.random.split(key, sd.shape[0]))
        return draw(key).astype(sd.dtype)

    @jax.jit
    def make():
        key = jax.random.fold_in(jax.random.key(lo), hi)
        keys = jax.random.split(key, len(paths))
        leaves = [leaf(k, p, sd) for k, p, sd in
                  zip(keys, paths, jax.tree_util.tree_leaves(shapes))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make()


def step_work(s: Shape, active_pos, chunks) -> tuple[float, float]:
    """(FLOPs, bytes) that one serve step needs, whatever implements it.

    ``active_pos``: cache position of every decoding slot (it writes row
    ``pos`` and attends over ``pos + 1`` rows).  ``chunks``: (offset,
    valid rows) of every prompt chunk riding the step.

    FLOPs: 2 x weight elements x rows for every matmul, attention over the
    valid context (QK^T and PV), and the head only on rows whose logits are
    used: every decode row and one row per chunk.  Bytes: every weight
    once (the tied embedding once, as the head), the valid K/V rows each
    slot and chunk attends over, read once, the rows written, and the
    logits the host reads.  Padding beyond a valid length counts nowhere.
    """
    n_dec = len(active_pos)
    rows = n_dec + sum(v for _, v in chunks)
    keys = sum(p + 1 for p in active_pos) + sum(
        v * o + v * (v + 1) // 2 for o, v in chunks)
    ctx_read = sum(p + 1 for p in active_pos) + sum(o + v for o, v in chunks)
    head_rows = n_dec + len(chunks)
    flops = (2.0 * s.layer_weights * rows * s.L
             + 4.0 * s.H * s.D * keys * s.L
             + 2.0 * s.V * s.d * head_rows)
    weight_bytes = (BF16_BYTES * (s.L * s.layer_weights + s.V * s.d)
                    + F32_BYTES * (2 * s.L * s.d + s.d))
    nbytes = (weight_bytes
              + s.L * s.kv_row_bytes * (ctx_read + rows)
              + F32_BYTES * s.V * head_rows)
    return flops, float(nbytes)
