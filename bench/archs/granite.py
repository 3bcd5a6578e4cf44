"""Granite 3.x dense decoder (``model_type: granite``): grouped-query
attention with rotary embeddings, a SwiGLU FFN, RMSNorm, tied embeddings.

The four parts an architecture module gives the harness (``bench/model.py``
finds it by the configuration's ``model_type``): ``Shape``,
``model_config``, ``step_work`` and the float32 reference ``forward``.
"""
from __future__ import annotations

import math

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

    def cache_bytes(self, batch: int, rows: int) -> int:
        """Bytes of the K/V cache of ``batch`` slots of ``rows`` rows."""
        return batch * rows * self.kv_row_bytes * self.L


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


def step_work(c: dict, active_pos, chunks) -> tuple[float, float]:
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
    s = Shape(c)
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


def forward(c: dict, params, tokens, int8: bool):
    """(T, V) float32 logits of ``tokens`` (T,): the published architecture
    in float32 ``jax.numpy`` (matmuls at ``Precision.HIGHEST``), written
    from the configuration file and importing nothing of the program.

    Token embedding times ``embedding_multiplier``; per layer RMSNorm, a
    fused QKV projection, rotary embedding (rotate-half form, base
    ``rope_theta``), causal grouped-query attention scaled by
    ``attention_multiplier``, the output projection added back times
    ``residual_multiplier``, RMSNorm, a SwiGLU FFN added back the same way;
    a final RMSNorm and the tied embedding as the head, divided by
    ``logits_scaling``.  It reads the weights in the program's parameter
    layout: QKV columns ``[q | k | v]``, the FFN in-projection
    ``[gate | up]``, each RMSNorm weight stored as its offset from 1.  The
    layers run in a scan, so each layer's weights are cast to float32 in
    turn.  ``int8``: every matmul with int8 weights and activations
    (per-column and per-row scales), the control.
    """
    import jax
    import jax.numpy as jnp

    s = Shape(c)
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    eps = float(c["rms_norm_eps"])

    def mm(x, w):
        w = w.astype(f32)
        if not int8:
            return jnp.matmul(x, w, precision=hi)
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        sx = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        wq = jnp.round(w / jnp.where(sw > 0, sw, 1.0))
        xq = jnp.round(x / jnp.where(sx > 0, sx, 1.0))
        return jnp.matmul(xq, wq) * sx * sw       # integers: exact products

    def rms(x, offset):
        w = 1.0 + offset.astype(f32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    T = tokens.shape[0]
    pos = jnp.arange(T, dtype=f32)
    half = s.D // 2
    inv = float(c["rope_theta"]) ** (-jnp.arange(half, dtype=f32) / half)
    ang = pos[:, None] * inv[None, :]                   # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):                                        # (T, heads, D)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    rep = s.H // s.Hkv
    causal = jnp.tril(jnp.ones((T, T), bool))
    a_mult = float(c["attention_multiplier"])
    r_mult = float(c["residual_multiplier"])

    def layer(x, p):
        h = rms(x, p["norm1"]["scale"])
        qkv = mm(h, p["attn"]["w_qkv"])
        q = rope(qkv[:, :s.H * s.D].reshape(T, s.H, s.D))
        k = rope(qkv[:, s.H * s.D:(s.H + s.Hkv) * s.D].reshape(T, s.Hkv, s.D))
        v = qkv[:, (s.H + s.Hkv) * s.D:].reshape(T, s.Hkv, s.D)
        qg = q.reshape(T, s.Hkv, rep, s.D)
        sc = jnp.einsum("thrd,shd->hrts", qg, k, precision=hi) * a_mult
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hrts,shd->thrd", pr, v, precision=hi)
        x = x + r_mult * mm(o.reshape(T, s.H * s.D), p["attn"]["w_o"])
        h2 = rms(x, p["norm2"]["scale"])
        a = mm(h2, p["mlp"]["w_in"])
        gate, up = a[:, :s.f], a[:, s.f:]
        x = x + r_mult * mm(jax.nn.silu(gate) * up, p["mlp"]["w_out"])
        return x, None

    emb = params["embed"]["embedding"].astype(f32)
    x = emb[tokens] * float(c["embedding_multiplier"])
    run = next(k for k in params if k.startswith("run"))
    stack = params[run]
    if stack["attn"]["w_qkv"].ndim == 2:                 # a single layer
        stack = jax.tree_util.tree_map(lambda a: a[None], stack)
    x, _ = jax.lax.scan(layer, x, stack)
    xf = rms(x, params["final_norm"]["scale"])
    return mm(xf, emb.T) / float(c["logits_scaling"])
