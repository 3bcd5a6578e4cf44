"""LM assembly: block dispatch, scan-grouped layer stacks, train / prefill /
decode entry points, KV/recurrent caches, modality frontends.

Layer stacking: consecutive layers with identical (kind, is_moe) are grouped
into a *run* whose parameters are stacked on a leading 'layer' axis and
evaluated with ``lax.scan`` — one compiled block body per run regardless of
depth (compile-time and HLO-size control for the 60-layer DeepSeek dry-run).
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, LOCAL_ATTN, MLA, MLSTM, RGLRU, SLSTM,
                                ModelConfig)
from repro.distributed.sharding import shard
from repro.runtime_flags import maybe_scan
from repro.models import layers, mla as mla_mod, moe as moe_mod
from repro.models import rglru as rglru_mod, xlstm as xlstm_mod
from repro.models.base import (ParamSpec, SpecTree, abstract_params,
                               count_spec_params, init_params, logical_axes,
                               stack_specs)


# ---------------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------------
class Run(NamedTuple):
    kind: str
    is_moe: bool
    start: int
    count: int

    @property
    def name(self) -> str:
        return f"run{self.start:02d}_{self.kind}{'_moe' if self.is_moe else ''}"


def layer_runs(cfg: ModelConfig) -> list[Run]:
    runs: list[Run] = []
    for i, kind in enumerate(cfg.pattern):
        m = cfg.moe_layer(i)
        if runs and runs[-1].kind == kind and runs[-1].is_moe == m:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(Run(kind, m, i, 1))
    return runs


# ---------------------------------------------------------------------------
# Per-block specs
# ---------------------------------------------------------------------------
def _ffn_spec(cfg: ModelConfig, is_moe: bool, layer0_dense: bool) -> dict:
    if is_moe:
        return {"moe": moe_mod.spec(cfg)}
    if cfg.d_ff == 0:
        return {}
    if layer0_dense and cfg.dense_d_ff_first:
        import dataclasses
        c = dataclasses.replace(cfg, d_ff=cfg.dense_d_ff_first)
        return {"mlp": layers.mlp_spec(c), "_dense_ff": None}
    return {"mlp": layers.mlp_spec(cfg)}


def block_spec(cfg: ModelConfig, run: Run) -> SpecTree:
    kind = run.kind
    sp: dict = {"norm1": layers.norm_spec(cfg)}
    if kind == ATTN or kind == LOCAL_ATTN:
        sp["attn"] = layers.attn_spec(cfg)
    elif kind == MLA:
        sp["attn"] = mla_mod.spec(cfg)
    elif kind == RGLRU:
        sp["rec"] = rglru_mod.spec(cfg)
    elif kind == MLSTM:
        sp["rec"] = xlstm_mod.mlstm_spec(cfg)
    elif kind == SLSTM:
        sp["rec"] = xlstm_mod.slstm_spec(cfg)
    else:
        raise ValueError(kind)
    layer0_dense = run.start == 0 and bool(cfg.dense_d_ff_first)
    ffn = _ffn_spec(cfg, run.is_moe, layer0_dense)
    ffn.pop("_dense_ff", None)
    if ffn:
        sp["norm2"] = layers.norm_spec(cfg)
        sp.update(ffn)
    return sp


# ---------------------------------------------------------------------------
# Model-level specs
# ---------------------------------------------------------------------------
def param_specs(cfg: ModelConfig) -> SpecTree:
    sp: dict = {}
    if cfg.frontend == "audio_stub":
        sp["embed"] = {"embedding": ParamSpec(
            (cfg.num_codebooks, cfg.vocab_size, cfg.d_model),
            (None, "vocab", "embed"), "embed")}
        sp["head"] = {"w": ParamSpec(
            (cfg.d_model, cfg.num_codebooks * cfg.vocab_size),
            ("embed", "vocab"))}       # K fused logit heads (horizontal fusion)
    else:
        sp["embed"] = layers.embed_spec(cfg)
        if not cfg.tie_embeddings:
            sp["head"] = {"w": ParamSpec((cfg.d_model, cfg.vocab_size),
                                         ("embed", "vocab"))}
    for run in layer_runs(cfg):
        one = block_spec(cfg, run)
        sp[run.name] = stack_specs(one, run.count) if run.count > 1 else one
    sp["final_norm"] = layers.norm_spec(cfg)
    return sp


def init(cfg: ModelConfig, key: jax.Array):
    return init_params(param_specs(cfg), key, jnp.dtype(cfg.dtype))


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    total = count_spec_params(param_specs(cfg))
    if active_only and cfg.is_moe:
        m = cfg.moe
        per_moe_layer = count_spec_params(
            {"w_in": moe_mod.spec(cfg)["w_in"], "w_out": moe_mod.spec(cfg)["w_out"]})
        n_moe = sum(1 for i in range(cfg.num_layers) if cfg.moe_layer(i))
        inactive = n_moe * per_moe_layer * (m.num_experts - m.top_k) // m.num_experts
        total -= inactive
    return total


# ---------------------------------------------------------------------------
# Block bodies — full sequence (train / prefill)
# ---------------------------------------------------------------------------
def _apply_ffn(cfg, p, x, is_moe):
    if is_moe:
        y, aux = moe_mod.apply(cfg, p["moe"], x)
        return y, aux
    if "mlp" not in p:
        return None, 0.0
    import dataclasses
    d_ff = p["mlp"]["w_out"].shape[-2]
    c = dataclasses.replace(cfg, d_ff=int(d_ff)) if d_ff != cfg.d_ff else cfg
    return layers.mlp(c, p["mlp"], x), 0.0


def block_apply_seq(cfg, run: Run, p, x, *, want_cache: bool, max_len: int = 0):
    """Full-sequence block.  Returns (x_out, aux_loss, cache_leaf|None)."""
    kind = run.kind
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    h = layers.apply_norm(cfg, p["norm1"], x)
    cache = None

    if kind in (ATTN, LOCAL_ATTN):
        q, k, v = layers.qkv_project(cfg, p["attn"], h)
        q = layers.rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = layers.rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
        q = shard(q, ("batch", "seq", "act_heads", None))
        k = shard(k, ("batch", "seq", "act_heads", None))
        if kind == ATTN:
            o = layers.blockwise_attention(q, k, v, causal=True)
            if want_cache:
                Smax = max_len or S
                kc = jnp.zeros((B, Smax) + k.shape[2:], k.dtype)
                vc = jnp.zeros_like(kc)
                cache = {"k": jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0)),
                         "v": jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))}
        else:
            W = cfg.local_window
            o = layers.local_attention(q, k, v, W)
            if want_cache:
                # ring-buffer handoff: slot(p) = p % Wb.  Valid when S < Wb
                # (identity) or S % Wb == 0 (aligned wrap) — both hold for
                # the assigned shapes (32768 % 2048 == 0).
                Wb = min(W, max_len or S)
                if S >= Wb:
                    cache = {"k": k[:, -Wb:], "v": v[:, -Wb:]}
                else:
                    kc = jnp.zeros((B, Wb) + k.shape[2:], k.dtype)
                    cache = {"k": jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0)),
                             "v": jax.lax.dynamic_update_slice(
                                 jnp.zeros_like(kc), v, (0, 0, 0, 0))}
        attn_out = o.reshape(B, S, -1) @ p["attn"]["w_o"]
    elif kind == MLA:
        attn_out, (latent, k_rope) = mla_mod.attend_full(cfg, p["attn"], h, positions)
        if want_cache:
            Smax = max_len or S
            lc = jnp.zeros((B, Smax, latent.shape[-1]), latent.dtype)
            rc = jnp.zeros((B, Smax, k_rope.shape[-1]), k_rope.dtype)
            cache = {"latent": jax.lax.dynamic_update_slice(lc, latent, (0, 0, 0)),
                     "rope": jax.lax.dynamic_update_slice(rc, k_rope, (0, 0, 0))}
    elif kind == RGLRU:
        attn_out, (h_last, conv_tail) = rglru_mod.apply_train(cfg, p["rec"], h)
        if want_cache:
            cache = {"h": h_last, "conv": conv_tail}
    elif kind == MLSTM:
        attn_out, (state, conv_tail) = xlstm_mod.mlstm_apply_train(cfg, p["rec"], h)
        if want_cache:
            cache = {"C": state[0], "n": state[1], "m": state[2], "conv": conv_tail}
    elif kind == SLSTM:
        attn_out, (state, conv_tail) = xlstm_mod.slstm_apply_train(cfg, p["rec"], h)
        if want_cache:
            cache = {"c": state[0], "n": state[1], "m": state[2],
                     "h": state[3], "conv": conv_tail}
    else:
        raise ValueError(kind)

    x = x + attn_out
    x = shard(x, ("batch", "seq", "embed"))
    ff, aux = _apply_ffn(cfg, p, layers.apply_norm(cfg, p["norm2"], x)
                         if "norm2" in p else x, run.is_moe)
    if ff is not None:
        x = x + ff
        x = shard(x, ("batch", "seq", "embed"))
    return x, jnp.asarray(aux, jnp.float32), cache


# ---------------------------------------------------------------------------
# Block bodies — single-token decode
# ---------------------------------------------------------------------------
def block_apply_decode(cfg, run: Run, p, x, cache, pos):
    """x: (B,1,d); pos: () int32 — index of the token being generated.
    Returns (x_out, new_cache_leaf)."""
    kind = run.kind
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    h = layers.apply_norm(cfg, p["norm1"], x)

    if kind in (ATTN, LOCAL_ATTN):
        q, k, v = layers.qkv_project(cfg, p["attn"], h)
        q = layers.rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = layers.rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
        # match the cache's canonical layout BEFORE the write: the fused-QKV
        # projection leaves k/v sharded on the (qkv@model) feature dim, which
        # would propagate into the cache and force a full-cache re-gather
        # every layer every step (measured 16 MB x 8 layers/step on
        # recurrentgemma decode_32k — §Perf iteration 7).
        cache_ax = ("batch", None, None, None)
        k = shard(k, cache_ax)
        v = shard(v, cache_ax)
        if kind == ATTN:
            kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
            o = layers.decode_attention(q, kc, vc, pos + 1)
            new_cache = {"k": kc, "v": vc}
        else:
            W = cache["k"].shape[1]
            slot = pos % W
            kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
            o = layers.decode_attention(q, kc, vc, jnp.minimum(pos + 1, W))
            new_cache = {"k": kc, "v": vc}
        attn_out = o.reshape(B, 1, -1) @ p["attn"]["w_o"]
    elif kind == MLA:
        attn_out, lc, rc = mla_mod.attend_absorbed(
            cfg, p["attn"], h, cache["latent"], cache["rope"], pos, positions)
        new_cache = {"latent": lc, "rope": rc}
    elif kind == RGLRU:
        attn_out, h_new, conv_buf = rglru_mod.apply_decode(
            cfg, p["rec"], h, cache["h"], cache["conv"])
        new_cache = {"h": h_new, "conv": conv_buf}
    elif kind == MLSTM:
        state = (cache["C"], cache["n"], cache["m"])
        attn_out, state, conv_buf = xlstm_mod.mlstm_apply_decode(
            cfg, p["rec"], h, state, cache["conv"])
        new_cache = {"C": state[0], "n": state[1], "m": state[2], "conv": conv_buf}
    elif kind == SLSTM:
        state = (cache["c"], cache["n"], cache["m"], cache["h"])
        attn_out, state, conv_buf = xlstm_mod.slstm_apply_decode(
            cfg, p["rec"], h, state, cache["conv"])
        new_cache = {"c": state[0], "n": state[1], "m": state[2],
                     "h": state[3], "conv": conv_buf}
    else:
        raise ValueError(kind)

    x = x + attn_out
    ff, _aux = _apply_ffn(cfg, p, layers.apply_norm(cfg, p["norm2"], x)
                          if "norm2" in p else x, run.is_moe)
    if ff is not None:
        x = x + ff
    return x, new_cache


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------
def _cache_leaf_shapes(cfg, run: Run, B: int, max_len: int) -> dict:
    kind = run.kind
    Hkv, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    f32 = jnp.float32
    K = cfg.conv1d_width
    if kind == ATTN:
        return {"k": ((B, max_len, Hkv, Dh), dt), "v": ((B, max_len, Hkv, Dh), dt)}
    if kind == LOCAL_ATTN:
        W = min(cfg.local_window, max_len)
        return {"k": ((B, W, Hkv, Dh), dt), "v": ((B, W, Hkv, Dh), dt)}
    if kind == MLA:
        m = cfg.mla
        return {"latent": ((B, max_len, m.kv_lora_rank), dt),
                "rope": ((B, max_len, m.qk_rope_head_dim), dt)}
    if kind == RGLRU:
        W = cfg.lru_width or cfg.d_model
        return {"h": ((B, W), f32), "conv": ((B, K - 1, W), dt)}
    if kind == MLSTM:
        f, qk, H, dk, dv = xlstm_mod.mlstm_dims(cfg)
        return {"C": ((B, H, dk, dv), f32), "n": ((B, H, dk), f32),
                "m": ((B, H), f32), "conv": ((B, K - 1, f), dt)}
    if kind == SLSTM:
        d = cfg.d_model
        return {"c": ((B, d), f32), "n": ((B, d), f32), "m": ((B, d), f32),
                "h": ((B, d), f32), "conv": ((B, K - 1, d), dt)}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    """Zero cache (m-states get NEG fill)."""
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    for run in layer_runs(cfg):
        leaves = _cache_leaf_shapes(cfg, run, B, max_len)
        run_cache = {}
        for name, (shape, dt) in leaves.items():
            full = (run.count,) + shape if run.count > 1 else shape
            fill = xlstm_mod.NEG if name == "m" else 0
            run_cache[name] = jnp.full(full, fill, dt)
        cache[run.name] = run_cache
    return cache


def cache_logical_axes(cfg: ModelConfig, B: int, max_len: int):
    """Logical axes for the cache pytree (mirrors init_cache)."""
    ax: dict = {"pos": ()}
    for run in layer_runs(cfg):
        leaves = _cache_leaf_shapes(cfg, run, B, max_len)
        run_ax = {}
        for name, (shape, _dt) in leaves.items():
            if name in ("k", "v"):
                # sequence-sharded KV cache (distributed flash-decode);
                # local-attn ring buffers stay unsharded in seq (tiny)
                seq_ax = "kv_seq" if run.kind == ATTN else None
                a = ("batch", seq_ax, None, None)
            elif name in ("latent", "rope"):
                a = ("batch", "kv_seq", None)
            elif name == "C":
                a = ("batch", None, "act_heads", None)
            elif name == "conv":
                a = ("batch", None, "act_ffn")
            else:
                a = ("batch",) + (None,) * (len(shape) - 1)
            run_ax[name] = (("layer",) + a) if run.count > 1 else a
        ax[run.name] = run_ax
    return ax


# ---------------------------------------------------------------------------
# Embedding / head / frontends
# ---------------------------------------------------------------------------
def _embed_inputs(cfg, params, batch):
    """-> (x (B,S,d), loss_mask (B,S) or None)."""
    tokens = batch["tokens"]
    if cfg.frontend == "audio_stub":
        # tokens: (B, K, S) — sum the K codebook embeddings + sinusoidal pos
        emb = params["embed"]["embedding"]        # (K, V, d)
        x = jnp.zeros(tokens.shape[0:1] + tokens.shape[2:] + (cfg.d_model,),
                      emb.dtype)
        for kk in range(cfg.num_codebooks):
            x = x + jnp.take(emb[kk], tokens[:, kk], axis=0)
        S = x.shape[1]
        x = x + layers.sinusoidal_embed(jnp.arange(S), cfg.d_model)[None].astype(x.dtype)
        return x, None
    x = layers.embed(params["embed"], tokens, cfg.d_model)
    mask = None
    if cfg.frontend == "vision_stub":
        n = cfg.num_image_tokens
        pix = batch["pixel_embeds"].astype(x.dtype)   # (B, n, d) precomputed
        x = jnp.concatenate([pix, x[:, n:]], axis=1)
        mask = (jnp.arange(x.shape[1]) >= n)[None, :]
    return x, mask


def _head(cfg, params, x):
    if cfg.frontend == "audio_stub":
        B, S, _ = x.shape
        logits = (x @ params["head"]["w"]).astype(jnp.float32)
        return logits.reshape(B, S, cfg.num_codebooks, cfg.vocab_size)
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], x, cfg.logit_softcap)
    logits = jnp.einsum("bsd,dv->bsv", x, params["head"]["w"],
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, batch, *, remat: bool = False):
    """Full-sequence forward -> (logits, aux_loss)."""
    x, mask = _embed_inputs(cfg, params, batch)
    x = shard(x, ("batch", "seq", "embed"))
    aux = jnp.zeros((), jnp.float32)
    for run in layer_runs(cfg):
        p_run = params[run.name]

        def body(carry, p_slice, _run=run):
            xx, au = carry
            y, a, _ = block_apply_seq(cfg, _run, p_slice, xx, want_cache=False)
            return (y, au + a), None

        if remat:
            # full rematerialization: save only the per-layer block inputs
            # (the scan carry).  dots_*_saveable policies would pin every
            # projection output (~2GB/layer/chip at train_4k) — measured
            # 84GB/chip temps vs ~17GB with full remat (EXPERIMENTS §Dry-run).
            # MoE archs additionally save the dispatched capacity buffer
            # ('moe_dispatch', ~20MB/chip/layer) so the backward pass does
            # not repeat the expert all-to-all (§Perf iteration 4).
            if cfg.is_moe:
                body = jax.checkpoint(
                    body,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "moe_dispatch"))
            else:
                body = jax.checkpoint(body)
        if run.count > 1:
            (x, aux), _ = maybe_scan(body, (x, aux), p_run)
        else:
            (x, aux), _ = body((x, aux), p_run)
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = _head(cfg, params, x)
    return logits, aux, mask


def loss_fn(cfg: ModelConfig, params, batch, *, remat: bool = True):
    logits, aux, mask = forward(cfg, params, batch, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "audio_stub":
        # labels: (B, K, S) -> logits (B,S,K,V)
        lab = labels.transpose(0, 2, 1)
        loss = layers.cross_entropy(logits, lab)
    else:
        loss = layers.cross_entropy(logits, labels, mask=mask)
    total = loss + 0.01 * aux
    return total, {"ce": loss, "aux": aux}


def prefill(cfg: ModelConfig, params, batch, max_len: int,
            compute_dtype=None):
    """-> (cache, last_token_logits).

    ``compute_dtype`` (e.g. float32) runs the whole pass at that precision:
    activations start there and each layer's weights are cast inside the
    layer scan, so a full-precision reference needs no second copy of the
    weights in device memory.  Pass a ``cfg`` whose ``dtype`` matches."""
    def cast(tree):
        if compute_dtype is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(compute_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    x, _mask = _embed_inputs(cfg, params, batch)
    x = shard(cast(x), ("batch", "seq", "embed"))
    S = x.shape[1]
    cache: dict = {"pos": jnp.asarray(S, jnp.int32)}
    for run in layer_runs(cfg):
        p_run = params[run.name]

        def body(carry, p_slice, _run=run):
            xx = carry
            y, _a, c = block_apply_seq(cfg, _run, cast(p_slice), xx,
                                       want_cache=True, max_len=max_len)
            return y, c

        if run.count > 1:
            x, run_cache = maybe_scan(body, x, p_run)
        else:
            x, run_cache = body(x, p_run)
        cache[run.name] = run_cache
    head_params = cast({k: params[k] for k in ("embed", "head", "final_norm")
                        if k in params})
    x = layers.apply_norm(cfg, head_params["final_norm"], x[:, -1:])
    return cache, _head(cfg, head_params, x)[:, 0]


def greedy_sample(cfg: ModelConfig, logits):
    """Greedy token selection designed to stay cheap under a vocab-sharded
    layout (§Perf iteration 7): argmax commutes with the vocab sharding, so
    the partitioner reduces (max, idx) pairs — O(B) on the wire — instead of
    gathering the (B, V) fp32 logits (131 MB/step for a 256k vocab)."""
    if cfg.frontend == "audio_stub":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, K)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B,)


def serve_step_greedy(cfg: ModelConfig, params, cache, tokens_t):
    """decode_step + on-device greedy sampling: returns ((B,) int32, cache).
    The full-logits variant is decode_step (needed for temperature sampling
    off-device); this is the production greedy path."""
    logits, new_cache = decode_step(cfg, params, cache, tokens_t)
    return greedy_sample(cfg, logits), new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens_t):
    """One decode step.  tokens_t: (B,) int32 (or (B,K) audio).
    Returns (logits, new_cache)."""
    if cfg.frontend == "audio_stub":
        emb = params["embed"]["embedding"]
        x = jnp.zeros((tokens_t.shape[0], 1, cfg.d_model), emb.dtype)
        for kk in range(cfg.num_codebooks):
            x = x + jnp.take(emb[kk], tokens_t[:, kk: kk + 1], axis=0)
        x = x + layers.sinusoidal_embed(
            cache["pos"][None].astype(jnp.float32), cfg.d_model)[None].astype(x.dtype)
    else:
        x = layers.embed_onehot(params["embed"], tokens_t[:, None], cfg.d_model)
    x = shard(x, ("batch", None, "embed"))
    pos = cache["pos"]
    new_cache: dict = {"pos": pos + 1}
    for run in layer_runs(cfg):
        p_run = params[run.name]
        if run.count > 1:
            def body(carry, xs, _run=run):
                xx = carry
                p_slice, c_slice = xs
                y, c_new = block_apply_decode(cfg, _run, p_slice, xx, c_slice, pos)
                return y, c_new
            x, run_cache = maybe_scan(body, x, (p_run, cache[run.name]))
        else:
            x, run_cache = block_apply_decode(cfg, run, p_run, x,
                                              cache[run.name], pos)
        new_cache[run.name] = run_cache
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = _head(cfg, params, x)
    return logits[:, 0], new_cache
