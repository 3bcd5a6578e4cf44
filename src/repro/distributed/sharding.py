"""Logical-axis sharding: rules tables, PartitionSpec resolution, and the
ambient-mesh `shard()` constraint helper used inside model code.

Model code annotates tensors with *logical* axes ("batch", "seq", "embed",
"heads", ...).  A per-family rules table maps logical axes to mesh axes.
Resolution is shape-aware: a logical axis whose dim is not divisible by the
mapped mesh-axis extent degrades to replication for that dim (never a
compile error — e.g. batch=1 long-context decode).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, tuple]

# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------
# Default single/multi-pod rules.  "data" resolves to ("pod","data") on a
# multi-pod mesh (pure DP across pods), "model" to the intra-pod model axis.
BASE_RULES: dict[str, str] = {
    # activations
    "batch": "data",
    "seq": None,
    "sp_seq": "model",       # sequence-parallel sections (norms, elementwise)
    "kv_seq": "model",       # sequence-sharded KV cache (distributed flash-decode)
    "embed": None,
    "act_ffn": "model",
    "act_heads": "model",
    "act_vocab": "model",
    # params
    "ffn": "model",
    "heads": "model",
    "kv_heads": "model",
    "qkv": "model",
    "vocab": "model",
    "expert": "model",
    "expert_ffn": "model",
    "capacity": None,
    "lru": "model",
    "layer": None,
    "kv_lora": None,
    "q_lora": None,
}

# Family overrides.  moe-huge (DeepSeek-V2-236B): expert count shards over the
# data axis (the expert corpus is the bulk of the 236B params — FSDP-style),
# expert hidden dim over model.
FAMILY_OVERRIDES: dict[str, dict[str, MeshAxes]] = {
    # DeepSeek-V2 class: the 222B expert corpus FSDP-shards its expert dim
    # over 'data'; capacity buffers shard over 'model' so per-device MoE
    # activations stay O(tokens/devices).
    "moe-huge": {"expert": "data", "expert_ffn": "model", "capacity": "model"},
    # dense archs at train shapes: pure FSDP (see rules_for docstring)
    "fsdp-train": {
        "batch": ("data", "model"),
        "embed": ("data", "model"),        # params shard on their embed dim
        "ffn": None, "heads": None, "kv_heads": None, "qkv": None,
        "vocab": None, "lru": None, "act_ffn": None, "act_heads": None,
        "act_vocab": None, "sp_seq": None, "kv_seq": None,
    },
}


def rules_for(cfg, mesh: Mesh, kind: str = "") -> dict[str, MeshAxes]:
    """Logical→mesh rules, specialized per family and workload kind.

    §Perf iteration 1 (EXPERIMENTS.md): at train shapes the global batch
    covers the whole mesh, and naive TP-16 is collective-bound (the backward
    of every TP matmul psums a (B,S,d) activation gradient: measured 289
    GB/chip/step on granite train_4k — tcoll 6.6s vs tc 0.74s).  For non-MoE
    archs whose params fit per-chip under full sharding, train shapes
    therefore switch to FSDP: batch over (data×model), params sharded over
    the combined mesh on their embed dim, no tensor parallelism — collective
    traffic becomes ~3×params of weight gathers (granite: 15GB, 0.3s).
    Prefill/decode keep TP (batch < mesh size there).
    """
    rules = dict(BASE_RULES)
    fam = cfg.family
    # moe-huge: per-layer expert corpus too large for model-axis sharding
    # alone (>= 1B params/layer => >= 125MB/chip at TP16 just for one layer)
    if cfg.is_moe and cfg.moe.num_experts * cfg.moe.d_ff_expert * cfg.d_model * 3 > 1e9:
        fam = "moe-huge"
    if kind == "train" and not cfg.is_moe:
        fam = "fsdp-train"
    rules.update(FAMILY_OVERRIDES.get(fam, {}))
    # map "data" -> ("pod","data") when a pod axis exists (pure DP over pods)
    if "pod" in mesh.axis_names:
        def remap(v):
            if v == "data":
                return ("pod", "data")
            if isinstance(v, tuple) and "data" in v:
                out = []
                for a in v:
                    out.extend(("pod", "data") if a == "data" else (a,))
                return tuple(out)
            return v
        rules = {k: remap(v) for k, v in rules.items()}
    return rules


# ---------------------------------------------------------------------------
# Ambient mesh context
# ---------------------------------------------------------------------------
class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[dict] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[dict] = None):
    old = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = old


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------
def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def resolve_pspec(logical: Sequence[Optional[str]], shape: Sequence[int],
                  mesh: Mesh, rules: dict[str, MeshAxes]) -> P:
    """Shape-aware logical→mesh resolution; drops non-divisible dims to None.
    Never assigns one mesh axis to two dims."""
    used: set[str] = set()
    out = []
    for name, dim in zip(logical, shape):
        target = rules.get(name) if name else None
        if target is None:
            out.append(None)
            continue
        tgt_tuple = (target,) if isinstance(target, str) else tuple(target)
        if any(a in used for a in tgt_tuple):
            out.append(None)
            continue
        if dim % _axis_size(mesh, tgt_tuple) != 0:
            # try a prefix of the tuple (e.g. only "pod" of ("pod","data"))
            ok = None
            for cut in range(len(tgt_tuple) - 1, 0, -1):
                sub = tgt_tuple[:cut]
                if dim % _axis_size(mesh, sub) == 0 and not any(a in used for a in sub):
                    ok = sub
                    break
            if ok is None:
                out.append(None)
                continue
            tgt_tuple = ok
        used.update(tgt_tuple)
        out.append(tgt_tuple[0] if len(tgt_tuple) == 1 else tgt_tuple)
    return P(*out)


def data_shards() -> int:
    """Extent of the (pod×)data axes of the ambient mesh (1 when unset).
    Used by the MoE grouped dispatch to keep token gathers shard-local."""
    mesh, rules = _CTX.mesh, _CTX.rules or BASE_RULES
    if mesh is None:
        return 1
    return _axis_size(mesh, rules.get("batch", "data"))


def _manual_axes() -> frozenset:
    """Mesh axes currently under manual shard_map control (e.g. 'pod' inside
    the int8-compressed gradient region) — constraints must not mention them."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return frozenset()
    return frozenset(n for n, t in zip(am.axis_names, am.axis_types)
                     if t == jax.sharding.AxisType.Manual)


def shard(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint against the ambient mesh (no-op when unset)."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None:
        return x
    rules = rules or BASE_RULES
    spec = resolve_pspec(logical, x.shape, mesh, rules)
    manual = _manual_axes()
    if manual:
        def drop(e):
            if e is None:
                return None
            t = (e,) if isinstance(e, str) else tuple(e)
            t = tuple(a for a in t if a not in manual)
            return None if not t else (t[0] if len(t) == 1 else t)
        spec = P(*(drop(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Param / state sharding trees
# ---------------------------------------------------------------------------
def sharding_tree(abstract_tree, axes_tree, mesh: Mesh, rules: dict):
    """NamedSharding pytree matching an abstract-value pytree.

    Walks nested dicts manually: axes leaves are tuples (which jax.tree would
    otherwise traverse as containers)."""
    def walk(ab, ax):
        if isinstance(ab, dict):
            return {k: walk(ab[k], ax[k]) for k in ab}
        return NamedSharding(mesh, resolve_pspec(ax, ab.shape, mesh, rules))
    return walk(abstract_tree, axes_tree)


def param_shardings(specs, mesh: Mesh, rules: dict, dtype="bfloat16"):
    import jax.numpy as jnp
    from repro.models.base import abstract_params, logical_axes
    ab = abstract_params(specs, jnp.dtype(dtype))
    ax = logical_axes(specs)
    return sharding_tree(ab, ax, mesh, rules)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Tensor-parallel serve helpers (head-sharded executed decode; serve/engine)
# ---------------------------------------------------------------------------
# The serve engine shards the decode program along attention heads / FFN
# width: each shard owns H/n query heads, Hkv/n KV heads and d_ff/n FFN
# columns, activations (d_model) stay replicated, and the two row-sharded
# output projections (w_o, w_out) psum their partial products.  Two fused
# weights need a COLUMN PERMUTATION before the even last-axis split hands
# each shard a self-consistent slab:
#
#   w_qkv (d, (H+2*Hkv)*D)  columns are [q_0..q_{H-1} | k_0.. | v_0..] —
#       a plain split would give shard 0 query heads only.  Permuted to
#       shard-major [q_s | k_s | v_s] per shard, the engine's head-split
#       glue works unchanged with local head counts.
#   w_in  (d, 2*d_ff)       gated activations store [gate | up]; permuted
#       to per-shard [gate_s | up_s] so the split-in-half gate math stays
#       local.  (Non-gated w_in needs no permutation.)
#
# Row-sharded weights (w_o rows are head-major, w_out rows follow the
# activation's column order) split evenly without reordering.

_TP_COL_SHARDED = ("w_qkv", "w_in")     # shard last axis (after permutation)
_TP_ROW_SHARDED = ("w_o", "w_out")      # shard axis -2; psum after matmul


def tp_qkv_permutation(H: int, Hkv: int, D: int, shards: int) -> np.ndarray:
    """Column permutation taking [q|k|v] to shard-major [q_s|k_s|v_s]."""
    if H % shards or Hkv % shards:
        raise ValueError(f"H={H}, Hkv={Hkv} not divisible by {shards} shards")
    Hl, Hkvl = H // shards * D, Hkv // shards * D
    idx = []
    for s in range(shards):
        idx.extend(range(s * Hl, (s + 1) * Hl))
        idx.extend(range(H * D + s * Hkvl, H * D + (s + 1) * Hkvl))
        idx.extend(range((H + Hkv) * D + s * Hkvl,
                         (H + Hkv) * D + (s + 1) * Hkvl))
    return np.asarray(idx, np.int32)


def tp_gated_ffn_permutation(F: int, shards: int) -> np.ndarray:
    """Column permutation taking [gate|up] to per-shard [gate_s|up_s]."""
    if F % shards:
        raise ValueError(f"d_ff={F} not divisible by {shards} shards")
    Fl = F // shards
    idx = []
    for s in range(shards):
        idx.extend(range(s * Fl, (s + 1) * Fl))
        idx.extend(range(F + s * Fl, F + (s + 1) * Fl))
    return np.asarray(idx, np.int32)


def tp_permute_qkv(w, H: int, Hkv: int, D: int, shards: int):
    """Shard-major column order for a fused QKV weight (last axis; works
    for layer-stacked ``(L, d, N)`` leaves too)."""
    import jax.numpy as jnp
    return jnp.take(w, tp_qkv_permutation(H, Hkv, D, shards), axis=-1)


def tp_permute_gated_ffn(w, F: int, shards: int):
    """Per-shard [gate_s|up_s] column order for a gated FFN in-projection."""
    import jax.numpy as jnp
    return jnp.take(w, tp_gated_ffn_permutation(F, shards), axis=-1)


def tp_param_pspec(name: str, ndim: int, axis: str = "model") -> P:
    """PartitionSpec for one serve param leaf under head-sharded TP.
    ``name`` is the leaf's key in the param tree; anything not explicitly
    sharded (norm scales, embeddings, the head) replicates."""
    if name in _TP_COL_SHARDED:
        return P(*([None] * (ndim - 1) + [axis]))
    if name in _TP_ROW_SHARDED:
        return P(*([None] * (ndim - 2) + [axis, None]))
    return P()


def tp_cache_pspec(name: str, ndim: int, axis: str = "model", *,
                   flat_heads: bool = False) -> P:
    """PartitionSpec for a KV-cache leaf: k/v shard their head axis (-2,
    both for contiguous ``(B,S,Hkv,D)`` / stacked ``(L,B,S,Hkv,D)`` leaves
    and for the paged ``(blocks,bs,Hkv,D)`` arena; -1 for ``flat_heads``
    leaves ``(L,B,S,Hkv*D)``, whose even split is the same head split);
    positions and block tables replicate — the per-slot ``(B,)`` position
    contract and the slot manager are shard-invariant."""
    if name in ("k", "v"):
        if flat_heads:
            return P(*([None] * (ndim - 1) + [axis]))
        return P(*([None] * (ndim - 2) + [axis, None]))
    return P()
