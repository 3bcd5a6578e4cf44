"""Batched serving engine: continuous batching with per-slot cache positions.

Semantics (``scheduling="continuous"``, the default): the engine keeps a
per-slot cache-position vector ``(B,)`` plus per-slot active masks, so every
slot advances, finishes (EOS / token budget / cache-full) and is refilled
independently at every iteration.  A waiting request's prompt is prefilled
*alongside* the decode step that runs in the same iteration — the planner
therefore sees a mixed prefill⊕decode op graph on (nearly) every step, not
only at wave boundaries.  The slot lifecycle, the ``(B,)`` position
contract and the fallback rules are documented in docs/serving.md.

The legacy wavefront scheduler (``scheduling="wavefront"``) is retained:
requests are grouped by prompt length into lock-step waves and the batch
only refills when a whole wave finishes.  It is the differential oracle the
continuous engine is tested against (tests/test_serve_continuous.py).

Fusion execution (``plan_fusion=True``): the decode step is *planned* by
``plan_decode_fusion`` and *executed* through the plan->program executor
(core/executor) — the norm -> decode-attention -> FFN-projection chain runs
as Pallas kernels routed by a binding registry over the live slot state
(hidden activations, the KV-cache blocks, the layer weights), with the
model glue (QKV projection, per-slot RoPE, per-slot cache scatter,
residuals, gating, head) living in the binding setters.  Decode attention
reads each slot's valid prefix from a vectorized ``(B, 1)`` int32 operand,
so one compiled kernel serves every mix of slot positions.

Chunked prefill (``PrefillBudget``): on the executed continuous path a
waiting prompt is admitted in chunks of ``chunk_rows`` tokens — the slot
enters a *prefilling* phase, each iteration scatters one chunk's k/v into
the slot's cache rows and runs the blockwise flash-prefill kernel
(kernels/prefill_attention) for that chunk *inside the decode step's fused
launch*.  Up to ``max_coresident_chunks`` chunks from different slots ride
one launch: N compute-bound prefill-attention ops ⊕ the memory-bound
vectorized decode attention, the paper's heterogeneous pairing as ONE
Pallas call.  Prompts of any length (up to the cache) are chipped away
across iterations; the first token samples from the final chunk's logits.
Configs outside the supported shape (multi-run stacks, MoE, non-RMSNorm)
fall back to the hand-wired ``lm.decode_step`` with a notice
(``executable_decode_supported`` returns the reason; see docs/serving.md
§Fallback).

``examples/dual_stream_decode.py`` shows the horizontal-fusion dual-stream
variant of the decode step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, ModelConfig
from repro.models import lm

_span = jax.profiler.TraceAnnotation


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_token: Optional[int] = None
    arrival: int = 0                   # engine step at which the request is
    #                                    visible to the slot manager
    #                                    (continuous scheduling only)
    out_tokens: list = field(default_factory=list)
    done: bool = False


@dataclass(frozen=True)
class PrefillBudget:
    """One iteration's prefill allowance — the single knob that replaced
    the ``prefill_rows`` / ``prefill_chunk`` / ``pad_prefill_rows`` trio.

    ``chunk_rows``: tokens of one prompt consumed per iteration (one
    prefill-attention chunk).  ``max_coresident_chunks``: how many chunks
    from *different* slots may ride one fused launch.  ``pad_to``: lane
    tile the legacy wavefront prefill-FFN operand rows pad to.
    ``policy``: which prefilling slots chunk first when more are ready
    than ``max_coresident_chunks`` allows — ``"fifo"`` (lowest slot index,
    the legacy order), ``"srpf"`` (shortest-remaining-prefill-first:
    prompts closest to completion chunk first, cutting mean admission
    latency on mixed short/long traces; ties break by slot index), or
    ``"eload"`` (expert-load-aware: srpf ordering, but when the running
    per-expert hit skew — ``ServeStats.expert_skew`` — reaches
    ``skew_threshold`` the step sheds one coresident chunk, narrowing the
    launch while the hot experts' weight streaming dominates the fused
    bundle's memory phase; MoE executed path only — without expert stats
    the skew stays 0 and eload degrades to srpf)."""
    chunk_rows: int = 2048
    max_coresident_chunks: int = 2
    pad_to: int = 128
    policy: str = "fifo"
    skew_threshold: float = 1.5

    def __post_init__(self):
        for f_ in ("chunk_rows", "max_coresident_chunks", "pad_to"):
            if getattr(self, f_) < 1:
                raise ValueError(f"PrefillBudget.{f_} must be >= 1")
        if self.policy not in ("fifo", "srpf", "eload"):
            raise ValueError(
                f"PrefillBudget.policy {self.policy!r} "
                "(fifo, srpf or eload)")
        if self.skew_threshold < 1.0:
            raise ValueError("PrefillBudget.skew_threshold must be >= 1.0 "
                             "(1.0 means perfectly balanced experts)")

    def pad_rows(self, rows: int) -> int:
        """Rows of a prefill FFN operand: raw up to one tile, the next
        ``pad_to`` multiple beyond (zero-padded)."""
        return rows if rows <= self.pad_to else \
            -(-rows // self.pad_to) * self.pad_to

    def effective_chunk(self, cache_len: int, multiple: int = 1) -> int:
        """Chunk rows actually used against a ``cache_len`` cache: the
        largest value <= min(chunk_rows, cache_len) dividing cache_len, so
        chunk offsets are always multiples of the chunk and a full-chunk
        scatter never crosses the cache end.  ``multiple`` further
        constrains the chunk to a multiple of it (the paged path passes the
        KV block size so every chunk is a whole number of pages); when even
        ``multiple`` itself exceeds ``chunk_rows`` it is returned as the
        minimum viable chunk.

        Direct divisor enumeration over ``sqrt(cache_len)`` pairs — the
        answer is by definition a divisor, so counting down from
        ``chunk_rows`` one integer at a time (the old loop) did O(cache_len)
        work for what is an O(sqrt) question.
        """
        if cache_len % multiple:
            raise ValueError(f"cache_len {cache_len} is not a multiple of "
                             f"the required alignment {multiple}")
        n = cache_len // multiple
        cap = max(min(self.chunk_rows, cache_len) // multiple, 1)
        best, i = 1, 1
        while i * i <= n:
            if n % i == 0:
                for d in (i, n // i):
                    if best < d <= cap:
                        best = d
            i += 1
        return best * multiple


@dataclass
class ServeStats:
    """Slot-manager trajectory of one continuous-batching ``run()``."""
    batch: int
    steps: int = 0                # engine iterations (incl. idle/prefill-only)
    decode_steps: int = 0         # iterations that decoded >= 1 active slot
    mixed_steps: int = 0          # decode iterations that also carried a
    #                               prefill chunk (the steady mixed graph)
    fused_mixed_steps: int = 0    # mixed iterations whose program fused a
    #                               prefill chunk with decode-side work
    prefill_only_steps: int = 0   # admissions with no active slot to decode
    slot_steps: int = 0           # sum of active slots over decode iterations
    tokens: int = 0
    prefill_chunks: int = 0       # chunk launches (chunked admission)
    fused_prefill_chunks: int = 0  # chunks whose program fused them with a
    #                                decode-side member (attention or the
    #                                FFN chain riding the other bundle)
    admissions: list = field(default_factory=list)   # (step, rid, slot)
    retirements: list = field(default_factory=list)  # (step, rid, reason)
    admission_latencies: list = field(default_factory=list)  # steps from
    #                                  arrival to first token, per admission
    # paged-KV trajectory (serve/kv_pool.py; zero on the contiguous path)
    prompt_tokens: int = 0        # prompt tokens across admitted requests
    prefix_hits: int = 0          # admissions that matched a cached prefix
    prefix_tokens_reused: int = 0  # prompt tokens whose prefill was skipped
    blocks_in_use: int = 0        # peak arena blocks mapped or cached
    evictions: int = 0            # prefix-cache blocks evicted under pressure
    # MoE trajectory (executed path only; empty/zero for dense configs)
    expert_hits: list = field(default_factory=list)  # per-expert routed
    #                               decode-token count, layer-summed
    load_shed_steps: int = 0      # steps where eload shed a coresident chunk
    kv_in_place_steps: int = 0    # dispatched steps whose layer scan kept
    #                               the stacked K/V cache in place

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots decoding per decode iteration."""
        return self.slot_steps / max(self.batch * self.decode_steps, 1)

    @property
    def mixed_fraction(self) -> float:
        """Fraction of decode iterations that carried a prefill partner."""
        return self.mixed_steps / max(self.decode_steps, 1)

    @property
    def fused_prefill_fraction(self) -> float:
        """Fraction of prefill chunks that rode a fused launch with
        decode-side work (vs launching as planner singles)."""
        return self.fused_prefill_chunks / max(self.prefill_chunks, 1)

    @property
    def mean_admission_latency(self) -> float:
        """Mean engine steps from request arrival to its first token."""
        lat = self.admission_latencies
        return sum(lat) / len(lat) if lat else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens whose prefill the prefix cache
        skipped entirely (paged KV only)."""
        return self.prefix_tokens_reused / max(self.prompt_tokens, 1)

    def add_expert_hits(self, counts) -> None:
        """Accumulate one step's per-expert decode-token counts (an (E,)
        vector off the device, summed over layers)."""
        counts = [int(c) for c in counts]
        if not self.expert_hits:
            self.expert_hits = [0] * len(counts)
        for i, c in enumerate(counts):
            self.expert_hits[i] += c

    @property
    def expert_skew(self) -> float:
        """Hottest expert's load relative to a perfectly balanced one:
        max(hits) * E / sum(hits).  1.0 = balanced, E = every routed
        token hit one expert; 0.0 until any hits land (dense configs,
        or before the first decode step)."""
        total = sum(self.expert_hits)
        if not total:
            return 0.0
        return max(self.expert_hits) * len(self.expert_hits) / total

    def describe(self) -> dict:
        return {
            "steps": self.steps, "decode_steps": self.decode_steps,
            "mixed_steps": self.mixed_steps,
            "fused_mixed_steps": self.fused_mixed_steps,
            "prefill_only_steps": self.prefill_only_steps,
            "tokens": self.tokens,
            "prefill_chunks": self.prefill_chunks,
            "fused_prefill_chunks": self.fused_prefill_chunks,
            "occupancy": round(self.occupancy, 3),
            "mixed_fraction": round(self.mixed_fraction, 3),
            "fused_prefill_fraction": round(self.fused_prefill_fraction, 3),
            "mean_admission_latency": round(self.mean_admission_latency, 3),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": round(self.prefix_hit_rate, 3),
            "blocks_in_use": self.blocks_in_use,
            "evictions": self.evictions,
            "expert_hits": list(self.expert_hits),
            "expert_skew": round(self.expert_skew, 3),
            "load_shed_steps": self.load_shed_steps,
            "kv_in_place_steps": self.kv_in_place_steps,
        }


def executable_decode_supported(cfg: ModelConfig) -> Optional[str]:
    """None when the planned decode program can replace ``lm.decode_step``
    for this config; otherwise the reason for the hand-wired fallback."""
    runs = lm.layer_runs(cfg)
    if cfg.frontend != "none":
        return f"frontend {cfg.frontend!r} (token frontend only)"
    if len(runs) != 1 or runs[0].kind != ATTN:
        return "needs a single global-attention layer run"
    if cfg.norm != "rmsnorm":
        return f"norm {cfg.norm!r} (rmsnorm only)"
    if not cfg.is_moe and cfg.d_ff <= 0:
        return "no FFN"
    if cfg.activation not in ("silu", "gelu", "gelu_mlp", "relu2_mlp"):
        return f"activation {cfg.activation!r}"
    return None


def _ffn_in_width(cfg: ModelConfig) -> int:
    """Width of the decode step's FFN in-projection — the real ``w_in``
    (gated activations fuse gate+up into one (d, 2f) matmul)."""
    if cfg.moe is not None:
        return cfg.moe.num_experts
    if cfg.d_ff <= 0:
        return cfg.d_model
    return 2 * cfg.d_ff if cfg.activation in ("silu", "gelu") else cfg.d_ff


def pad_prefill_rows(rows: int) -> int:
    """Deprecated: use ``PrefillBudget.pad_rows`` (the padding tile is a
    budget policy now, not a module constant)."""
    warnings.warn("pad_prefill_rows is deprecated — use "
                  "PrefillBudget.pad_rows", DeprecationWarning, stacklevel=2)
    return PrefillBudget().pad_rows(rows)


def _out_proj(x, w, axis: Optional[str] = None):
    """``x @ w``.  Under tensor parallelism (``axis`` set) ``w`` is this
    shard's row slab: the shards' fp32 partial products sum across ``axis``
    and round to ``x.dtype`` once, as the single-device matmul does, so the
    sharded step reproduces the one-device numerics up to fp32 summation
    order.  The barrier keeps the TPU compiler from rounding the partials
    to ``x.dtype`` ahead of the all-reduce, as it otherwise does at some
    shapes; it still folds the one rounding into the all-reduce, which
    reads the fp32 partials and writes the ``x.dtype`` sum."""
    if axis is None:
        return x @ w
    s = jax.lax.psum(jnp.dot(x, w, preferred_element_type=jnp.float32), axis)
    return jax.lax.optimization_barrier(s).astype(x.dtype)


ATTN_OUT_PSUM, FFN_OUT_PSUM = "tp_attn_out_psum", "tp_ffn_out_psum"


def _psum_scope(name: str, axis: Optional[str]):
    """Under tensor parallelism, the named scope an output projection's
    product and ``psum`` run in (``ATTN_OUT_PSUM`` for W_o,
    ``FFN_OUT_PSUM`` for W_out), so a compiled program's all-reduces say
    which projection each closes; a single-device step is left as it
    is."""
    return jax.named_scope(name) if axis else contextlib.nullcontext()


def _mlp_from_h(cfg: ModelConfig, h, w_out, axis: Optional[str] = None):
    """layers.mlp, minus the in-projection the executor already ran."""
    act = cfg.activation
    if act in ("silu", "gelu"):
        gate, up = jnp.split(h, 2, axis=-1)
        g = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate)
        h = g * up
    elif act == "gelu_mlp":
        h = jax.nn.gelu(h)
    elif act == "relu2_mlp":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(act)
    with _psum_scope(FFN_OUT_PSUM, axis):
        return _out_proj(h, w_out, axis)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch: int = 8,
                 max_len: int = 512, rng_seed: int = 0,
                 plan_fusion: bool = False, measure=None,
                 schedule_cache=None, scheduling: str = "continuous",
                 prefill_budget: Optional[PrefillBudget] = None,
                 reject_overlong: bool = False,
                 stitch_epilogues: bool = True,
                 paged_kv: bool = False, kv_block_size: int = 16,
                 kv_slot_blocks: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 mesh=None, shard_axis: str = "model"):
        if scheduling not in ("continuous", "wavefront"):
            raise ValueError(f"scheduling {scheduling!r} "
                             "(continuous or wavefront)")
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.scheduling = scheduling
        # tensor-parallel serve: with a mesh whose ``shard_axis`` has
        # extent n > 1, the executed continuous step runs under
        # jax.shard_map — each shard owns num_heads/n query heads,
        # num_kv_heads/n KV-cache heads and d_ff/n FFN columns, plans its
        # own shard-local fusion, and psums the two row-sharded output
        # projections.  The slot manager, the per-slot (B,) position
        # contract and every sampled token stay shard-replicated.
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.tp_shards = 1
        if mesh is not None and dict(mesh.shape).get(shard_axis, 1) > 1:
            n_tp = int(dict(mesh.shape)[shard_axis])
            if scheduling != "continuous" or not plan_fusion:
                raise ValueError(
                    "tensor-parallel serve requires scheduling='continuous' "
                    "and plan_fusion=True (only the executed continuous "
                    "step runs under shard_map)")
            reason = executable_decode_supported(cfg)
            if reason is not None:
                raise ValueError("tensor-parallel serve: config not "
                                 f"executor-supported ({reason})")
            if cfg.is_moe:
                raise ValueError(
                    "tensor-parallel serve: MoE expert weights are "
                    "expert-major, not head/column-sharded — serve MoE "
                    "single-device (expert parallelism is a ROADMAP item)")
            for what, dim in (("num_heads", cfg.num_heads),
                              ("num_kv_heads", cfg.num_kv_heads),
                              ("d_ff", cfg.d_ff)):
                if dim % n_tp:
                    raise ValueError(
                        f"tensor-parallel serve: {what}={dim} is not "
                        f"divisible by mesh axis {shard_axis!r} extent "
                        f"{n_tp}")
            self.tp_shards = n_tp
        self._mesh_tag = (f"{shard_axis}:{self.tp_shards}"
                          if self.tp_shards > 1 else "")
        self.paged_kv = paged_kv
        self.kv_pool = None
        if paged_kv:
            # paged KV rides the executed chunked path: the arena gather
            # lives in the paged kernels, the table bookkeeping in
            # serve/kv_pool.py — neither exists on the fallback paths
            if scheduling != "continuous" or not plan_fusion:
                raise ValueError("paged_kv requires scheduling='continuous' "
                                 "and plan_fusion=True (the paged kernels "
                                 "run only on the executed chunked path)")
            reason = executable_decode_supported(cfg)
            if reason is None and lm.layer_runs(cfg)[0].count > 1:
                reason = ("the paged arena is single-layer — stacked runs "
                          "serve from the contiguous cache")
            if reason is None and cfg.is_moe:
                reason = ("MoE decode serves from the contiguous cache "
                          "(the paged+MoE combination is untested)")
            if reason is not None:
                raise ValueError(f"paged_kv: config not executor-supported "
                                 f"({reason}) — the vmapped fallback has no "
                                 "paged cache")
            if kv_block_size < 1 or 128 % kv_block_size:
                raise ValueError(f"kv_block_size {kv_block_size} must divide "
                                 "128 (cache lengths and kv chunks are "
                                 "128-aligned)")
            self.kv_block_size = kv_block_size
            if kv_slot_blocks is None:
                kv_slot_blocks = self._aligned_len() // kv_block_size
            if (kv_slot_blocks * kv_block_size) % 128:
                raise ValueError("kv_slot_blocks * kv_block_size = "
                                 f"{kv_slot_blocks * kv_block_size} must be "
                                 "a multiple of 128")
            self.kv_slot_blocks = kv_slot_blocks
            # default arena: every slot can hold its full logical capacity
            # (parity-by-construction with the contiguous cache); tighter
            # arenas degrade through LRU eviction, not rejection
            if kv_blocks is None:
                kv_blocks = batch * kv_slot_blocks + batch
            self.kv_blocks = kv_blocks
            from repro.serve.kv_pool import KVPool
            # the pool persists across run() calls: the prefix trie keeps
            # retired prompts' blocks cached, so a later run sharing a
            # prefix skips those chunks too
            self.kv_pool = KVPool(num_blocks=kv_blocks,
                                  block_size=kv_block_size, slots=batch,
                                  max_blocks_per_slot=kv_slot_blocks)
        # stitch_epilogues=False keeps the decode graph's producer→consumer
        # pairs as separate planner ops — the honest unstitched baseline the
        # differential tests and benchmarks compare against
        self.stitch_epilogues = stitch_epilogues
        self.prefill_budget = prefill_budget or PrefillBudget()
        self.reject_overlong = reject_overlong
        self.rng = jax.random.PRNGKey(rng_seed)
        self._measure = measure
        self._schedule_cache = schedule_cache
        self._decode = jax.jit(
            lambda p, c, t: lm.decode_step(cfg, p, c, t))
        self._prefill = jax.jit(
            lambda p, b: lm.prefill(cfg, p, b, max_len=self.cache_len))

        self.executed = False
        self._mixed_steps: dict[int, object] = {}   # prompt len -> jitted step
        #                                             (wavefront co-prefill)
        self._cb_steps: dict[int, object] = {}      # n chunks -> jitted step
        #                                             (continuous, executed)
        self._cb_fused_chunks: dict[int, frozenset] = {}  # n chunks -> chunk
        #                                             indices the program
        #                                             fused with decode attn
        self.cb_program_info: dict[int, dict] = {}  # n chunks -> launch
        #                                             table (the supported
        #                                             reporting accessor)
        self._cb_decode = None                      # generic vmapped fallback
        self._refill_write = None
        self._tp_cache_init = None                  # jitted sharded cache
        self.stats = ServeStats(batch=batch)
        # the executed continuous step decodes with _step_params: the plain
        # params single-device, the shard-major-permuted copy under TP (see
        # _tp_permuted_params — shard_map's even last-axis split then hands
        # each shard a self-consistent [q_s|k_s|v_s] / [gate_s|up_s] slab)
        self._step_params = params
        if self.tp_shards > 1:
            # placed on the mesh once: every step then hands shard_map
            # operands that already live where its in_specs say
            permuted = self._tp_permuted_params()
            self._step_params = self._tp_place(
                permuted, self._tp_param_specs(permuted))
        self.fusion_plan = None
        if plan_fusion:
            reason = executable_decode_supported(cfg)
            if reason is None and scheduling == "wavefront" \
                    and lm.layer_runs(cfg)[0].count > 1:
                reason = ("stacked layer runs execute on the continuous "
                          "path only (wavefront keeps the hand-wired step)")
            if reason is None and scheduling == "wavefront" and cfg.is_moe:
                reason = ("MoE decode executes on the continuous path only "
                          "(the wavefront co-prefill glue is dense-FFN "
                          "shaped)")
            if reason is None:
                # the executed decode program indexes the cache by the
                # planned (128-aligned) length; ``cache_len`` exposes it —
                # ``max_len`` stays exactly what the caller configured
                if scheduling == "wavefront":
                    # the continuous path builds its own per-P steps
                    # (_cb_step) lazily; only wavefront decodes through
                    # this program
                    self._decode = jax.jit(
                        self._make_decode_step(prefill_len=0))
                self.executed = True
            else:
                print(f"[plan-fusion] decode step stays hand-wired: {reason}")
            self.fusion_plan = self.plan_decode_fusion(
                measure=measure, cache=schedule_cache)

    # ------------------------------------------------------------------
    def _aligned_len(self) -> int:
        return max(128, -(-self.max_len // 128) * 128)

    @property
    def cache_len(self) -> int:
        """Rows of cache a slot can actually hold — the admission and
        retirement limit.  ``max_len`` is immutable (exactly what the
        caller configured); the executed paths size their cache to the
        128-aligned length, and the paged path to the per-slot block-table
        span, so capacity can EXCEED ``max_len`` (a paged engine with
        ``kv_slot_blocks`` raised serves prompts the contiguous contract
        would reject)."""
        if getattr(self, "paged_kv", False):
            return self.kv_slot_blocks * self.kv_block_size
        if getattr(self, "executed", False):
            return self._aligned_len()
        return self.max_len

    @property
    def kv_in_place(self) -> bool:
        """Whether the executed continuous step keeps the K/V cache in place
        across its layer scan: true for a stacked (``count > 1``)
        contiguous run.  The cache is then ``(L, B, S, Hkv * D)`` per
        leaf, the scan carries it whole, and decode attention reads layer
        ``l`` of it in HBM (docs/serving.md §The KV cache in place).  Paged
        arenas and single-layer runs keep their per-layer form."""
        return (getattr(self, "executed", False)
                and not getattr(self, "paged_kv", False)
                and lm.layer_runs(self.cfg)[0].count > 1)

    @staticmethod
    def _kv_chunk(S: int) -> int:
        """KV rows per attention grid step: the largest 128-multiple <= 512
        dividing the (128-aligned) cache length.  Paged caches hold whole
        pages per chunk because the block size divides 128."""
        return next(c for c in range(min(512, S), 0, -128) if S % c == 0)

    def chunk_rows(self, budget: Optional[PrefillBudget] = None) -> int:
        """Prompt rows per prefill chunk: the budget's ``effective_chunk``
        against the cache, shrunk until the co-resident chunks' attention
        kernels take at most half the VMEM budget double-buffered (the
        decode side's members share the launch).  At smoke widths the
        budget's chunk always fits; at published widths this is what keeps
        a 2048-row request for a chunk from asking for gigabytes of VMEM."""
        from repro.core.cost_model import VMEM_BUDGET
        from repro.kernels.prefill_attention import prefill_attention_op

        budget = budget or self.prefill_budget
        cfg = self.cfg
        paged = getattr(self, "paged_kv", False)
        S = self.cache_len if paged else self._aligned_len()
        multiple = self.kv_block_size if paged else 1
        tp = getattr(self, "tp_shards", 1)
        rows = budget.chunk_rows
        while True:
            C = dataclasses.replace(budget, chunk_rows=rows).effective_chunk(
                S, multiple=multiple)
            op = prefill_attention_op(
                C, S, cfg.num_heads // tp, cfg.num_kv_heads // tp,
                cfg.resolved_head_dim, dtype=jnp.dtype(cfg.dtype),
                ck=self._kv_chunk(S))
            need = 2 * op.vmem_bytes * budget.max_coresident_chunks
            if C <= multiple or need <= VMEM_BUDGET // 2:
                return C
            rows = C - 1

    def decode_graph(self, *, budget: Optional[PrefillBudget] = None,
                     prefill_chunks: int = 0, ffn_rows: int = 0,
                     dynamic_length: bool = True,
                     prefill_rows: Optional[int] = None):
        """The serving step as a planner graph, with stable operand
        signatures (core/binding.py): decode-slot RMSNorm -> decode
        attention (per-slot valid prefixes in a (B, 1) int32 operand) ->
        post-attention RMSNorm -> the router/FFN in-projection.

        ``prefill_chunks=N`` adds N independent blockwise flash-prefill
        attention ops (kernels/prefill_attention) — one prompt chunk of one
        prefilling slot each, ``budget.effective_chunk`` rows against the
        slot's whole cache.  Compute-bound at scale, they are the paper's
        heterogeneous partners for the memory-bound decode attention.

        ``ffn_rows>0`` adds the legacy wavefront co-prefill partner: the
        riding prompt's FFN in-projection matmul.  (``prefill_rows`` is the
        deprecated alias for it.)  With neither, the graph is a pure decode
        step: a dependency chain the planner correctly leaves unfused.

        For executor-supported configs the graph carries the decode step's
        epilogue chains (core/stitch.py): the pre-attention RMSNorm declares
        the QKV projection matmul as its epilogue consumer, and the FFN
        in-projection declares the activation — the planner contracts each
        pair into one stitched member whose intermediate never touches HBM.
        ``stitch_epilogues=False`` on the engine keeps the same six ops as
        separate nodes (the unstitched baseline).
        """
        from repro.core import planner
        from repro.kernels import elementwise
        from repro.kernels.decode_attention import decode_attention_op
        from repro.kernels.matmul import matmul_1d_op, weight_tile
        from repro.kernels.prefill_attention import prefill_attention_op
        from repro.kernels.rmsnorm import rmsnorm_op

        if prefill_rows is not None:
            warnings.warn("decode_graph(prefill_rows=) is deprecated — use "
                          "ffn_rows (wavefront FFN partner) or "
                          "prefill_chunks + PrefillBudget (chunked prefill)",
                          DeprecationWarning, stacklevel=2)
            ffn_rows = prefill_rows
        budget = budget or self.prefill_budget
        cfg = self.cfg
        d, H, Hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
        D = cfg.resolved_head_dim
        # tensor-parallel: the graph the planner sees is ONE SHARD's —
        # local head counts and local FFN width.  d_model (activations)
        # stays replicated, so every row dimension is unchanged.
        tp = getattr(self, "tp_shards", 1)
        H, Hkv = H // tp, Hkv // tp
        ffn_in = _ffn_in_width(cfg) // tp
        ffn_out = cfg.d_ff // tp
        dt = jnp.dtype(cfg.dtype)
        paged = getattr(self, "paged_kv", False)
        # paged: S is the per-slot LOGICAL capacity spanned by the block
        # table (a 128-multiple by construction); contiguous: the
        # 128-aligned cache length
        S = self.cache_len if paged else self._aligned_len()
        bt = (self.kv_blocks, self.kv_block_size) if paged else None
        B = self.batch

        norm2 = dataclasses.replace(rmsnorm_op(R=B, d=d, dtype=dt, bm=B),
                                    name="decode_norm2")
        ck = self._kv_chunk(S)
        att = decode_attention_op(
            B=B, S=S, H=H, Hkv=Hkv, D=D, dtype=dt, ck=ck,
            dynamic_length=dynamic_length, block_table=bt,
            stacked_layers=(lm.layer_runs(cfg)[0].count
                            if self.kv_in_place else None))
        # decode-slot projection: MoE router when the model routes, else the
        # FFN in-projection — weight streaming dominates at serving batch
        # (memory-bound; the honest fig_framework finding), so the planner
        # pairs it with the prefill chunk's genuinely compute-bound matmul.
        # Weights are streamed in column tiles of a few MiB (weight_tile):
        # a whole (d, 2 d_ff) weight would not fit VMEM.
        gated = (cfg.moe is None and cfg.d_ff > 0
                 and cfg.activation in ("silu", "gelu"))
        ffn_bn = weight_tile(d, ffn_out if gated else ffn_in, dt,
                             views=2 if gated else 1)
        proj = matmul_1d_op(M=B, K=d, N=ffn_in, dtype=dt, bm=B, bn=ffn_bn,
                            gated=gated)
        proj = dataclasses.replace(
            proj, name="moe_router" if cfg.moe is not None else "ffn_proj")
        qkv_n = (H + 2 * Hkv) * D
        qkv = dataclasses.replace(
            matmul_1d_op(M=B, K=d, N=qkv_n, dtype=dt, bm=B,
                         bn=weight_tile(d, qkv_n, dt)),
            name="qkv_proj")
        # the pre-attention norm repeats its row block once per QKV column
        # tile, so the norm -> QKV pair stitches (equal grids, same block)
        norm1 = dataclasses.replace(
            rmsnorm_op(R=B, d=d, dtype=dt, bm=B, repeat=qkv.grid),
            name="decode_norm1")
        executable = executable_decode_supported(cfg) is None
        if executable and cfg.moe is not None:
            # Executed MoE decode: the router projection and the grouped
            # expert GMM (kernels/moe_gmm) are planner ops; the top-k /
            # softmax / dispatch-gather / combine-scatter glue lives in the
            # binding slots between them (build_decode_program).  The
            # router's logits stay fp32 (its own matmul op) so the softmax
            # and top-k see exactly what the vmapped fallback computes;
            # capacity is static per program (capacity(cfg, B) — the same
            # function route_from_logits resolves at trace time).
            from repro.kernels.moe_gmm import moe_gmm_op
            from repro.models import moe as moe_mod
            m = cfg.moe
            proj = dataclasses.replace(
                matmul_1d_op(M=B, K=d, N=m.num_experts,
                             dtype=jnp.float32, bm=B),
                name="moe_router")
            gated = cfg.activation in ("silu", "gelu")
            gmm = moe_gmm_op(
                E=m.num_experts, C=moe_mod.capacity(cfg, B), d=d,
                f=m.d_ff_expert, dtype=dt,
                act=cfg.activation if gated else "gelu", gated=gated)
            if getattr(self, "stitch_epilogues", True):
                norm1 = dataclasses.replace(norm1,
                                            epilogue=(qkv.name, "x"))
            # the expert GMM sits at the end of the decode dependency
            # chain, so its fused partners are the independent prefill
            # chunks — expert weight streaming (memory-bound) riding the
            # chunk's compute-bound attention, the paper's pairing
            graph = [planner.GraphOp(norm1),
                     planner.GraphOp(qkv, deps=frozenset({norm1.name})),
                     planner.GraphOp(att, deps=frozenset({qkv.name})),
                     planner.GraphOp(norm2, deps=frozenset({att.name})),
                     planner.GraphOp(proj, deps=frozenset({norm2.name})),
                     planner.GraphOp(gmm, deps=frozenset({proj.name}))]
        elif executable:
            # Executor-supported configs plan the QKV projection and the FFN
            # activation as graph ops (not binding glue), so each
            # producer→consumer pair can stitch into one launch.  Stitched or
            # not, the op set and numerics are identical — only the epilogue
            # declarations below differ.
            act_fn = {"silu": elementwise.silu_gate,
                      "gelu": elementwise.gelu_gate,
                      "gelu_mlp": elementwise.gelu_plain,
                      "relu2_mlp": elementwise.relu2}[cfg.activation]
            act = elementwise.activation_op(
                R=B, F_in=ffn_in, F_out=ffn_out, fn=act_fn,
                dtype=dt, bm=B, name="decode_act", bn=ffn_bn)
            if getattr(self, "stitch_epilogues", True):
                norm1 = dataclasses.replace(norm1,
                                            epilogue=(qkv.name, "x"))
                proj = dataclasses.replace(proj,
                                           epilogue=(act.name, "h"))
            # precise single-reader dataflow: norm1 feeds ONLY qkv (att
            # consumes the projected q/k/v, not the normed x), and proj
            # feeds ONLY the activation — the contraction pre-pass checks
            # exactly this
            graph = [planner.GraphOp(norm1),
                     planner.GraphOp(qkv, deps=frozenset({norm1.name})),
                     planner.GraphOp(att, deps=frozenset({qkv.name})),
                     planner.GraphOp(norm2, deps=frozenset({att.name})),
                     planner.GraphOp(proj, deps=frozenset({norm2.name})),
                     planner.GraphOp(act, deps=frozenset({proj.name}))]
        else:
            # fallback graph (MoE, stacked runs, ...): QKV/activation stay
            # binding glue; dataflow norm1 -> attention -> norm2 -> proj
            graph = [planner.GraphOp(norm1),
                     planner.GraphOp(att, deps=frozenset({norm1.name})),
                     planner.GraphOp(norm2, deps=frozenset({norm1.name,
                                                            att.name})),
                     planner.GraphOp(proj, deps=frozenset({norm2.name}))]
        if ffn_rows:
            # the wavefront co-prefill partner is a full-FFN-width matmul
            # (compute-bound at scale) — for MoE that is the *expert* FFN
            # in-projection (gate+up fused when gated), not the tiny router
            # projection the decode side plans and not the dense cfg.d_ff
            pf_n = ((2 * cfg.moe.d_ff_expert
                     if cfg.activation in ("silu", "gelu")
                     else cfg.moe.d_ff_expert)
                    if cfg.moe is not None else _ffn_in_width(cfg))
            pf = matmul_1d_op(M=ffn_rows, K=d, N=pf_n,
                              dtype=dt, bm=min(128, ffn_rows))
            pf = dataclasses.replace(pf, name="prefill_ffn")
            graph.append(planner.GraphOp(pf))
        if prefill_chunks:
            C = self.chunk_rows(budget)
            sfx = f"_pg{self.kv_block_size}" if paged else ""
            for i in range(prefill_chunks):
                pa = prefill_attention_op(
                    C, S, H, Hkv, D, dtype=dt, ck=ck, block_table=bt,
                    name=f"prefill_attn{i}_C{C}_S{S}_H{H}kv{Hkv}{sfx}")
                graph.append(planner.GraphOp(pa))
        return graph

    def plan_decode_fusion(self, *, max_ways: Optional[int] = None,
                           budget: Optional[PrefillBudget] = None,
                           measure=None, cache=None,
                           prefill_chunk: Optional[int] = None):
        """Register the serving step's ops as a planner graph (ROADMAP) and
        plan the bundles; ``build_decode_program`` lowers the result onto
        the live slot state.  The graph carries the budget's full chunk
        complement (``max_coresident_chunks`` flash-prefill ops), so the
        plan shown at engine start is the steady mixed-iteration plan.
        With ``measure`` the schedule is profiled, and ``cache`` makes
        every later engine start skip the search entirely.
        """
        from repro.core import planner

        if prefill_chunk is not None:
            warnings.warn("plan_decode_fusion(prefill_chunk=) is deprecated "
                          "— pass budget=PrefillBudget(chunk_rows=...)",
                          DeprecationWarning, stacklevel=2)
            budget = dataclasses.replace(budget or self.prefill_budget,
                                         chunk_rows=prefill_chunk)
        budget = budget or self.prefill_budget
        n = budget.max_coresident_chunks
        if max_ways is None:
            max_ways = 2 + n                 # {att, chunk_0..chunk_{n-1}} +1
        graph = self.decode_graph(budget=budget, prefill_chunks=n)
        return planner.plan(graph, max_ways=max_ways, measure=measure,
                            cache=cache,
                            mesh_tag=getattr(self, "_mesh_tag", ""))

    # ------------------------------------------------------------------
    # Executed decode step: plan -> program -> live slot state
    # ------------------------------------------------------------------
    def build_decode_program(self, *, prefill_chunks: int = 0,
                             ffn_rows: int = 0,
                             interpret: Optional[bool] = None,
                             prefill_rows: Optional[int] = None):
        """Compile the planned decode step into an executor Program bound to
        the live slot state.  The binding setters carry the model glue: the
        norm's output slot projects QKV, applies RoPE at each slot's own
        position and scatters k/v into each slot's cache row (masked by the
        per-slot ``act`` vector, so prefilling/idle slots never see a stale
        garbage write; with ``kv_in_place`` the row lands in layer
        ``state["layer"]`` of the stacked cache, which decode attention
        then reads in place); the attention output slot applies W_o and the
        residual; the projection output slot finishes the MLP and the
        second residual.  Each of the ``prefill_chunks`` flash-prefill ops
        reads its own slot's cache rows (``pf{i}_slot``) at its own chunk
        offset (``pf{i}_off``) — the step function scatters the chunk's k/v
        *before* the program runs.  The state's ``pos`` key is the per-slot
        position vector ``(B,)`` — the wavefront path broadcasts its scalar
        wave position into it (see ``_wave_state``).  ``prefill_rows`` is
        the deprecated alias for ``ffn_rows``.
        """
        from repro.core import executor, planner, stitch
        from repro.core.binding import BindingRegistry, Slot
        from repro.models import layers

        if prefill_rows is not None:
            warnings.warn("build_decode_program(prefill_rows=) is "
                          "deprecated — use ffn_rows (wavefront FFN "
                          "partner) or prefill_chunks (chunked prefill)",
                          DeprecationWarning, stacklevel=2)
            ffn_rows = prefill_rows
        cfg = self.cfg
        if interpret is None:
            # the Pallas interpreter is the CPU test backend only: any
            # accelerator compiles the kernels, and a kernel the compiler
            # refuses fails the step instead of silently interpreting
            interpret = jax.default_backend() == "cpu"
        d, H, Hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
        D = cfg.resolved_head_dim
        # tensor-parallel: the program is traced once and runs SPMD inside
        # shard_map — all head splits below are shard-local, the weight
        # state leaves arrive as shards, and the two row-sharded output
        # projections psum their partial products across ``shard_axis``
        tp = getattr(self, "tp_shards", 1)
        axis = getattr(self, "shard_axis", "model")
        H, Hkv = H // tp, Hkv // tp
        dt = jnp.dtype(cfg.dtype)
        B = self.batch

        graph = self.decode_graph(prefill_chunks=prefill_chunks,
                                  ffn_rows=ffn_rows)
        # allow_same_bound: at full scale the prefill chunk is genuinely
        # compute-bound (the paper pairing); at smoke scale everything is
        # memory-bound and the launch/ramp amortization still decides —
        # admission stays the planner's, never forced
        plan = planner.plan(graph, max_ways=max(3, 2 + prefill_chunks),
                            allow_same_bound=True,
                            measure=self._measure,
                            cache=self._schedule_cache,
                            mesh_tag=getattr(self, "_mesh_tag", ""))

        paged = getattr(self, "paged_kv", False)
        bs = self.kv_block_size if paged else 0
        in_place = self.kv_in_place
        S = self._aligned_len()

        def qkv_put(state, qkv):
            # the planned QKV matmul's output: split heads, RoPE at each
            # slot's own position, act-masked cache scatter (mirrors
            # layers.qkv_project's slicing exactly).  Paged: the scatter
            # routes through each slot's block-table row — writes land at
            # (table[b, pos//bs], pos % bs) in the arena.  An idle slot's
            # table row points at its private sentinel block and a
            # prefilling slot's next block is its own (admission floors
            # prefix reuse to whole chunks), so the masked no-op rewrites
            # can never land on a block another slot shares.
            qkv = qkv.astype(dt)[:, None, :]                    # (B, 1, N)
            q = qkv[..., :H * D].reshape(B, 1, H, D)
            k = qkv[..., H * D:(H + Hkv) * D].reshape(B, 1, Hkv, D)
            v = qkv[..., (H + Hkv) * D:].reshape(B, 1, Hkv, D)
            positions = state["pos"].reshape(B, 1)              # per-slot
            q = layers.rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
            k = layers.rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
            state = dict(state)
            state["q"] = q[:, 0]
            k, v = k[:, 0], v[:, 0]                              # (B, Hkv, D)
            rows = jnp.arange(B)
            if paged:
                rows = state["bt"][rows, state["pos"] // bs]    # arena blocks
                idx = (rows, state["pos"] % bs)
            elif in_place:
                # layer `layer` of the stacked cache, heads flat in a row
                idx = (state["layer"], rows, state["pos"])
                k, v = k.reshape(B, Hkv * D), v.reshape(B, Hkv * D)
            else:
                idx = (rows, state["pos"])
            # act-masked scatter: only decoding slots land k/v — a
            # prefilling slot's row at `pos` is live chunk data this very
            # step and must not be clobbered by its stale last-token write
            act = state["act"].reshape((B,) + (1,) * (k.ndim - 1))
            k_row = jnp.where(act, k, state["k_cache"][idx])
            v_row = jnp.where(act, v, state["v_cache"][idx])
            state["k_cache"] = state["k_cache"].at[idx].set(k_row)
            state["v_cache"] = state["v_cache"].at[idx].set(v_row)
            return state

        psum_axis = axis if tp > 1 else None   # row-sharded W_o / W_out

        def att_put(state, o):
            with _psum_scope(ATTN_OUT_PSUM, psum_axis):
                attn_out = _out_proj(o.astype(dt).reshape(B, H * D),
                                     state["w_o"], psum_axis)
            state = dict(state)
            state["h_mid"] = state["x"] + attn_out              # residual 1
            return state

        def act_put(state, h_act):
            with _psum_scope(FFN_OUT_PSUM, psum_axis):
                ff = _out_proj(h_act.astype(dt), state["w_out"], psum_axis)
            state = dict(state)
            state["x_out"] = state["h_mid"] + ff                # residual 2
            return state

        # bindings follow the CONTRACTED graph: a stitched chain is one node
        # exposing only external operands, so it binds once under its chain
        # name; if the planner left a pair unstitched (or the engine was
        # built with stitch_epilogues=False) each op binds separately with
        # the intermediate routed through a named state slot
        plan_names = {g.op.name for g in plan.graph}
        reg = BindingRegistry()
        chain1 = stitch.chain_label("decode_norm1", "qkv_proj")
        if chain1 in plan_names:
            reg.bind(chain1, x="x", scale="norm1_scale", w="w_qkv",
                     outputs={"out": Slot(put=qkv_put)})
        else:
            reg.bind("decode_norm1", x="x", scale="norm1_scale",
                     outputs={"out": "x_normed"})
            reg.bind("qkv_proj", x="x_normed", w="w_qkv",
                     outputs={"out": Slot(put=qkv_put)})
        att_name = next(g.op.name for g in graph
                        if g.op.name.startswith("decode_attn"))
        att_in = {"len": Slot(get=lambda s: (s["pos"] + 1)
                              .reshape(B, 1).astype(jnp.int32))}
        if paged:
            att_in["bt"] = "bt"               # (B, max_blocks) device table
        if in_place:
            att_in["layer"] = Slot(get=lambda s: jnp.reshape(
                s["layer"], (1, 1)).astype(jnp.int32))
        reg.bind(att_name, q="q", k="k_cache", v="v_cache",
                 inputs=att_in,
                 outputs={"o": Slot(put=att_put), "m": "attn_m",
                          "l": "attn_l"})
        reg.bind("decode_norm2", x="h_mid", scale="norm2_scale",
                 outputs={"out": "h2"})
        gmm_name = next((g.op.name for g in graph
                         if g.op.name.startswith("moe_gmm")), None)
        if gmm_name is not None:
            # MoE: the router matmul and the grouped expert GMM are planner
            # ops; everything between them — softmax/top-k, the sort-based
            # capacity dispatch, the combine scatter — is binding glue.
            # Both glue bodies mirror models/moe.apply() line for line
            # (same fp32 logits, same dt combine multiply, same
            # expert-major scatter-add order) so the executed path is
            # token-for-token the vmapped fallback.
            from repro.models import moe as moe_mod
            m = cfg.moe

            def router_put(state, logits):
                # logits (B, E) fp32 straight off the planned matmul
                r = moe_mod.route_from_logits(cfg, logits)
                state = dict(state)
                h_pad = jnp.concatenate(
                    [state["h2"], jnp.zeros((1, d), state["h2"].dtype)])
                state["moe_xe"] = h_pad[r.dispatch_idx]      # (E, C, d)
                state["moe_dispatch"] = r.dispatch_idx
                state["moe_combine"] = r.combine_w
                # per-expert hit counts over *decoding* slots only — the
                # act mask zeroes prefilling/idle rows and the B-index
                # padding row, so the host-side load stats see real load
                act_pad = jnp.concatenate(
                    [state["act"].astype(jnp.int32),
                     jnp.zeros((1,), jnp.int32)])
                state["expert_counts"] = act_pad[r.dispatch_idx].sum(axis=1)
                return state

            def gmm_put(state, ye):
                # combine: weight each expert row, scatter-add back to its
                # token (expert-major order, matching apply()); shared
                # experts run dense on the same normed hidden
                state = dict(state)
                ye = ye * state["moe_combine"][..., None].astype(ye.dtype)
                out = jnp.zeros((B + 1, d), ye.dtype).at[
                    state["moe_dispatch"].reshape(-1)].add(
                    ye.reshape(-1, d))[:B]
                if m.num_shared_experts:
                    h = state["h2"] @ state["shared_w_in"]
                    if cfg.activation in ("silu", "gelu"):
                        g_, u_ = jnp.split(h, 2, axis=-1)
                        h = (jax.nn.silu(g_) if cfg.activation == "silu"
                             else jax.nn.gelu(g_)) * u_
                    else:
                        h = jax.nn.gelu(h)
                    out = out + h @ state["shared_w_out"]
                state["x_out"] = state["h_mid"] + out.astype(dt)  # residual 2
                return state

            # the router reads h2 widened to fp32 — exactly the fallback's
            # x2d.astype(float32) @ router_w
            reg.bind("moe_router",
                     inputs={"x": Slot(get=lambda s:
                                       s["h2"].astype(jnp.float32)),
                             "w": "w_router"},
                     outputs={"out": Slot(put=router_put)})
            reg.bind(gmm_name, xe="moe_xe", w_in="w_in", w_out="w_out",
                     outputs={"ye": Slot(put=gmm_put)})
        else:
            proj_name = "moe_router" if cfg.moe is not None else "ffn_proj"
            chain2 = stitch.chain_label(proj_name, "decode_act")
            # a gated projection reads the [gate | up] weight twice, as its
            # gate and up column-tile views
            if chain2 in plan_names:
                reg.bind(chain2, x="h2", w="w_in", w_up="w_in",
                         outputs={"out": Slot(put=act_put)})
            else:
                reg.bind(proj_name, x="h2", w="w_in", w_up="w_in",
                         outputs={"out": "h_ffn"})
                reg.bind("decode_act", h="h_ffn",
                         outputs={"out": Slot(put=act_put)})
        if ffn_rows:
            reg.bind("prefill_ffn", x="pf_h2", w="w_in", outputs={"out": "pf_ffn"})
        for g in graph:
            if not g.op.name.startswith("prefill_attn"):
                continue
            i = int(g.op.name.split("_")[1][4:])      # prefill_attn{i}_...
            # the chunk reads ITS OWN slot's cache rows — a (S, Hkv, D)
            # gather the decode scatter never touches (act masks that slot).
            # Paged: k/v are the WHOLE shared arena and the chunk's slot
            # contributes its (1, max_blocks) table row instead.  In place:
            # the slot's rows of this layer, heads unflattened.
            if paged:
                pf_in = {"off": f"pf{i}_off", "q": f"pf{i}_q",
                         "k": "k_cache", "v": "v_cache",
                         "bt": Slot(get=lambda s, i=i:
                                    s["bt"][s[f"pf{i}_slot"]][None])}
            elif in_place:
                def slot_rows(name, i=i):
                    return Slot(get=lambda s: s[name][
                        s["layer"], s[f"pf{i}_slot"]].reshape(S, Hkv, D))
                pf_in = {"off": f"pf{i}_off", "q": f"pf{i}_q",
                         "k": slot_rows("k_cache"), "v": slot_rows("v_cache")}
            else:
                pf_in = {"off": f"pf{i}_off", "q": f"pf{i}_q",
                         "k": Slot(get=lambda s, i=i:
                                   s["k_cache"][s[f"pf{i}_slot"]]),
                         "v": Slot(get=lambda s, i=i:
                                   s["v_cache"][s[f"pf{i}_slot"]])}
            reg.bind(g.op.name, inputs=pf_in, outputs={"o": f"pf{i}_o"})
        return executor.compile_plan(plan, bindings=reg, interpret=interpret)

    def _layer_state(self, p, kv, x, pos, act):
        """State pytree for ONE layer of the executed program: ``p`` is the
        layer's block params, ``kv`` its ``{"k", "v"}`` cache leaves (in
        place, the whole stacked cache the scan carries); ``pos`` is
        the per-slot position vector (B,), ``act`` the per-slot decoding
        mask (B,) bool gating the decode k/v scatter."""
        state = {
            "x": x, "pos": pos, "act": act,
            "norm1_scale": p["norm1"]["scale"].reshape(1, -1),
            "norm2_scale": p["norm2"]["scale"].reshape(1, -1),
            "w_qkv": p["attn"]["w_qkv"], "w_o": p["attn"]["w_o"],
            "k_cache": kv["k"], "v_cache": kv["v"],
        }
        if "moe" in p:
            # expert-major leaves: the router projection plus the grouped
            # GMM's (E, d, fin)/(E, f, d) weight stacks (models/moe.spec)
            state["w_router"] = p["moe"]["router"]
            state["w_in"] = p["moe"]["w_in"]
            state["w_out"] = p["moe"]["w_out"]
            if self.cfg.moe.num_shared_experts:
                state["shared_w_in"] = p["moe"]["shared_w_in"]
                state["shared_w_out"] = p["moe"]["shared_w_out"]
        else:
            state["w_in"] = p["mlp"]["w_in"]
            state["w_out"] = p["mlp"]["w_out"]
        return state

    def _slot_state(self, params, cache, x, pos, act):
        """Single-layer form of ``_layer_state`` over the full param/cache
        trees (the wavefront path and unstacked configs)."""
        run = lm.layer_runs(self.cfg)[0]
        return self._layer_state(params[run.name], cache[run.name],
                                 x, pos, act)

    # ------------------------------------------------------------------
    # Tensor parallelism: shard-major weight layout + shard_map specs
    # ------------------------------------------------------------------
    def _tp_permuted_params(self):
        """Params copy whose fused column-sharded weights are permuted to
        shard-major order (distributed/sharding.py): w_qkv's [q|k|v] column
        blocks become per-shard [q_s|k_s|v_s], a gated w_in's [gate|up]
        becomes per-shard [gate_s|up_s] — shard_map's even last-axis split
        then hands every shard a slab the unmodified head-split and
        gate-split glue consumes directly.  Row-sharded weights (w_o,
        w_out) and everything replicated pass through untouched."""
        from repro.distributed import sharding as shd
        cfg = self.cfg
        run = lm.layer_runs(cfg)[0]
        p = dict(self.params)
        blk = dict(p[run.name])
        attn = dict(blk["attn"])
        attn["w_qkv"] = shd.tp_permute_qkv(
            attn["w_qkv"], cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, self.tp_shards)
        blk["attn"] = attn
        if cfg.activation in ("silu", "gelu"):
            mlp = dict(blk["mlp"])
            mlp["w_in"] = shd.tp_permute_gated_ffn(
                mlp["w_in"], cfg.d_ff, self.tp_shards)
            blk["mlp"] = mlp
        p[run.name] = blk
        return p

    def _tp_param_specs(self, params):
        """PartitionSpec tree for the step params: weight leaves shard by
        name (sharding.tp_param_pspec), everything else replicates."""
        from jax.tree_util import tree_map_with_path
        from repro.distributed import sharding as shd
        return tree_map_with_path(
            lambda path, leaf: shd.tp_param_pspec(path[-1].key,
                                                  jnp.ndim(leaf),
                                                  self.shard_axis),
            params)

    def _tp_cache_specs(self):
        """PartitionSpec tree for the slot cache (sharding.tp_cache_pspec):
        k/v shard their head axis, positions replicate."""
        from jax.tree_util import tree_map_with_path
        from repro.distributed import sharding as shd
        return tree_map_with_path(
            lambda path, leaf: shd.tp_cache_pspec(
                path[-1].key, jnp.ndim(leaf), self.shard_axis,
                flat_heads=self.kv_in_place),
            jax.eval_shape(self._init_slot_cache_local))

    def _tp_place(self, tree, specs):
        """Put ``tree`` on the mesh once, leaf by leaf, with ``specs``."""
        from jax.sharding import NamedSharding
        return jax.device_put(
            tree, jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs))

    def _tp_specs(self, n_chunks: int):
        """(in_specs, out_specs) for shard_map around the continuous step:
        weight and KV-cache leaves shard by name, everything the slot
        manager owns — tokens, masks, positions, chunk metadata, block
        tables — replicates."""
        from jax.sharding import PartitionSpec as P
        c_specs = self._tp_cache_specs()
        in_specs = (self._tp_param_specs(self._step_params), c_specs, P(), P())
        if getattr(self, "paged_kv", False):
            in_specs += (P(),)
        if n_chunks:
            in_specs += (P(), P(), P(), P())
        out_specs = (P(), c_specs) + ((P(),) if n_chunks else ())
        return in_specs, out_specs

    def _wave_state(self, params, cache, x):
        """Wavefront form: the scalar wave position broadcasts into the
        per-slot (B,) position vector the program contract expects; every
        wavefront slot decodes, so the scatter mask is all-true."""
        pos = jnp.full((self.batch,), cache["pos"], jnp.int32)
        return self._slot_state(params, cache, x, pos,
                                jnp.ones((self.batch,), bool))

    def _coprefill_to_ffn_in(self, params, pf_tokens, P: int, pf_rows: int):
        """Run a riding prompt's prefill up to the FFN in-projection input
        — the part that precedes the fused launch.  pf_tokens: (Bp, P).
        Returns (pf_h2 (pf_rows, d) zero-padded, xm post-attention hidden
        (Bp, P, d), kp, vp (Bp, P, Hkv, D))."""
        from repro.models import layers

        cfg = self.cfg
        run = lm.layer_runs(cfg)[0]
        p = params[run.name]
        xp, _ = lm._embed_inputs(cfg, params, {"tokens": pf_tokens})
        Bp = xp.shape[0]
        hp = layers.apply_norm(cfg, p["norm1"], xp)
        qp, kp, vp = layers.qkv_project(cfg, p["attn"], hp)
        positions = jnp.arange(P)[None, :]
        qp = layers.rope(qp, positions, cfg.rope_theta, cfg.rope_fraction)
        kp = layers.rope(kp, positions, cfg.rope_theta, cfg.rope_fraction)
        op_ = layers.blockwise_attention(qp, kp, vp, causal=True)
        xm = xp + op_.reshape(Bp, P, -1) @ p["attn"]["w_o"]
        h2p = layers.apply_norm(cfg, p["norm2"], xm)
        rows = Bp * P
        pf_x = h2p.reshape(rows, cfg.d_model)
        if pf_rows != rows:
            pf_x = jnp.concatenate(
                [pf_x, jnp.zeros((pf_rows - rows, cfg.d_model), pf_x.dtype)])
        return pf_x.astype(jnp.dtype(cfg.dtype)), xm, kp, vp

    def _make_decode_step(self, prefill_len: int):
        """The jitted executed decode step (wavefront scheduling).
        ``prefill_len > 0`` is the mixed form: the pending wave's
        (B, prefill_len) prompt rides along — its FFN in-projection joins
        the fused launch, the rest of its prefill completes here, and the
        returned (cache, logits) seed that wave's decode without ever
        calling ``lm.prefill``."""
        from repro.models import layers

        cfg = self.cfg
        B, d = self.batch, cfg.d_model
        run = lm.layer_runs(cfg)[0]
        S = self._aligned_len()
        P = prefill_len
        rows = B * P
        pf_rows = self.prefill_budget.pad_rows(rows)
        program = self.build_decode_program(ffn_rows=pf_rows if P else 0)

        def step(params, cache, tokens, pf_tokens=None):
            p = params[run.name]
            x = layers.embed_onehot(params["embed"], tokens[:, None], d)
            state = self._wave_state(params, cache, x[:, 0])

            if P:
                # pending wave's prefill, up to the FFN in-projection
                state["pf_h2"], xm, kp, vp = self._coprefill_to_ffn_in(
                    params, pf_tokens, P, pf_rows)

            state = program(state)

            xf = layers.apply_norm(cfg, params["final_norm"],
                                   state["x_out"][:, None, :].astype(x.dtype))
            logits = lm._head(cfg, params, xf)[:, 0]
            new_cache = {"pos": cache["pos"] + 1,
                         run.name: {"k": state["k_cache"],
                                    "v": state["v_cache"]}}
            if not P:
                return logits, new_cache

            ff = _mlp_from_h(cfg, state["pf_ffn"][:rows]
                             .astype(jnp.dtype(cfg.dtype)).reshape(B, P, -1),
                             p["mlp"]["w_out"])
            xop = xm + ff
            kc = jnp.zeros((B, S) + kp.shape[2:], kp.dtype)
            vc = jnp.zeros_like(kc)
            pf_cache = {"pos": jnp.asarray(P, jnp.int32),
                        run.name: {
                            "k": jax.lax.dynamic_update_slice(
                                kc, kp, (0, 0, 0, 0)),
                            "v": jax.lax.dynamic_update_slice(
                                vc, vp, (0, 0, 0, 0))}}
            xfp = layers.apply_norm(cfg, params["final_norm"], xop[:, -1:])
            pf_logits = lm._head(cfg, params, xfp)[:, 0]
            return logits, new_cache, pf_cache, pf_logits

        return step

    def _mixed_step(self, prefill_len: int):
        if prefill_len not in self._mixed_steps:
            self._mixed_steps[prefill_len] = jax.jit(
                self._make_decode_step(prefill_len))
        return self._mixed_steps[prefill_len]

    # ------------------------------------------------------------------
    # Continuous batching: per-slot cache positions, admit/refill per token
    # ------------------------------------------------------------------
    def _init_slot_cache(self):
        """The slot cache.  Under tensor parallelism it is made where it
        lies on the mesh: each chip writes only its own KV heads' zeros,
        and no chip ever holds the whole cache (at granite-3.0-8b's 32
        slots x 2048 rows that is 10.7 GB, more than one v5e has free
        beside the weights)."""
        if getattr(self, "tp_shards", 1) == 1:
            return self._init_slot_cache_local()
        if self._tp_cache_init is None:
            from jax.sharding import NamedSharding
            out = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                               self._tp_cache_specs())
            self._tp_cache_init = jax.jit(self._init_slot_cache_local,
                                          out_shardings=out)
        return self._tp_cache_init()

    def _init_slot_cache_local(self):
        """The slot cache: ``lm.init_cache`` with the scalar wave position
        replaced by the per-slot position vector (B,).  Paged: the k/v
        leaves are the flat ``(kv_blocks, block_size, Hkv, D)`` arena the
        block tables index into, not per-slot regions.  In place
        (``kv_in_place``): each k/v leaf is ``(L, B, S, Hkv * D)``, a
        slot's row holding its heads side by side, which the stacked decode
        attention reads without a relayout."""
        run = lm.layer_runs(self.cfg)[0]
        cfg = self.cfg
        if getattr(self, "paged_kv", False):
            shape = (self.kv_blocks, self.kv_block_size,
                     cfg.num_kv_heads, cfg.resolved_head_dim)
        elif self.kv_in_place:
            shape = (run.count, self.batch, self.cache_len,
                     cfg.num_kv_heads * cfg.resolved_head_dim)
        else:
            cache = lm.init_cache(cfg, self.batch, self.cache_len)
            cache["pos"] = jnp.zeros((self.batch,), jnp.int32)
            return cache
        dt = jnp.dtype(cfg.dtype)
        return {"pos": jnp.zeros((self.batch,), jnp.int32),
                run.name: {"k": jnp.zeros(shape, dt),
                           "v": jnp.zeros(shape, dt)}}

    def _slot_axes(self):
        """vmap axes pytree for the slot cache: batch lives on axis 0 of
        plain run leaves and axis 1 of scan-stacked (layer-major) leaves."""
        axes = {"pos": 0}
        for run in lm.layer_runs(self.cfg):
            leaves = lm._cache_leaf_shapes(self.cfg, run, 1, self.cache_len)
            axes[run.name] = {name: (1 if run.count > 1 else 0)
                              for name in leaves}
        return axes

    def _cb_plain_decode(self):
        """Generic continuous decode: ``lm.decode_step`` vmapped over slots,
        each at its own cache position — works for EVERY config (stacked
        runs, MoE, recurrent caches), not just the executable shape."""
        if self._cb_decode is None:
            cfg = self.cfg
            runs = lm.layer_runs(cfg)
            axes = self._slot_axes()

            def one(params, cache_b, tok):
                # vmap stripped the slot axis — restore the B=1 batch dim
                # lm.decode_step expects (pos stays a per-slot scalar)
                full = {"pos": cache_b["pos"]}
                for run in runs:
                    ax = 1 if run.count > 1 else 0
                    full[run.name] = {k: jnp.expand_dims(v, ax)
                                      for k, v in cache_b[run.name].items()}
                logits, newc = lm.decode_step(cfg, params, full, tok[None])
                out = {"pos": newc["pos"]}
                for run in runs:
                    ax = 1 if run.count > 1 else 0
                    out[run.name] = {k: jnp.squeeze(v, ax)
                                     for k, v in newc[run.name].items()}
                return logits[0], out

            def step(params, cache, tokens, active):
                logits, newc = jax.vmap(
                    one, in_axes=(None, axes, 0),
                    out_axes=(0, axes))(params, cache, tokens)
                # inactive slots hold their position (their writes land one
                # past their retired prefix — masked, and overwritten by the
                # next refill before they could ever become visible)
                newc["pos"] = jnp.where(active, newc["pos"], cache["pos"])
                return logits, newc

            self._cb_decode = jax.jit(step)
        return self._cb_decode

    def _cb_refill(self, cache, slot, prompt):
        """Admit one prompt into a free slot: prefill (1, P), write the
        cache leaves into the slot's rows, set its position to P.  Returns
        (cache, last-token logits (V,))."""
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
        c1, logits = self._prefill(self.params, {"tokens": toks})
        if self._refill_write is None:
            runs = lm.layer_runs(self.cfg)

            def write(cache, c1, slot):
                new = {"pos": cache["pos"].at[slot]
                       .set(c1["pos"].astype(jnp.int32))}
                for run in runs:
                    if run.count > 1:
                        new[run.name] = {
                            k: cache[run.name][k].at[:, slot]
                            .set(c1[run.name][k][:, 0])
                            for k in cache[run.name]}
                    else:
                        new[run.name] = {
                            k: cache[run.name][k].at[slot]
                            .set(c1[run.name][k][0])
                            for k in cache[run.name]}
                return new

            self._refill_write = jax.jit(write)
        return self._refill_write(cache, c1, jnp.asarray(slot)), logits[0]

    def _make_cb_step(self, n_chunks: int):
        """The jitted executed continuous step: decode every slot at its own
        cache position; with ``n_chunks > 0``, that many prompt chunks from
        *prefilling* slots ride along.  Each chunk's k/v is scattered into
        its slot's cache rows before the program runs, its flash-prefill
        attention shares the decode launch (the steady mixed
        prefill⊕decode bundle), and the chunk's FFN + residuals finish
        after the program.  The final chunk's last valid row yields the
        request's first-token logits.

        Stacked configs (one ATTN run with ``count > 1``) scan the
        per-layer body over the layer-stacked params — the program runs
        once per layer inside ``lax.scan``, carrying the decode hidden
        (B, d), each chunk's (C, d) hidden and the whole stacked K/V cache
        between layers (``kv_in_place``): layer ``l`` writes its new rows
        into the carried cache and decode attention reads layer ``l`` of
        it where it lies, so no layer's cache is sliced out or written
        back.  Under tensor parallelism the whole step body runs inside
        ``jax.shard_map``: every shard executes its own shard-local
        fused program, the output projections psum, and logits/positions
        come out replicated."""
        from repro.models import layers
        from repro.runtime_flags import maybe_scan

        cfg = self.cfg
        B, d = self.batch, cfg.d_model
        run = lm.layer_runs(cfg)[0]
        L = run.count
        dt = jnp.dtype(cfg.dtype)
        n = n_chunks
        tp = self.tp_shards
        axis = self.shard_axis
        psum_axis = axis if tp > 1 else None   # row-sharded W_o / W_out
        H_l = cfg.num_heads // tp
        Hkv_l = cfg.num_kv_heads // tp
        D = cfg.resolved_head_dim
        paged = getattr(self, "paged_kv", False)
        bs = self.kv_block_size if paged else 0
        in_place = self.kv_in_place
        C = self.chunk_rows()
        program = self.build_decode_program(prefill_chunks=n)
        # a chunk counts as fused when it shares a launch with any
        # decode-side member — decode attention OR the stitched FFN chain
        # (with epilogue stitching the planner's second bundle pairs a chunk
        # with ffn_proj→decode_act, which is just as much a mixed launch)
        self._cb_fused_chunks[n] = frozenset(
            i for i in range(n)
            if any(any(m.startswith(f"prefill_attn{i}_") for m in ms)
                   and any(not m.startswith("prefill_attn") for m in ms)
                   for ms in program.fused_members))
        self.cb_program_info[n] = {
            "fused_launches": program.n_fused,
            "total_launches": len(program.steps),
            "fused_members": [sorted(ms) for ms in program.fused_members],
            "steps": program.describe(),
            "interpret": program.interpret,
            "kv_in_place": in_place,
        }
        is_moe = cfg.moe is not None

        def layer_step(p, kv, x, pos, act, bt, chs, ch_slots, ch_offs,
                       layer=None):
            """One transformer layer over the whole slot state: the decode
            step for all B slots plus the riding chunks' pre/post-work.
            ``chs`` is the tuple of per-chunk (C, d) hiddens this layer
            consumes and reproduces (the scan carry).  In place, ``kv`` is
            the whole stacked cache and ``layer`` this layer's index."""
            state = self._layer_state(p, kv, x, pos, act)
            if paged:
                state["bt"] = bt              # (B, max_blocks) int32 tables
            if in_place:
                state["layer"] = layer

            # chunk pre-work: norm + QKV + RoPE at absolute chunk
            # positions, then land the chunk's k/v in its slot's cache rows
            # BEFORE the program (the prefill kernel only reads the cache).
            # The QKV split uses shard-local head counts — under TP the
            # weight slab arrives permuted to [q_s|k_s|v_s], so the plain
            # contiguous slicing below is exactly layers.qkv_project on
            # this shard's heads.  Paged: chunk offsets are chunk-aligned
            # (admission floors prefix reuse to whole chunks), so the
            # chunk covers exactly C // bs whole pages — gather their
            # arena blocks from the slot's table row and scatter page by
            # page.
            kc, vc = state["k_cache"], state["v_cache"]
            for i in range(n):
                xp = chs[i][None]                              # (1, C, d)
                hp = layers.apply_norm(cfg, p["norm1"], xp)
                qkv = hp @ p["attn"]["w_qkv"]
                qp = qkv[..., :H_l * D].reshape(1, C, H_l, D)
                kp = qkv[..., H_l * D:(H_l + Hkv_l) * D] \
                    .reshape(1, C, Hkv_l, D)
                vp = qkv[..., (H_l + Hkv_l) * D:].reshape(1, C, Hkv_l, D)
                positions = ch_offs[i] + jnp.arange(C)[None, :]
                qp = layers.rope(qp, positions, cfg.rope_theta,
                                 cfg.rope_fraction)
                kp = layers.rope(kp, positions, cfg.rope_theta,
                                 cfg.rope_fraction)
                if paged:
                    npg = C // bs
                    blks = jax.lax.dynamic_slice(
                        bt, (ch_slots[i], ch_offs[i] // bs), (1, npg))[0]
                    kc = kc.at[blks].set(
                        kp[0].reshape(npg, bs, *kp.shape[2:]).astype(kc.dtype))
                    vc = vc.at[blks].set(
                        vp[0].reshape(npg, bs, *vp.shape[2:]).astype(vc.dtype))
                elif in_place:
                    at = (layer, ch_slots[i], ch_offs[i], 0)
                    kc = jax.lax.dynamic_update_slice(
                        kc, kp.reshape(1, 1, C, -1).astype(kc.dtype), at)
                    vc = jax.lax.dynamic_update_slice(
                        vc, vp.reshape(1, 1, C, -1).astype(vc.dtype), at)
                else:
                    kc = jax.lax.dynamic_update_slice(
                        kc, kp.astype(kc.dtype),
                        (ch_slots[i], ch_offs[i], 0, 0))
                    vc = jax.lax.dynamic_update_slice(
                        vc, vp.astype(vc.dtype),
                        (ch_slots[i], ch_offs[i], 0, 0))
                state[f"pf{i}_q"] = qp[0].transpose(1, 0, 2).astype(dt)
                state[f"pf{i}_slot"] = ch_slots[i]
                state[f"pf{i}_off"] = jnp.reshape(ch_offs[i],
                                                  (1, 1)).astype(jnp.int32)
            state["k_cache"], state["v_cache"] = kc, vc

            state = program(state)

            # chunk post-work: W_o + residual, norm2 + MLP + residual —
            # the chunk leaves this layer as its next (C, d) hidden.
            # Under TP both output projections are row-sharded partials.
            new_chs = []
            for i in range(n):
                o = state[f"pf{i}_o"].astype(dt)             # (H_l, C, D)
                with _psum_scope(ATTN_OUT_PSUM, psum_axis):
                    attn_out = _out_proj(o.transpose(1, 0, 2).reshape(C, -1),
                                         p["attn"]["w_o"], psum_axis)
                xm = chs[i] + attn_out
                h2 = layers.apply_norm(cfg, p["norm2"], xm[None])
                if is_moe:
                    # chunk rows route jointly (T = C), same jnp path as
                    # the fallback's whole-prompt prefill — at the serving
                    # capacities in play (capacity(cfg, C) >= C) neither
                    # batching ever drops a token, so outputs are exact
                    ff = lm._apply_ffn(cfg, p, h2, True)[0][0]
                else:
                    ff = _mlp_from_h(cfg, h2[0] @ p["mlp"]["w_in"],
                                     p["mlp"]["w_out"], psum_axis)
                new_chs.append(xm + ff)
            ret = (state["x_out"],
                   {"k": state["k_cache"], "v": state["v_cache"]},
                   tuple(new_chs))
            if is_moe:
                ret += (state["expert_counts"],)
            return ret

        def core(params, cache, tokens, active, *rest):
            rest = list(rest)
            bt = rest.pop(0) if paged else None
            ch_slots = ch_offs = ch_valid = ch_tokens = None
            if n:
                ch_slots, ch_offs, ch_valid, ch_tokens = rest
            x = layers.embed_onehot(params["embed"], tokens[:, None], d)
            chs = tuple(
                lm._embed_inputs(cfg, params,
                                 {"tokens": ch_tokens[i][None]})[0][0]
                for i in range(n))
            pos = cache["pos"]
            ecounts = None
            if L == 1:
                out = layer_step(
                    params[run.name], cache[run.name], x[:, 0], pos,
                    active, bt, chs, ch_slots, ch_offs)
                if is_moe:
                    x1, kv_new, chs, ecounts = out
                else:
                    x1, kv_new, chs = out
            else:
                # the whole stacked cache rides the carry (in place); the
                # MoE scan also carries a per-expert hit accumulator so
                # the host sees layer-summed counts per step
                def body(carry, xs):
                    xc, chc, kv, cnt = carry
                    p_l, l = xs
                    xn, kv, chn, *c_l = layer_step(p_l, kv, xc, pos, active,
                                                   bt, chc, ch_slots,
                                                   ch_offs, layer=l)
                    if is_moe:
                        cnt = cnt + c_l[0]
                    return (xn, chn, kv, cnt), None
                cnt0 = (jnp.zeros((cfg.moe.num_experts,), jnp.int32)
                        if is_moe else None)
                (x1, chs, kv_new, ecounts), _ = maybe_scan(
                    body, (x[:, 0], chs, cache[run.name], cnt0),
                    (params[run.name], jnp.arange(L, dtype=jnp.int32)),
                    length=L)

            xf = layers.apply_norm(cfg, params["final_norm"],
                                   x1[:, None, :].astype(x.dtype))
            logits = lm._head(cfg, params, xf)[:, 0]
            new_pos = jnp.where(active, pos + 1, pos)
            new_cache = {"pos": new_pos, run.name: kv_new}
            moe_tail = (ecounts,) if is_moe else ()
            if not n:
                return (logits, new_cache) + moe_tail

            # the (possibly partial) chunk's last valid row -> first-token
            # logits; positions advance by the chunk's valid rows
            pf_logits = []
            for i in range(n):
                xlast = jax.lax.dynamic_slice_in_dim(chs[i],
                                                     ch_valid[i] - 1, 1)
                xfp = layers.apply_norm(cfg, params["final_norm"],
                                        xlast[None])
                pf_logits.append(lm._head(cfg, params, xfp)[0, 0])
                new_pos = new_pos.at[ch_slots[i]].set(ch_offs[i]
                                                      + ch_valid[i])
            new_cache["pos"] = new_pos
            return (logits, new_cache, jnp.stack(pf_logits)) + moe_tail

        if tp > 1:
            in_specs, out_specs = self._tp_specs(n)
            # fully-manual SPMD: every shard traces the same program over
            # its slab; logits come out replicated (both projections psum
            # before anything data-dependent), so sampling stays host-side
            # and shard-invariant.  check_vma=False: replication is not
            # inferred through the Pallas calls.
            core = jax.shard_map(core, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_specs, axis_names={axis},
                                 check_vma=False)

        def step(params, cache, tokens, active, bt=None,
                 ch_slots=None, ch_offs=None, ch_valid=None, ch_tokens=None):
            args = (params, cache, tokens, active)
            if paged:
                args += (bt,)
            if n:
                args += (ch_slots, ch_offs, ch_valid, ch_tokens)
            return core(*args)

        return step

    def _cb_step(self, n_chunks: int):
        """The jitted continuous step.  Where its kernels compile (any
        backend but the CPU), the step donates its cache argument, so the
        cache it returns reuses the input's buffers and the K/V rows a step
        writes land where the cache lies.  The CPU's interpret mode keeps
        its input cache alive: a caller there may run a step twice on one
        state.

        Under tensor parallelism the first call compiles the step through
        ``lower(...).compile()`` and reads the compiled program's
        collectives: ``cb_program_info[n]["collectives"]`` counts them by
        kind, ``["collective_dtypes"]`` the element types each reads and
        writes (``f32->bf16``: fp32 partial products in, the rounded sum
        out).  The call itself then finds that compilation in JAX's cache,
        so no step compiles twice."""
        if n_chunks not in self._cb_steps:
            step = self._make_cb_step(n_chunks)
            donate = () if self.cb_program_info[n_chunks]["interpret"] \
                else (1,)
            jitted = jax.jit(step, donate_argnums=donate)
            self._cb_steps[n_chunks] = jitted
            if self.tp_shards > 1:
                def first_call(*args, **kw):
                    from repro.distributed import hlo_analysis
                    text = jitted.lower(*args, **kw).compile().as_text()
                    info = self.cb_program_info[n_chunks]
                    info["collectives"] = hlo_analysis.collective_counts(text)
                    info["collective_dtypes"] = \
                        hlo_analysis.collective_dtypes(text)
                    self._cb_steps[n_chunks] = jitted
                    return jitted(*args, **kw)
                self._cb_steps[n_chunks] = first_call
        return self._cb_steps[n_chunks]

    # ------------------------------------------------------------------
    def _wave_tokens(self, wave: list[Request]) -> np.ndarray:
        S = len(wave[0].prompt)
        toks = np.zeros((self.batch, S), np.int32)
        for i, r in enumerate(wave):
            toks[i] = r.prompt
        return toks

    def _prefill_wave(self, wave: list[Request]):
        """Waves are grouped by prompt length (see run()); empty slots
        duplicate row 0 and are ignored."""
        toks = self._wave_tokens(wave)
        cache, last_logits = self._prefill(self.params, {"tokens": jnp.asarray(toks)})
        return cache, last_logits

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature > 0:
            self.rng, sub = jax.random.split(self.rng)
            return int(jax.random.categorical(
                sub, jnp.asarray(logits) / req.temperature))
        return int(logits.argmax())

    # ------------------------------------------------------------------
    def run(self, requests: list[Request]) -> list[Request]:
        if self.scheduling == "continuous":
            return self._run_continuous(requests)
        return self._run_wavefront(requests)

    # ------------------------------------------------------------------
    def _retire_reason(self, req: Request, tok: int, n_out: int, pos: int, *,
                       check_eos: bool = True) -> Optional[str]:
        """Retirement rule over explicit (n_out, pos) so the same-step
        refill predictor evaluates it on post-step values — prediction and
        reality cannot desync."""
        if check_eos and req.eos_token is not None and tok == req.eos_token:
            return "eos"
        if n_out >= req.max_new_tokens:
            return "max_new"
        if pos >= self.cache_len:
            return "max_len"                 # cache full: truncate
        return None

    def _will_retire_this_step(self, req: Request, pos_now: int) -> bool:
        """Deterministic retirement predictor: a decode step always lands
        one token and advances the position by one; EOS is data-dependent
        and deliberately excluded."""
        return self._retire_reason(req, -1, len(req.out_tokens) + 1,
                                   pos_now + 1, check_eos=False) is not None

    def _admit(self, req: Request, slot: int, pf_logits, slots, pos_h, last):
        """First token from the prompt's last-position logits; the slot goes
        active unless the request already retires (budget 1 / cache full).
        EOS is deliberately NOT checked here: the wavefront oracle only
        honours EOS on decode-loop tokens, never on the prefill-sampled
        first token, and the differential harness pins that behaviour."""
        stats = self.stats
        tok = self._sample(np.asarray(pf_logits, np.float32), req)
        req.out_tokens.append(tok)
        stats.tokens += 1
        stats.admissions.append((stats.steps - 1, req.rid, slot))
        stats.admission_latencies.append(stats.steps - 1 - req.arrival)
        pos_h[slot] = len(req.prompt)
        reason = self._retire_reason(req, tok, len(req.out_tokens),
                                     pos_h[slot], check_eos=False)
        if reason:
            req.done = True
            stats.retirements.append((stats.steps - 1, req.rid, reason))
        else:
            assert slots[slot] is None, \
                f"slot {slot} refilled while request {slots[slot].rid} lives"
            slots[slot] = req
            last[slot] = tok

    def _run_continuous(self, requests: list[Request]) -> list[Request]:
        """Iteration-level continuous batching.  Prompts longer than the
        cache can never be admitted; with ``reject_overlong=True`` the
        legacy single-iteration admission contract is restored and prompts
        exceeding one iteration's prefill budget are rejected too.  The
        executed path admits by chunks (``_run_continuous_chunked``); the
        hand-wired fallback prefills whole prompts alongside the decode
        (``_run_continuous_plain``)."""
        paged = getattr(self, "paged_kv", False)
        chunk = self.chunk_rows()
        for r in requests:
            if len(r.prompt) > self.cache_len:
                raise ValueError(
                    f"request {r.rid}: prompt length {len(r.prompt)} exceeds "
                    f"max_seq_len {self.cache_len} — continuous batching "
                    "cannot admit it (raise max_len"
                    + (" or kv_slot_blocks" if paged else "")
                    + " or truncate the prompt)")
            if self.reject_overlong and len(r.prompt) > chunk:
                raise ValueError(
                    f"request {r.rid}: prompt length {len(r.prompt)} exceeds "
                    f"the per-iteration prefill budget {chunk} and this "
                    f"engine was built with reject_overlong=True (drop the "
                    f"flag to admit it in chunks)")
        self.stats = ServeStats(batch=self.batch)
        # FIFO by arrival step, submission order breaking ties
        waiting = sorted(requests, key=lambda r: r.arrival)
        if self.executed:
            return self._run_continuous_chunked(requests, waiting)
        return self._run_continuous_plain(requests, waiting)

    def _run_continuous_chunked(self, requests, waiting) -> list[Request]:
        """Executed continuous batching with chunk-granular admission:
        every step decodes all active slots at their own cache positions
        while up to ``max_coresident_chunks`` *prefilling* slots each
        consume one prompt chunk inside the same fused launch.  A freshly
        emptied slot's first chunk rides the very step it is claimed; a
        slot whose occupant retires deterministically this step is reserved
        and starts chunking the next step (its retiree's final decode must
        read the cache first).  A prompt completing its last chunk samples
        its first token from that chunk's final valid row.

        Every iteration with work in flight is one ``serve.step`` profiler
        span (``step_num`` = ``stats.steps``) holding its host phases in
        order: ``serve.admit``, ``serve.stage``, ``serve.dispatch``
        (args ``step``, ``chunks``, ``active``, ``kv``: ``in_place``
        where the step keeps the stacked cache in place, else ``sliced``,
        and ``shards``: the tensor-parallel shards the step runs over),
        ``serve.sync`` (the
        logits' copy to the host; a second one for the prompt logits),
        ``serve.sample`` and ``serve.first_token`` (arg ``rids``).  An
        iteration with nothing in flight and nothing arrived records none
        (docs/serving.md §Tracing the serve loop)."""
        B = self.batch
        stats = self.stats
        budget = self.prefill_budget
        pool = self.kv_pool
        paged = pool is not None
        is_moe = self.cfg.moe is not None
        C = self.chunk_rows()
        kv_form = "in_place" if self.kv_in_place else "sliced"
        if paged:
            # the pool persists across runs (prefix cache survives); this
            # run's stats report the deltas
            pool_base = (pool.evictions, pool.prefix_hits,
                         pool.prefix_tokens_reused)
        slots: list[Optional[Request]] = [None] * B   # decoding occupants
        pref: dict[int, dict] = {}                    # slot -> prefilling
        #                                               {req, done, ready}
        pos_h = [0] * B                               # host mirror of pos
        last = np.zeros(B, np.int32)
        cache = self._init_slot_cache()

        def claim(b, req, now):
            """Start prefilling ``req`` in slot ``b``.  Paged: allocate its
            table row, and let a prefix-cache hit skip whole chunks —
            ``done`` starts at the reused token count, not 0."""
            ent = {"req": req, "done": 0, "ready": now}
            if paged:
                ent["done"] = pool.admit(b, req.prompt, C, now)
                stats.prompt_tokens += len(req.prompt)
            pref[b] = ent

        def admit(step_i):
            """Arrivals, slot claims, reservations and chunk selection.
            Returns the slots that chunk this step, the reserved
            ``(slot, request)`` pairs and the decoding slots' mask."""
            arrived = [r for r in waiting if r.arrival <= step_i]
            # claim empty slots now (their first chunk rides this very
            # step); deterministically-retiring slots are only *reserved*
            # — their chunk starts next step, after the retiree's final
            # decode has read the cache (EOS retirements are not
            # predictable; those slots are claimed one step later)
            reserved = []
            for b in range(B):
                if not arrived:
                    break
                if slots[b] is None and b not in pref:
                    req = arrived.pop(0)
                    waiting.remove(req)
                    claim(b, req, step_i)
            for b in range(B):
                if not arrived:
                    break
                if slots[b] is not None and self._will_retire_this_step(
                        slots[b], pos_h[b]):
                    req = arrived.pop(0)
                    waiting.remove(req)
                    reserved.append((b, req))
            # chunk selection, capped by the budget's co-residency.
            # fifo: lowest prefilling slot index first (legacy order).
            # srpf: shortest-remaining-prefill-first — the prompt with the
            # fewest chunks left to consume goes first, so near-done
            # requests admit (emit their first token) without queuing
            # behind a long prompt's tail; slot index breaks ties, keeping
            # the schedule deterministic.
            sel = [b for b in sorted(pref) if pref[b]["ready"] <= step_i]
            if budget.policy in ("srpf", "eload"):
                sel.sort(key=lambda b: (len(pref[b]["req"].prompt)
                                        - pref[b]["done"], b))
            sel = sel[:budget.max_coresident_chunks]
            # eload: when the running expert-hit skew says a few hot
            # experts dominate the decode side's weight streaming, shed
            # one coresident chunk this step — the fused launch narrows
            # so the memory phase the hot experts already saturate isn't
            # stretched further by an extra prefill partner
            if (budget.policy == "eload" and len(sel) > 1
                    and stats.expert_skew >= budget.skew_threshold):
                sel = sel[:-1]
                stats.load_shed_steps += 1
            if paged:
                # map the chunk's pages before its scatter; a chunk the
                # arena cannot back this step (even after eviction) simply
                # stalls — admission degrades gracefully, never crashes
                sel = [b for b in sel
                       if pool.ensure_rows(b, pref[b]["done"],
                                           pref[b]["done"] + C, step_i)]
                # each decoding slot writes one token row this step; a slot
                # the pool cannot extend retires truncated (mirrors the
                # contiguous cache-full rule, under dynamic pressure)
                for b in range(B):
                    if slots[b] is None:
                        continue
                    if not pool.ensure_rows(b, pos_h[b], pos_h[b] + 1,
                                            step_i):
                        req = slots[b]
                        req.done = True
                        slots[b] = None
                        pool.release(b)
                        stats.retirements.append((step_i, req.rid,
                                                  "pool_full"))
            return sel, reserved, np.array([s is not None for s in slots])

        while waiting or any(s is not None for s in slots) or pref:
            step_i = stats.steps
            if not pref and all(s is None for s in slots) and not any(
                    r.arrival <= step_i for r in waiting):
                stats.steps += 1       # idle: nothing in flight or arrived
                continue
            with jax.profiler.StepTraceAnnotation("serve.step",
                                                  step_num=step_i):
                with _span("serve.admit"):
                    sel, reserved, active = admit(step_i)
                n_active = int(active.sum())
                n = len(sel)
                if n == 0 and n_active == 0:
                    ready = [b for b in pref if pref[b]["ready"] <= step_i]
                    if paged and ready:
                        # arena deadlock: every schedulable chunk stalled
                        # with no decoder left to drain blocks — fail the
                        # prompt with the most work remaining
                        # (deterministic) so its partial allocation frees
                        # the others
                        b = max(ready, key=lambda b: (
                            len(pref[b]["req"].prompt) - pref[b]["done"], b))
                        req = pref.pop(b)["req"]
                        req.done = True
                        pool.release(b)
                        stats.retirements.append((step_i, req.rid,
                                                  "pool_full"))
                    stats.steps += 1
                    continue

                with _span("serve.stage"):
                    extra = ()
                    if paged:
                        extra = (jnp.asarray(np.asarray(pool.table,
                                                        np.int32)),)
                        stats.blocks_in_use = max(stats.blocks_in_use,
                                                  pool.blocks_in_use)
                    tokens_dev = jnp.asarray(last)
                    active_dev = jnp.asarray(active)
                    chunk_kw = {}
                    if n:
                        ch_valid = [min(C, len(pref[b]["req"].prompt)
                                        - pref[b]["done"]) for b in sel]
                        ch_tok = np.zeros((n, C), np.int32)
                        for j, b in enumerate(sel):
                            off = pref[b]["done"]
                            ch_tok[j, :ch_valid[j]] = np.asarray(
                                pref[b]["req"].prompt[off:off + ch_valid[j]],
                                np.int32)
                        chunk_kw = dict(
                            ch_slots=jnp.asarray(np.asarray(sel, np.int32)),
                            ch_offs=jnp.asarray(np.asarray(
                                [pref[b]["done"] for b in sel], np.int32)),
                            ch_valid=jnp.asarray(np.asarray(ch_valid,
                                                            np.int32)),
                            ch_tokens=jnp.asarray(ch_tok))
                with _span("serve.dispatch", step=step_i, chunks=n,
                           active=n_active, kv=kv_form,
                           shards=self.tp_shards):
                    ret = self._cb_step(n)(
                        self._step_params, cache, tokens_dev, active_dev,
                        *extra, **chunk_kw)
                logits, cache = ret[:2]
                pf_logits = ret[2] if n else None

                stats.steps += 1
                if self.cb_program_info[n]["kv_in_place"]:
                    stats.kv_in_place_steps += 1
                if n_active:
                    stats.decode_steps += 1
                    stats.slot_steps += n_active
                else:
                    stats.prefill_only_steps += 1
                if n and n_active:
                    stats.mixed_steps += 1
                    if self._cb_fused_chunks[n]:
                        stats.fused_mixed_steps += 1
                if n:
                    stats.prefill_chunks += n
                    stats.fused_prefill_chunks += len(
                        self._cb_fused_chunks[n])

                with _span("serve.sync"):
                    logits_np = np.asarray(logits, np.float32)
                    if is_moe:
                        stats.add_expert_hits(np.asarray(ret[-1]))
                with _span("serve.sample"):
                    for b in range(B):
                        req = slots[b]
                        if req is None:
                            continue
                        pos_h[b] += 1
                        tok = self._sample(logits_np[b], req)
                        req.out_tokens.append(tok)
                        stats.tokens += 1
                        last[b] = tok
                        reason = self._retire_reason(
                            req, tok, len(req.out_tokens), pos_h[b])
                        if reason:
                            req.done = True
                            slots[b] = None
                            if paged:
                                pool.release(b)
                            stats.retirements.append((stats.steps - 1,
                                                      req.rid, reason))
                if n:
                    with _span("serve.sync"):
                        pf_np = np.asarray(pf_logits, np.float32)
                    complete = []                    # prefill complete
                    for j, b in enumerate(sel):
                        ent = pref[b]
                        ent["done"] += ch_valid[j]
                        pos_h[b] = ent["done"]
                        if ent["done"] >= len(ent["req"].prompt):
                            del pref[b]
                            complete.append((b, ent["req"], pf_np[j]))
                    if complete:
                        with _span("serve.first_token", rids=" ".join(
                                str(r.rid) for _, r, _ in complete)):
                            for b, req, row in complete:
                                if paged:
                                    # the prompt is fully in cache: index
                                    # its full blocks so later prompts
                                    # sharing the prefix skip those chunks
                                    pool.register(b, req.prompt, step_i)
                                self._admit(req, b, row, slots, pos_h, last)
                                if paged and slots[b] is None:
                                    pool.release(b)  # admitted-and-retired
                for b, req in reserved:
                    # the retiree's final decode ran this step (and, paged,
                    # its blocks were just released) — claim now, chunk
                    # next step
                    claim(b, req, stats.steps)
        if paged:
            stats.evictions = pool.evictions - pool_base[0]
            stats.prefix_hits = pool.prefix_hits - pool_base[1]
            stats.prefix_tokens_reused = (pool.prefix_tokens_reused
                                          - pool_base[2])
        return requests

    def _run_continuous_plain(self, requests, waiting) -> list[Request]:
        """Fallback continuous batching (hand-wired decode): every step
        decodes all active slots, retires finished slots, and refills EVERY
        free slot from the arrival queue — lowest free slot first, arrival
        order first (deterministic refill given a fixed arrival queue).
        Whole prompts prefill alongside the decode in the same iteration; a
        slot whose request retires deterministically this step (budget /
        cache-full) refills in that same iteration."""
        B = self.batch
        stats = self.stats
        slots: list[Optional[Request]] = [None] * B
        pos_h = [0] * B                               # host mirror of pos
        last = np.zeros(B, np.int32)
        cache = self._init_slot_cache()

        while waiting or any(s is not None for s in slots):
            step_i = stats.steps
            # a slot is refillable when empty OR when its request retires
            # *deterministically* this very step (budget / cache-full): the
            # retiring slot's last decode reads the cache before the
            # refill's prefill rows land, so the new prompt co-prefills in
            # the same iteration (EOS retirements are not predictable;
            # those slots refill one step later)
            free = [i for i, s in enumerate(slots)
                    if s is None or self._will_retire_this_step(s, pos_h[i])]
            arrived = [r for r in waiting if r.arrival <= step_i]
            refills = list(zip(free, arrived))
            for _slot, r in refills:
                waiting.remove(r)
            active = np.array([s is not None for s in slots])
            n_active = int(active.sum())

            if n_active == 0:
                stats.steps += 1
                if not refills:
                    continue                          # idle: future arrivals
                stats.prefill_only_steps += 1
                for slot, req in refills:
                    cache, pf_logits = self._cb_refill(cache, slot,
                                                       req.prompt)
                    self._admit(req, slot, pf_logits, slots, pos_h, last)
                continue

            logits, cache = self._cb_plain_decode()(
                self.params, cache, jnp.asarray(last), jnp.asarray(active))
            extra_logits = []
            for slot, req in refills:     # side-by-side prefills
                cache, pf_logits = self._cb_refill(cache, slot, req.prompt)
                extra_logits.append(pf_logits)
            stats.steps += 1
            stats.decode_steps += 1
            stats.slot_steps += n_active
            if refills:
                stats.mixed_steps += 1

            logits_np = np.asarray(logits, np.float32)
            for b in range(B):
                req = slots[b]
                if req is None:
                    continue
                pos_h[b] += 1
                tok = self._sample(logits_np[b], req)
                req.out_tokens.append(tok)
                stats.tokens += 1
                last[b] = tok
                reason = self._retire_reason(req, tok, len(req.out_tokens),
                                             pos_h[b])
                if reason:
                    req.done = True
                    slots[b] = None
                    stats.retirements.append((stats.steps - 1, req.rid,
                                              reason))
            for (slot, req), pf_logits in zip(refills, extra_logits):
                self._admit(req, slot, pf_logits, slots, pos_h, last)
        return requests

    # ------------------------------------------------------------------
    def _run_wavefront(self, requests: list[Request]) -> list[Request]:
        # group by prompt length: one wave = one (length, <=batch) group
        by_len: dict[int, list[Request]] = {}
        for r in requests:
            by_len.setdefault(len(r.prompt), []).append(r)
        pending: list[list[Request]] = []
        for _, group in sorted(by_len.items()):
            for i in range(0, len(group), self.batch):
                pending.append(group[i: i + self.batch])
        carried = None              # (cache, logits) co-prefilled for pending[0]
        while pending:
            wave = pending.pop(0)
            if carried is not None:
                cache, last_logits = carried
                carried = None
            else:
                cache, last_logits = self._prefill_wave(wave)
            logits = np.asarray(last_logits, np.float32)
            for i, r in enumerate(wave):
                r.out_tokens.append(self._sample(logits[i], r))
            budget = max(r.max_new_tokens for r in wave)
            for step_i in range(budget - 1):
                if all(r.done or len(r.out_tokens) >= r.max_new_tokens
                       for r in wave):
                    break
                toks = np.zeros((self.batch,), np.int32)
                for i, r in enumerate(wave):
                    toks[i] = r.out_tokens[-1]
                if (self.executed and step_i == 0 and pending
                        and carried is None):
                    # chunked prefill⊕decode co-execution: the next wave's
                    # prompt FFN rides in this step's fused launch
                    nxt = pending[0]
                    out, cache, pf_cache, pf_logits = self._mixed_step(
                        len(nxt[0].prompt))(
                            self.params, cache, jnp.asarray(toks),
                            jnp.asarray(self._wave_tokens(nxt)))
                    carried = (pf_cache, pf_logits)
                else:
                    out, cache = self._decode(self.params, cache,
                                              jnp.asarray(toks))
                logits = np.asarray(out, np.float32)
                for i, r in enumerate(wave):
                    if r.done or len(r.out_tokens) >= r.max_new_tokens:
                        continue
                    tok = self._sample(logits[i], r)
                    r.out_tokens.append(tok)
                    if r.eos_token is not None and tok == r.eos_token:
                        r.done = True
            for r in wave:
                r.done = True
        return requests
