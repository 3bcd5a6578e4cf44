"""Chunked flash-attention prefill Pallas kernel: one prompt chunk of ONE
slot attending that slot's KV cache.

The serving engine admits prompts of any length by chipping them away one
chunk per iteration (docs/serving.md §Chunked prefill): the chunk's C query
rows land in the cache *before* the launch, then this kernel runs full
causal attention of those rows against the slot's whole cache — the rows
[0, off) it prefilled on earlier iterations plus the chunk itself.  At real
scale the op is compute-bound (O(C) flops per cache byte streamed), which
makes it the paper's canonical partner for the memory-bound decode
attention that shares the launch: N of these chunks (different slots) ⊕ the
vectorized decode kernel form ONE fused bundle (ServeEngine.decode_graph).

Fusible form mirrors kernels/decode_attention.py: a 1-D grid over kv
chunks, per-KV-head 2-D matmuls, the output block as the running
accumulator and the online-softmax (m, l) statistics in per-op VMEM scratch
(``OpSpec.scratch``, which core/hfuse.generate allocates per bundle
member).  The chunk's start position arrives as a (1, 1) int32 operand
("off", in scalar memory), so one compiled kernel serves every chunk of
every prompt.

Causal chunk masking against the existing cache: query row r (absolute
position off + r) admits cache position p iff p <= off + r.  That single
predicate covers all three row classes: the already-prefilled prefix
(p < off: always admitted), the chunk itself (causal within the chunk), and
everything beyond (garbage rows the engine has not written yet: masked).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.op_spec import MIN_BLOCK_ROWS, OpSpec, Operand, smem_operand
from repro.kernels.decode_attention import NEG_INF, attend_group, gather_pages

LANES = 128


def prefill_attention_op(C: int, S: int, H: int, Hkv: int, D: int,
                         dtype=jnp.bfloat16, ck: int = 1024,
                         name: str | None = None,
                         block_table=None) -> OpSpec:
    """q: (H,C,D) one chunk of one slot, head-major; cache k,v: (S,Hkv,D);
    off: (1,1) int32 absolute start position of the chunk; out o: (H,C,D)
    fp32.  Head-major q/o make every head a leading-axis index, so the
    kernel loops over a KV head's query heads instead of unrolling them.

    Grid: S // ck kv-chunk steps.  The engine scatters the chunk's own k/v
    into rows [off, off+C) before the launch, so the kernel only ever reads
    the cache — there is no in-kernel write ordering to get wrong, and the
    same (S,Hkv,D) operand contract as decode attention lets the executor
    bind both kernels to the same cache leaves in one fused launch.

    Tuned variants rebuild through the ``shrink`` factory (smaller ``ck``,
    proportionally larger grid) rather than ``op_spec.shrink_blocks`` — the
    body closes over the kv-chunk count, so a structural block rewrite
    would silently break the online-softmax recurrence.

    ``block_table=(num_blocks, block_size)``: paged form, mirroring
    kernels/decode_attention.py — k/v are the shared arena, ``S`` is the
    slot's logical capacity, and a ``(1, max_blocks)`` int32 operand ("bt",
    this slot's table row, in scalar memory like "off") maps logical pages
    to arena blocks for the in-body gather.  The reassembled ``(ck, D)``
    head block feeds math identical to the contiguous body, so both forms
    are bitwise-equal on equal logical cache content.
    """
    assert S % ck == 0 and H % Hkv == 0
    nk = S // ck
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    paged = block_table is not None
    if paged:
        num_blocks, bs = block_table
        assert ck % bs == 0 and S % bs == 0
        max_blocks = S // bs
        npc = ck // bs                       # pages per kv-chunk
    resolved = name or (f"prefill_attn_C{C}_S{S}_H{H}kv{Hkv}"
                        + (f"_pg{bs}" if paged else ""))

    def body(step, off_ref, *refs):
        if paged:
            bt_ref, refs = refs[0], refs[1:]
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        j = step                                           # kv-chunk index

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            o_ref[...] = jnp.zeros_like(o_ref)

        kpos = j * ck + jax.lax.broadcasted_iota(jnp.int32, (C, ck), 1)
        qpos = off_ref[0, 0] + jax.lax.broadcasted_iota(jnp.int32, (C, ck), 0)
        keep = kpos <= qpos
        for g in range(Hkv):
            if paged:
                k = gather_pages(k_ref, bt_ref, 0, j * npc, npc, g)
                v = gather_pages(v_ref, bt_ref, 0, j * npc, npc, g)
            else:
                k, v = k_ref[:, g, :], v_ref[:, g, :]        # (ck, D)

            def head(h, carry, k=k, v=v):
                qh = q_ref[h].astype(jnp.float32) * scale       # (C, D)
                m, l, acc = attend_group(qh, k, v, keep, m_ref[h][:, :1],
                                         l_ref[h][:, :1], o_ref[h])
                m_ref[h] = jnp.broadcast_to(m, (C, LANES))
                l_ref[h] = jnp.broadcast_to(l, (C, LANES))
                o_ref[h] = acc
                return carry

            jax.lax.fori_loop(g * rep, (g + 1) * rep, head, 0)

        @pl.when(j == nk - 1)
        def _():
            o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...][..., :1], 1e-30)

    def shrink(factor: int):
        sck = ck // factor
        if ck % factor or sck < MIN_BLOCK_ROWS or (paged and sck % bs):
            return None
        return prefill_attention_op(C, S, H, Hkv, D, dtype=dtype,
                                    ck=sck, name=resolved,
                                    block_table=block_table)

    if paged:
        bt_in = (smem_operand((1, max_blocks)),)
        kv = (Operand((num_blocks, bs, Hkv, D), dtype,
                      (num_blocks, bs, Hkv, D), lambda s: (0, 0, 0, 0)),
              Operand((num_blocks, bs, Hkv, D), dtype,
                      (num_blocks, bs, Hkv, D), lambda s: (0, 0, 0, 0)))
        bt_name = ("bt",)
    else:
        kv = (Operand((S, Hkv, D), dtype, (ck, Hkv, D),
                      lambda s: (s, 0, 0)),
              Operand((S, Hkv, D), dtype, (ck, Hkv, D),
                      lambda s: (s, 0, 0)))
        bt_in, bt_name = (), ()

    itemsize = jnp.dtype(dtype).itemsize
    return OpSpec(
        name=resolved, grid=nk, body=body,
        inputs=(smem_operand((1, 1)),)
        + bt_in
        + (Operand((H, C, D), dtype, (H, C, D), lambda s: (0, 0, 0)),)
        + kv,
        outputs=(Operand((H, C, D), jnp.float32, (H, C, D),
                         lambda s: (0, 0, 0)),),
        # running max / denominator per (head, row), replicated over the
        # 128 lanes a (C, 1) column would occupy anyway
        scratch=(((H, C, LANES), jnp.float32),) * 2,
        flops=2.0 * C * H * S * D * 2,
        hbm_bytes=2.0 * S * Hkv * D * itemsize
        + C * H * D * (itemsize + 4.0) + 4.0 * C * H * 2,
        shrink=shrink,
        tag="framework:prefill_attention",
        # one head's fp32 score/probability tiles (C, ck) live at once
        extra_vmem_bytes=2 * C * ck * 4,
        in_names=("off",) + bt_name + ("q", "k", "v"),
        out_names=("o",))
