"""Decode attention Pallas kernel: one new token vs a long KV cache.

Memory-bound par excellence (streams the whole cache, does O(D) flops per
byte) — the framework's Ethash: the canonical horizontal-fusion partner for
compute-bound matmuls in the dual-stream decode mode (serve/dual_stream.py).

Fusible form: 1-D grid over (batch, kv-chunk) linearized; the online-softmax
(m, l) carries live in small fp32 *outputs* with constant index maps (not
scratch) so the op composes under core/hfuse.generate.

Paged form (``block_table=(num_blocks, block_size)``): the k/v operands are
a flat block arena ``(num_blocks, block_size, Hkv, D)`` shared by every
slot, and a per-slot block table rides as one more small int32 operand
("bt", ``(B, max_blocks)``, in scalar memory like "len").  Each kv-chunk
step gathers its ``ck // block_size`` pages from the arena by table lookup
— the memory-intensive indirection the serve engine pairs with
compute-bound GEMMs in one fused launch (serve/kv_pool.py owns the arena).
The page gather reassembles exactly the contiguous kernel's ``(ck, Hkv,
D)`` block, so paged and contiguous attention are BITWISE equal for equal
logical cache content (tests/test_kv_paged_attention.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.op_spec import MIN_BLOCK_ROWS, OpSpec, Operand, smem_operand

NEG_INF = -1e30


def gather_pages(ref, bt_ref, row, first_page, npages: int, g: int):
    """KV head ``g`` of one (npages * block_size, D) kv-chunk, assembled
    from the arena ``ref`` by looking pages ``first_page ..`` up in row
    ``row`` of the SMEM block table ``bt_ref``.  ``row`` and ``first_page``
    may be traced scalars; ``npages`` and ``g`` are static."""
    pages = [ref[pl.ds(bt_ref[row, first_page + p], 1), :, g, :][0]
             for p in range(npages)]
    return pages[0] if npages == 1 else jnp.concatenate(pages, axis=0)


def attend_group(qg, k, v, keep, m_prev, l_prev, acc):
    """One online-softmax update for one KV head: ``qg`` (R, D) fp32
    pre-scaled queries sharing that head, ``k``/``v`` (ck, D), ``keep``
    (R, ck) bool mask, carries ``m_prev``/``l_prev`` (R, 1) and ``acc``
    (R, D).  Returns the new (m, l, acc).  Plain 2-D matmuls, so the
    compiled kernel needs no 4-D contraction or sublane reshape."""
    s = jax.lax.dot_general(qg, k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (R, ck)
    s = jnp.where(keep, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    pv = jnp.dot(p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    return m_new, l_prev * alpha + p.sum(-1, keepdims=True), \
        acc * alpha + pv


def decode_attention_op(B: int, S: int, H: int, Hkv: int, D: int,
                        dtype=jnp.bfloat16, ck: int = 1024,
                        length=None, dynamic_length: bool = False,
                        block_table=None) -> OpSpec:
    """q: (B,H,D); cache k,v: (B,S,Hkv,D); out o: (B,H,D) fp32.

    Grid: B * (S // ck) steps, batch-major.  `length` (static) masks the
    valid cache prefix; None = full cache.  ``dynamic_length`` instead adds
    a tiny (B, 1) int32 operand ("len", in scalar memory, read at the
    step's slot) holding each slot's valid prefix, so one compiled kernel
    serves every decode position of every slot independently — the form
    the executor binds to a live per-slot ``pos + 1`` vector (continuous
    batching: slots advance, finish and refill at unrelated cache positions
    within one launch).

    ``block_table=(num_blocks, block_size)`` switches to the paged form:
    k/v become the shared ``(num_blocks, block_size, Hkv, D)`` arena
    (constant index map — the gather is in-body, since fused index maps are
    pure functions of the grid step), ``S`` becomes the per-slot LOGICAL
    capacity (``max_blocks = S // block_size`` table columns), and a
    ``(B, max_blocks)`` int32 operand ("bt", in scalar memory) maps each
    slot's logical pages to arena blocks.  Requires ``ck % block_size ==
    0`` so every kv-chunk is a whole number of pages.
    """
    assert S % ck == 0 and H % Hkv == 0
    assert not (dynamic_length and length is not None)
    nk = S // ck
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    valid_len = S if length is None else int(length)
    paged = block_table is not None
    if paged:
        num_blocks, bs = block_table
        assert ck % bs == 0 and S % bs == 0
        max_blocks = S // bs
        npc = ck // bs                       # pages per kv-chunk

    def body(step, *refs):
        if paged:
            bt_ref, refs = refs[0], refs[1:]
        b, j = step // nk, step % nk
        if dynamic_length:
            len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
            cur_len = len_ref[b, 0]
        else:
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
            cur_len = valid_len

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            o_ref[...] = jnp.zeros_like(o_ref)

        kpos = j * ck + jax.lax.broadcasted_iota(jnp.int32, (rep, ck), 1)
        keep = kpos < cur_len
        for g in range(Hkv):                 # query heads g*rep .. +rep
            rows = slice(g * rep, (g + 1) * rep)
            if paged:
                k = gather_pages(k_ref, bt_ref, b, j * npc, npc, g)
                v = gather_pages(v_ref, bt_ref, b, j * npc, npc, g)
            else:
                k, v = k_ref[0, :, g, :], v_ref[0, :, g, :]     # (ck, D)
            qg = q_ref[0, rows, :].astype(jnp.float32) * scale  # (rep, D)
            m, l, acc = attend_group(qg, k, v, keep, m_ref[0, rows, :],
                                     l_ref[0, rows, :], o_ref[0, rows, :])
            m_ref[0, rows, :] = m
            l_ref[0, rows, :] = l
            o_ref[0, rows, :] = acc

        @pl.when(j == nk - 1)
        def _():
            o_ref[0] = o_ref[0] / jnp.maximum(l_ref[0], 1e-30)

    itemsize = jnp.dtype(dtype).itemsize
    len_in = (smem_operand((B, 1)),) if dynamic_length else ()
    if paged:
        bt_in = (smem_operand((B, max_blocks)),)
        kv = (Operand((num_blocks, bs, Hkv, D), dtype,
                      (num_blocks, bs, Hkv, D), lambda s: (0, 0, 0, 0)),
              Operand((num_blocks, bs, Hkv, D), dtype,
                      (num_blocks, bs, Hkv, D), lambda s: (0, 0, 0, 0)))
        suffix, bt_name = f"_pg{bs}", ("bt",)

        def shrink(factor: int):
            sck = ck // factor
            if ck % factor or sck % bs or sck < MIN_BLOCK_ROWS:
                return None
            return decode_attention_op(B, S, H, Hkv, D, dtype=dtype, ck=sck,
                                       length=length,
                                       dynamic_length=dynamic_length,
                                       block_table=block_table)
    else:
        kv = (Operand((B, S, Hkv, D), dtype, (1, ck, Hkv, D),
                      lambda s: (s // nk, s % nk, 0, 0)),
              Operand((B, S, Hkv, D), dtype, (1, ck, Hkv, D),
                      lambda s: (s // nk, s % nk, 0, 0)))
        bt_in, suffix, bt_name, shrink = (), "", (), None
    return OpSpec(
        name=f"decode_attn_B{B}_S{S}_H{H}kv{Hkv}{suffix}",
        grid=B * nk, body=body,
        inputs=bt_in + len_in
        + (Operand((B, H, D), dtype, (1, H, D), lambda s: (s // nk, 0, 0)),)
        + kv,
        outputs=(Operand((B, H, D), jnp.float32, (1, H, D),
                         lambda s: (s // nk, 0, 0)),
                 Operand((B, H, 1), jnp.float32, (1, H, 1),
                         lambda s: (s // nk, 0, 0)),
                 Operand((B, H, 1), jnp.float32, (1, H, 1),
                         lambda s: (s // nk, 0, 0))),
        flops=2.0 * B * H * valid_len * D * 2,
        hbm_bytes=2.0 * B * valid_len * Hkv * D * itemsize
        + 2.0 * B * H * D * itemsize,
        shrink=shrink,
        tag="framework:decode_attention",
        # one KV head's fp32 score/probability tiles (rep, ck) live at once
        extra_vmem_bytes=2 * rep * ck * 4,
        in_names=bt_name + (("len",) if dynamic_length else ())
        + ("q", "k", "v"),
        out_names=("o", "m", "l"))
