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

Stacked form (``stacked_layers=L``): the k/v operands are the whole
layer-stacked cache with heads flattened into rows, ``(L, B, S, Hkv * D)``,
left in HBM (``Operand.hbm``), and a ``(1, 1)`` int32 operand ("layer", in
scalar memory) says which layer to read.  The body copies each step's
``(ck, Hkv * D)`` k and v rows itself into two-slot VMEM scratch, starting
the copy for its next step before it computes this one — the double
buffering BlockSpec pipelining gives the other forms — and reads head
``g`` as lanes ``[g * D, (g + 1) * D)``.  The flat rows are what keep the
cache where it lies: on a TPU, XLA lays a bf16 ``(.., S, Hkv, D)`` array
with ``D = 64`` out sequence-minor, which a kernel cannot read without a
relayout copy, while ``(.., S, Hkv * D)`` rows are laid out exactly as
the kernel reads them, unpadded.  The serve engine's layer scan then hands attention the
cache it carries, with no per-layer slice.  Per head the blocks and the
math are the contiguous form's, so both are BITWISE equal on
``cache[l].reshape(B, S, Hkv, D)`` (tests/test_decode_attention_stacked.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.op_spec import (DMA_SEMAPHORE, MIN_BLOCK_ROWS, OpSpec,
                                Operand, hbm_operand, smem_operand)

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
                        block_table=None, stacked_layers=None) -> OpSpec:
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

    ``stacked_layers=L`` switches to the stacked form: k/v are the whole
    ``(L, B, S, Hkv * D)`` cache in HBM and a ``(1, 1)`` int32 operand
    ("layer", in scalar memory, ahead of "len") picks the layer; the body
    double-buffers its own block copies (module docstring).
    """
    assert S % ck == 0 and H % Hkv == 0
    assert not (dynamic_length and length is not None)
    nk = S // ck
    grid = B * nk
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    valid_len = S if length is None else int(length)
    paged = block_table is not None
    stacked = stacked_layers is not None
    assert not (paged and stacked)
    if paged:
        num_blocks, bs = block_table
        assert ck % bs == 0 and S % bs == 0
        max_blocks = S // bs
        npc = ck // bs                       # pages per kv-chunk

    def body(step, *refs):
        refs = list(refs)
        bt_ref = refs.pop(0) if paged else None
        layer_ref = refs.pop(0) if stacked else None
        len_ref = refs.pop(0) if dynamic_length else None
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *scratch = refs
        b, j = step // nk, step % nk
        cur_len = len_ref[b, 0] if dynamic_length else valid_len
        if stacked:
            k_buf, v_buf, sem = scratch
            slot = step % 2

            def copies(s, into):
                """Step ``s``'s k and v row copies into buffer ``into``."""
                rows = pl.ds((s % nk) * ck, ck)
                layer = layer_ref[0, 0]
                return [pltpu.make_async_copy(src.at[layer, s // nk, rows],
                                              buf.at[into], sem.at[i, into])
                        for i, (src, buf) in enumerate(((k_ref, k_buf),
                                                        (v_ref, v_buf)))]

            @pl.when(step == 0)
            def _():
                for c in copies(step, slot):
                    c.start()

            @pl.when(step + 1 < grid)
            def _():
                for c in copies(step + 1, 1 - slot):
                    c.start()

            for c in copies(step, slot):
                c.wait()

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
            elif stacked:
                lanes = slice(g * D, (g + 1) * D)
                k, v = k_buf[slot, :, lanes], v_buf[slot, :, lanes]
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
    elif stacked:
        L = int(stacked_layers)
        kv = (hbm_operand((L, B, S, Hkv * D), dtype),
              hbm_operand((L, B, S, Hkv * D), dtype))
        bt_in, suffix, bt_name, shrink = (), f"_L{L}", (), None
    else:
        kv = (Operand((B, S, Hkv, D), dtype, (1, ck, Hkv, D),
                      lambda s: (s // nk, s % nk, 0, 0)),
              Operand((B, S, Hkv, D), dtype, (1, ck, Hkv, D),
                      lambda s: (s // nk, s % nk, 0, 0)))
        bt_in, suffix, bt_name, shrink = (), "", (), None
    layer_in = (smem_operand((1, 1)),) if stacked else ()
    # two k and two v landing buffers, and one semaphore per copy in flight
    scratch = (((2, ck, Hkv * D), dtype), ((2, ck, Hkv * D), dtype),
               ((2, 2), DMA_SEMAPHORE)) if stacked else ()
    return OpSpec(
        name=f"decode_attn_B{B}_S{S}_H{H}kv{Hkv}{suffix}",
        grid=grid, body=body,
        inputs=bt_in + layer_in + len_in
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
        in_names=bt_name + (("layer",) if stacked else ())
        + (("len",) if dynamic_length else ()) + ("q", "k", "v"),
        out_names=("o", "m", "l"),
        scratch=scratch)
