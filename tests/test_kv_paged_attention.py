"""Property test for the paged attention kernels: decode and prefill
attention with ``block_table=`` are BITWISE equal to their contiguous
forms when the arena holds the same logical cache content — under random
block permutations (pages scattered anywhere in the arena, any order) and
under ``shrink`` variants (smaller kv-chunks, the autotuner's search
moves).  The page gather (kernels/decode_attention.gather_pages)
reassembles exactly the contiguous kernel's ``(ck, Hkv, D)`` block, so
the math is the same fp32 op sequence — equality is exact, not approx.
Deterministic engine-level coverage lives in tests/test_serve_paged.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (see "
                           "requirements.txt); deterministic paged parity "
                           "cases live in tests/test_serve_paged.py")
from hypothesis import given, settings, strategies as st

from repro.core import hfuse
from repro.kernels.decode_attention import decode_attention_op
from repro.kernels.prefill_attention import prefill_attention_op

H, Hkv, D = 4, 2, 8
BS = 16                                    # arena block size (tokens)


def _paged_cache(key, B, S, num_blocks, seed_tables):
    """Contiguous (B, S, Hkv, D) k/v plus an arena + tables holding the
    SAME logical content with pages randomly placed: block b of slot s
    lives at arena row tables[s, b], a random permutation draw."""
    kc, vc = (jax.random.normal(k, (B, S, Hkv, D), jnp.float32)
              for k in jax.random.split(key, 2))
    nper = S // BS
    rng = np.random.default_rng(seed_tables)
    tables = rng.permutation(num_blocks)[:B * nper].reshape(B, nper)
    ka = np.zeros((num_blocks, BS, Hkv, D), np.float32)
    va = np.zeros((num_blocks, BS, Hkv, D), np.float32)
    kn, vn = np.asarray(kc), np.asarray(vc)
    for b in range(B):
        for p in range(nper):
            ka[tables[b, p]] = kn[b, p * BS:(p + 1) * BS]
            va[tables[b, p]] = vn[b, p * BS:(p + 1) * BS]
    return (kc, vc, jnp.asarray(ka), jnp.asarray(va),
            jnp.asarray(tables.astype(np.int32)))


@settings(deadline=None, max_examples=10)
@given(B=st.integers(1, 3), nck=st.sampled_from([1, 2, 4]),
       shrink=st.sampled_from([None, 2]),
       seed=st.integers(0, 2 ** 16))
def test_paged_decode_bitwise_equals_contiguous(B, nck, shrink, seed):
    S = 64
    ck = S // nck
    num_blocks = B * (S // BS) + 3         # slack: unused arena rows stay 0
    key = jax.random.PRNGKey(seed)
    kq, kkv = jax.random.split(key)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    kc, vc, ka, va, bt = _paged_cache(kkv, B, S, num_blocks, seed)
    lens = jnp.asarray(
        np.random.default_rng(seed + 1).integers(1, S + 1, (B, 1)),
        jnp.int32)
    paged = decode_attention_op(B, S, H, Hkv, D, dtype=jnp.float32, ck=ck,
                                dynamic_length=True,
                                block_table=(num_blocks, BS))
    if shrink:
        paged = paged.shrink(shrink)
        if paged is None:                  # shrunk ck below the block size
            return
        ck //= shrink
    # bitwise equality needs the SAME kv-chunk sequence (online-softmax
    # rounding depends on ck), so the reference is built at the final ck
    base = decode_attention_op(B, S, H, Hkv, D, dtype=jnp.float32, ck=ck,
                               dynamic_length=True)
    o_ref, *_ = hfuse.run_single(base, interpret=True)(lens, q, kc, vc)
    o_pg, *_ = hfuse.run_single(paged, interpret=True)(bt, lens, q, ka, va)
    assert np.array_equal(np.asarray(o_pg), np.asarray(o_ref))


@settings(deadline=None, max_examples=10)
@given(C=st.sampled_from([8, 16]), nck=st.sampled_from([1, 2, 4]),
       shrink=st.sampled_from([None, 2]),
       seed=st.integers(0, 2 ** 16))
def test_paged_prefill_bitwise_equals_contiguous(C, nck, shrink, seed):
    S = 64
    ck = S // nck
    num_blocks = S // BS + 2
    key = jax.random.PRNGKey(seed)
    kq, kkv = jax.random.split(key)
    q = jax.random.normal(kq, (H, C, D), jnp.float32)         # head-major
    kc, vc, ka, va, bt = _paged_cache(kkv, 1, S, num_blocks, seed)
    off = jnp.full((1, 1),
                   int(np.random.default_rng(seed + 1).integers(0, S - C + 1)),
                   jnp.int32)
    paged = prefill_attention_op(C, S, H, Hkv, D, dtype=jnp.float32, ck=ck,
                                 block_table=(num_blocks, BS))
    if shrink:
        paged = paged.shrink(shrink)
        if paged is None:
            return
        ck //= shrink
    base = prefill_attention_op(C, S, H, Hkv, D, dtype=jnp.float32, ck=ck)
    o_ref, *_ = hfuse.run_single(base, interpret=True)(
        off, q, kc[0], vc[0])
    o_pg, *_ = hfuse.run_single(paged, interpret=True)(off, bt, q, ka, va)
    assert np.array_equal(np.asarray(o_pg), np.asarray(o_ref))
