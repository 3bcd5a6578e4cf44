"""The stacked form of decode attention (``stacked_layers=L``): k/v are the
whole layer-stacked cache ``(L, B, S, Hkv * D)`` left in HBM, a ``(1, 1)``
"layer" operand picks the layer, and the body double-buffers its own block
copies.  It must be BITWISE equal to the per-layer form on
``cache[l].reshape(B, S, Hkv, D)`` for every layer, alone and inside a
fused launch beside a prefill chunk, where the fused grid interleaves its
steps with the chunk's (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import hfuse
from repro.core.cost_model import Schedule
from repro.core.op_spec import DMA_SEMAPHORE, hbm_operand, vmem_bytes_of
from repro.kernels.decode_attention import decode_attention_op
from repro.kernels.prefill_attention import prefill_attention_op

L, B, S, H, Hkv, D, CK, C = 3, 2, 64, 4, 2, 16, 16, 8
LENS = np.array([[5], [40]], np.int32)        # one slot mid-chunk, one late


def _data(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(bf)
    k = jax.random.normal(ks[1], (L, B, S, Hkv * D), jnp.float32).astype(bf)
    v = jax.random.normal(ks[2], (L, B, S, Hkv * D), jnp.float32).astype(bf)
    pq = jax.random.normal(ks[3], (H, C, D), jnp.float32).astype(bf)
    return q, k, v, pq


def _layer(x, l):
    """Layer ``l`` of a flat stacked cache, heads unflattened."""
    return x[l].reshape(B, S, Hkv, D)


def _same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _layer_arg(l):
    return jnp.asarray([[l]], jnp.int32)


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("lengths", ["dynamic", "static", "full"])
def test_stacked_equals_per_layer_bitwise(layer, lengths):
    q, k, v, _ = _data()
    kw = {"dynamic": dict(dynamic_length=True),
          "static": dict(length=37), "full": {}}[lengths]
    per = decode_attention_op(B, S, H, Hkv, D, ck=CK, **kw)
    st = decode_attention_op(B, S, H, Hkv, D, ck=CK, stacked_layers=L, **kw)
    lens = (jnp.asarray(LENS),) if lengths == "dynamic" else ()
    want = hfuse.run_single(per, interpret=True)(
        *lens, q, _layer(k, layer), _layer(v, layer))
    got = hfuse.run_single(st, interpret=True)(
        _layer_arg(layer), *lens, q, k, v)
    _same(got, want)


@pytest.mark.parametrize("ratios", [(1, 1), (3, 1), (1, 2), (8, 4)])
def test_stacked_in_fused_launch_beside_prefill_chunk(ratios):
    """Interleaved with a chunk's steps, the copies started for the next
    step of attention land before that step reads them."""
    q, k, v, pq = _data(1)
    layer, off = 1, jnp.asarray([[16]], jnp.int32)
    st = decode_attention_op(B, S, H, Hkv, D, ck=CK, dynamic_length=True,
                             stacked_layers=L)
    per = decode_attention_op(B, S, H, Hkv, D, ck=CK, dynamic_length=True)
    pf = prefill_attention_op(C, S, H, Hkv, D, ck=CK)
    slot_k = _layer(k, layer)[1]
    slot_v = _layer(v, layer)[1]
    fused = hfuse.generate((st, pf), Schedule(ratios), interpret=True)
    got = fused(_layer_arg(layer), jnp.asarray(LENS), q, k, v,
                off, pq, slot_k, slot_v)
    want = (hfuse.run_single(per, interpret=True)(
        jnp.asarray(LENS), q, _layer(k, layer), _layer(v, layer))
        + hfuse.run_single(pf, interpret=True)(off, pq, slot_k, slot_v))
    _same(got, want)


def test_stacked_operands_and_costs():
    """The cache stays in HBM and costs no VMEM: the two-slot landing
    buffers are the op's scratch.  Work and traffic are the per-layer
    form's valid-prefix counts."""
    per = decode_attention_op(B, S, H, Hkv, D, ck=CK, dynamic_length=True)
    st = decode_attention_op(B, S, H, Hkv, D, ck=CK, dynamic_length=True,
                             stacked_layers=L)
    assert st.name == per.name + f"_L{L}"
    assert st.in_names == ("layer", "len", "q", "k", "v")
    k_op = st.inputs[st.in_names.index("k")]
    assert k_op.hbm and k_op.shape == (L, B, S, Hkv * D)
    assert k_op.block_bytes() == 0
    assert st.scratch[-1] == ((2, 2), DMA_SEMAPHORE)
    assert (st.flops, st.hbm_bytes, st.grid) == \
        (per.flops, per.hbm_bytes, per.grid)
    spec = hfuse._block_spec(hbm_operand((4, 128), jnp.bfloat16), None)
    assert spec.memory_space == pl.ANY
    with pytest.raises(AssertionError):
        decode_attention_op(B, S, H, Hkv, D, ck=CK, stacked_layers=L,
                            block_table=(8, 8))
    # the landing buffers replace the per-layer k/v blocks in the count
    assert st.vmem_bytes - per.vmem_bytes == \
        2 * vmem_bytes_of((2, CK, Hkv * D), jnp.bfloat16) \
        - 2 * vmem_bytes_of((1, CK, Hkv, D), jnp.bfloat16)
