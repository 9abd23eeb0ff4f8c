"""Batched GQA/MQA decode + single-pass blockwise prefill vs the naive
two-pass oracle; parity across decode impls (tokenwise / blockwise / kernel)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as attn
from repro.kernels.swiftkv_decode.ref import swiftkv_decode_ref

RNG = np.random.default_rng(0)


def mk(b=2, hq=4, hkv=2, s=96, d=32, dtype=jnp.float32):
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), dtype)
    lengths = jnp.asarray(RNG.integers(1, s + 1, (b,)), jnp.int32)
    return q, k, v, lengths


@pytest.mark.parametrize("impl", ["tokenwise", "blockwise", "kernel", "naive"])
def test_decode_impl_parity(impl):
    q, k, v, lengths = mk()
    # the kernel runs in the Pallas interpreter here, asked for explicitly
    got = attn.decode_attention(q, k, v, lengths, impl=impl, block_size=32,
                                interpret=(impl == "kernel"))
    want = swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_kernel_impl_needs_tpu_or_explicit_interpret():
    """Off the TPU the compiled kernel cannot run, and the Pallas
    interpreter runs only when the caller asks for it: no silent
    fallback."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernel runs here")
    q, k, v, lengths = mk()
    with pytest.raises(ValueError, match="interpret=True"):
        attn.decode_attention(q, k, v, lengths, impl="kernel", block_size=32)


@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (8, 1)])  # MHA/GQA/MQA
def test_decode_head_layouts(hq, hkv):
    q, k, v, lengths = mk(hq=hq, hkv=hkv)
    got = attn.decode_attention(q, k, v, lengths, impl="blockwise",
                                block_size=32)
    want = swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_decode_window():
    q, k, v, lengths = mk(s=128)
    got = attn.decode_attention(q, k, v, lengths, impl="blockwise",
                                window=40, block_size=32)
    want = swiftkv_decode_ref(q, k, v, lengths, window=40)
    np.testing.assert_allclose(got, want, atol=2e-5)


def _naive_prefill(q, k, v, *, causal, window=None, kv_len=None):
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = jnp.swapaxes(q, 1, 2).reshape(b, hkv, g, sq, d).astype(jnp.float32)
    kc = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vc = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kc) / np.sqrt(d)
    pos_q = jnp.arange(sq)[:, None]
    pos_k = jnp.arange(skv)[None, :]
    valid = jnp.ones((sq, skv), bool)
    if causal:
        valid &= pos_k <= pos_q
    if window is not None:
        valid &= pos_k > pos_q - window
    if kv_len is not None:
        valid &= pos_k < kv_len
    s = jnp.where(valid[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vc)
    return jnp.swapaxes(out.reshape(b, hq, sq, d), 1, 2).astype(q.dtype)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 24),
                                           (False, None)])
def test_prefill_blockwise_vs_naive(causal, window):
    b, sq, hq, hkv, d = 2, 64, 4, 2, 32
    q = jnp.asarray(RNG.standard_normal((b, sq, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, sq, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, sq, hkv, d)), jnp.float32)
    got = attn.prefill_attention(q, k, v, causal=causal, window=window,
                                 kv_block=16)
    want = _naive_prefill(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_prefill_cross_attention_kv_length():
    """Cross-attn: non-causal with a padded KV prefix (stub frontend)."""
    b, sq, skv, h, d = 2, 16, 40, 4, 16
    q = jnp.asarray(RNG.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, skv, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, skv, h, d)), jnp.float32)
    kv_len = 25
    got = attn.prefill_attention(
        q, k, v, causal=False, kv_lengths=jnp.full((b,), kv_len, jnp.int32),
        kv_block=16)
    want = _naive_prefill(q, k, v, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(got, want, atol=3e-5)


def test_bf16_decode_stays_close():
    q, k, v, lengths = mk(dtype=jnp.bfloat16, s=64)
    got = attn.decode_attention(q, k, v, lengths, impl="blockwise",
                                block_size=32).astype(jnp.float32)
    want = swiftkv_decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), lengths)
    np.testing.assert_allclose(got, want, atol=3e-2)
