"""Pallas swiftkv_decode kernel: shape/dtype sweep vs the pure-jnp oracle
(interpret mode on CPU; identical code targets the TPU MXU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.swiftkv_decode import ops, ref

RNG = np.random.default_rng(7)


def mk(b, hq, hkv, s, d, dtype):
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), dtype)
    lengths = jnp.asarray(RNG.integers(1, s + 1, (b,)), jnp.int32)
    return q, k, v, lengths


SWEEP = [
    # b, hq, hkv, s,    d,   block
    (1, 4, 4, 256, 64, 128),    # MHA
    (2, 8, 2, 512, 64, 128),    # GQA 4:1
    (2, 8, 1, 256, 128, 128),   # MQA
    (3, 4, 2, 384, 128, 128),   # non-pow2 batch/seq
    (1, 16, 8, 1024, 64, 256),  # wide
    (1, 2, 2, 128, 256, 128),   # big head_dim (gemma-style)
]


@pytest.mark.parametrize("b,hq,hkv,s,d,blk", SWEEP)
def test_kernel_vs_oracle_f32(b, hq, hkv, s, d, blk):
    q, k, v, lengths = mk(b, hq, hkv, s, d, jnp.float32)
    got = ops.swiftkv_decode(q, k, v, lengths, block_k=blk, interpret=True)
    want = ref.swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("b,hq,hkv,s,d,blk", SWEEP[:3])
def test_kernel_vs_oracle_bf16(b, hq, hkv, s, d, blk):
    q, k, v, lengths = mk(b, hq, hkv, s, d, jnp.bfloat16)
    got = ops.swiftkv_decode(q, k, v, lengths, block_k=blk, interpret=True)
    want = ref.swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=3e-2)


@pytest.mark.parametrize("window", [32, 100, 4096])
def test_kernel_sliding_window(window):
    q, k, v, lengths = mk(2, 4, 2, 512, 64, jnp.float32)
    got = ops.swiftkv_decode(q, k, v, lengths, block_k=128, window=window,
                             interpret=True)
    want = ref.swiftkv_decode_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_kernel_lut_exp_mode():
    """exp_mode='lut' reproduces Eq. 9-10 inside the kernel; the error bound
    follows the paper's 0.00586% LUT error times the softmax conditioning."""
    q, k, v, lengths = mk(2, 4, 2, 256, 64, jnp.float32)
    got = ops.swiftkv_decode(q, k, v, lengths, block_k=128, exp_mode="lut",
                             interpret=True)
    want = ref.swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, atol=5e-4)


def test_kernel_length_edge_cases():
    q, k, v, _ = mk(3, 4, 2, 256, 64, jnp.float32)
    for lens in ([1, 1, 1], [256, 256, 256], [1, 128, 256]):
        lengths = jnp.asarray(lens, jnp.int32)
        got = ops.swiftkv_decode(q, k, v, lengths, block_k=128,
                                 interpret=True)
        want = ref.swiftkv_decode_ref(q, k, v, lengths)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_kernel_block_k_snaps_to_divisor():
    """A non-dividing block_k request snaps down to the largest power-of-two
    divisor of S (640 = 5*128: 512 -> 128) — the cache still streams
    zero-copy in its native layout."""
    q, k, v, lengths = mk(2, 4, 2, 640, 64, jnp.float32)
    got = ops.swiftkv_decode(q, k, v, lengths, block_k=512, interpret=True)
    want = ref.swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_kernel_small_cache_runs_unpadded():
    """S below one lane tile (64) uses block_k = S — no call-time pad."""
    q, k, v, lengths = mk(2, 4, 2, 64, 64, jnp.float32)
    got = ops.swiftkv_decode(q, k, v, lengths, block_k=512, interpret=True)
    want = ref.swiftkv_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("hkv,d,dtype,cap", [
    (8, 80, jnp.bfloat16, 512),     # h2o-danube-1.8b: 16 x 128 bf16 rows
    (8, 128, jnp.int8, 512),        # int8 tiles pad Hkv to 32 sublanes
    (32, 128, jnp.bfloat16, 256),   # MHA: 512 rows would need 16 MiB
    (1, 256, jnp.float32, 256),     # MQA: one head still pads to 8 rows
])
def test_block_k_cap_fits_kv_tiles_in_vmem(hkv, d, dtype, cap):
    """A tile spans every KV head, so block_k is capped where the padded,
    double-buffered K and V tiles would pass 8 MiB of VMEM."""
    assert ops._tile_block_cap(hkv, d, dtype) == cap


def test_kernel_misaligned_cache_raises():
    """The zero-copy contract: a cache whose max_len admits no usable block
    size raises at trace time (allocate block-aligned at init_cache) instead
    of silently paying a whole-cache pad+copy per layer per decode step."""
    q, k, v, lengths = mk(2, 4, 2, 300, 64, jnp.float32)
    with pytest.raises(ValueError, match="block-aligned"):
        ops.swiftkv_decode(q, k, v, lengths, block_k=128, interpret=True)


# ---------------------------------------------------------------------------
# ring caches: rotated layouts consumed in place
# ---------------------------------------------------------------------------

RING = 256          # ring slots (= S fed to the kernel)
RWIN = 100          # SWA window


def _ringify(full: np.ndarray, lengths, r: int) -> jnp.ndarray:
    """Rotate a temporal cache into ring layout: slot s holds the newest
    position congruent to s mod r (zeros where that position is negative,
    i.e. before the row has written slot s)."""
    b, _, hkv, d = full.shape
    ring = np.zeros((b, r, hkv, d), full.dtype)
    for i in range(b):
        p = int(lengths[i]) - 1
        for s in range(r):
            pos = p - ((p - s) % r)
            if pos >= 0:
                ring[i, s] = full[i, pos]
    return jnp.asarray(ring)


# wrap offset: where (lengths mod RING) sits relative to the ring — exactly
# on the boundary, one past it, one short of a block edge, and mid-ring
@pytest.mark.parametrize("wrap_off", [0, 1, 127, 131])
def test_kernel_ring_rotated_cache(wrap_off):
    """The Pallas wrapper consumes a wrapped (rotated) ring cache in place
    and matches the temporal-layout oracle exactly — one wrapped row, one
    unwrapped row, one fresh row per batch."""
    b, hq, hkv, d = 3, 4, 2, 64
    lengths = np.asarray([2 * RING + wrap_off, RING - 37, 1], np.int32)
    L = int(lengths.max())
    kf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    vf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
    kr, vr = _ringify(kf, lengths, RING), _ringify(vf, lengths, RING)
    got = ops.swiftkv_decode(q, kr, vr, jnp.asarray(lengths), window=RWIN,
                             ring=True, block_k=128, interpret=True)
    want = ref.swiftkv_decode_ref(q, jnp.asarray(kf), jnp.asarray(vf),
                                  jnp.asarray(lengths), window=RWIN)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("wrap_off", [0, 1, 127, 131])
def test_blockwise_ring_rotated_cache(wrap_off):
    """swiftkv_decode_blockwise (the TPU-shaped reference the kernel
    mirrors) folds the same rotated ring to the same result through
    decode_attention's ring dispatch."""
    from repro.core import attention as attn
    b, hq, hkv, d = 2, 4, 2, 64
    lengths = np.asarray([2 * RING + wrap_off, RING + wrap_off // 2 + 7],
                         np.int32)
    L = int(lengths.max())
    kf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    vf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
    kr, vr = _ringify(kf, lengths, RING), _ringify(vf, lengths, RING)
    got = attn.decode_attention(q, kr, vr, jnp.asarray(lengths),
                                impl="blockwise", window=RWIN, ring=True,
                                block_size=128)
    want = ref.swiftkv_decode_ref(q, jnp.asarray(kf), jnp.asarray(vf),
                                  jnp.asarray(lengths), window=RWIN)
    np.testing.assert_allclose(got, want, atol=2e-5)


def _flat_primitives(jaxpr, acc: set) -> set:
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                inner = getattr(x, "jaxpr", None)
                if inner is not None:
                    _flat_primitives(inner, acc)
                elif hasattr(x, "eqns"):
                    _flat_primitives(x, acc)
    return acc


def test_kernel_ring_consumed_zero_copy():
    """No silent unrotate: the lowered ring kernel program recovers slot
    positions arithmetically — it must contain no gather / roll / sort /
    scatter of the cache (a host-side unrotation would need one)."""
    q = jnp.zeros((2, 4, 64), jnp.float32)
    kr = jnp.zeros((2, RING, 2, 64), jnp.float32)
    lengths = jnp.asarray([2 * RING + 5, 40], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: ops.swiftkv_decode(*a, window=RWIN, ring=True,
                                      block_k=128, interpret=True))(
        q, kr, kr, lengths)
    prims = _flat_primitives(jaxpr.jaxpr, set())
    assert not prims & {"gather", "roll", "sort", "scatter",
                        "scatter-add", "rev"}, prims


def test_blockwise_ring_adds_no_data_movement():
    """The blockwise ring program must be the linear-cache program plus
    position *arithmetic* only: an unrotate would show up as new
    data-movement primitives (gather of the whole cache, roll, sort, ...)
    relative to the plain windowed decode on the same shapes."""
    from repro.core import attention as attn
    q = jnp.zeros((2, 4, 64), jnp.float32)
    kr = jnp.zeros((2, RING, 2, 64), jnp.float32)
    lengths = jnp.asarray([2 * RING + 5, 40], jnp.int32)

    def fn(ring):
        return jax.make_jaxpr(
            lambda *a: attn.decode_attention(*a, impl="blockwise",
                                             window=RWIN, ring=ring,
                                             block_size=128))(
            q, kr, kr, lengths)

    ring_prims = _flat_primitives(fn(True).jaxpr, set())
    linear_prims = _flat_primitives(fn(False).jaxpr, set())
    arithmetic = {"rem", "add", "sub", "mul", "sign", "select_n", "and",
                  "or", "not", "lt", "le", "gt", "ge", "eq", "ne",
                  "convert_element_type", "broadcast_in_dim", "iota",
                  "stop_gradient"}
    assert ring_prims - linear_prims <= arithmetic, \
        ring_prims - linear_prims


def test_ring_requires_window():
    q, k, v, lengths = mk(2, 4, 2, 256, 64, jnp.float32)
    with pytest.raises(ValueError, match="window"):
        ops.swiftkv_decode(q, k, v, lengths, ring=True, block_k=128,
                           interpret=True)


# ---------------------------------------------------------------------------
# int8 KV caches (+w4a8 serving): scale-plumbing parity vs the dequant oracle
# ---------------------------------------------------------------------------
# The int8 contract is *exact* relative to dequantize-then-attend: the scale
# multiply rides the block loads, so running the kernel on (int8 rows,
# scales) must equal running it on the dequantized f32 rows — float-order
# tolerance only, no quantization-error budget in these assertions.

from repro.core import attention as attn
from repro.core.quantization import dequantize_kv, quantize_kv


def _quant_cache(k):
    """[B, S, Hkv, D] f32 -> (int8 rows, scales [B, Hkv, S], dequant f32)."""
    q8, s = quantize_kv(k)                        # scale [B, S, Hkv]
    sc = jnp.transpose(s, (0, 2, 1))              # position-last plane
    return q8, sc, dequantize_kv(q8, s)


@pytest.mark.parametrize("b,hq,hkv,s,d,blk", SWEEP[:4])
def test_kernel_int8_vs_dequant_oracle(b, hq, hkv, s, d, blk):
    q, k, v, lengths = mk(b, hq, hkv, s, d, jnp.float32)
    k8, ks, kf = _quant_cache(k)
    v8, vs, vf = _quant_cache(v)
    got = ops.swiftkv_decode(q, k8, v8, lengths, block_k=blk,
                             k_scale=ks, v_scale=vs, interpret=True)
    want = ops.swiftkv_decode(q, kf, vf, lengths, block_k=blk,
                              interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_blockwise_int8_vs_dequant_oracle():
    q, k, v, lengths = mk(2, 8, 2, 512, 64, jnp.float32)
    k8, ks, kf = _quant_cache(k)
    v8, vs, vf = _quant_cache(v)
    got = attn.decode_attention(q, k8, v8, lengths, impl="blockwise",
                                block_size=128, k_scale=ks, v_scale=vs)
    want = attn.decode_attention(q, kf, vf, lengths, impl="blockwise",
                                 block_size=128)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_naive_int8_agrees_with_blockwise_int8():
    """The dense oracle (dequantize up front) and the streaming scale
    multiply are the same math in different orders."""
    q, k, v, lengths = mk(2, 4, 2, 256, 64, jnp.float32)
    k8, ks, _ = _quant_cache(k)
    v8, vs, _ = _quant_cache(v)
    a = attn.decode_attention(q, k8, v8, lengths, impl="naive",
                              k_scale=ks, v_scale=vs)
    b_ = attn.decode_attention(q, k8, v8, lengths, impl="blockwise",
                               block_size=128, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(a, b_, atol=2e-5)


@pytest.mark.parametrize("wrap_off", [0, 1, 127, 131])
def test_kernel_int8_ring_wrap(wrap_off):
    """int8 ring cache at the wrap boundary offsets: the per-slot scale
    plane rides the same rotated layout as the rows (slot s's scale
    multiplies slot s's row, wherever its absolute position landed)."""
    b, hq, hkv, d = 3, 4, 2, 64
    lengths = np.asarray([2 * RING + wrap_off, RING - 37, 1], np.int32)
    L = int(lengths.max())
    kf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    vf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
    kr, vr = _ringify(kf, lengths, RING), _ringify(vf, lengths, RING)
    k8, ks, krf = _quant_cache(kr)
    v8, vs, vrf = _quant_cache(vr)
    got = ops.swiftkv_decode(q, k8, v8, jnp.asarray(lengths), window=RWIN,
                             ring=True, block_k=128, k_scale=ks, v_scale=vs,
                             interpret=True)
    want = ops.swiftkv_decode(q, krf, vrf, jnp.asarray(lengths), window=RWIN,
                              ring=True, block_k=128, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("wrap_off", [0, 1, 127, 131])
def test_blockwise_int8_ring_wrap(wrap_off):
    b, hq, hkv, d = 2, 4, 2, 64
    lengths = np.asarray([2 * RING + wrap_off, RING + 11], np.int32)
    L = int(lengths.max())
    kf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    vf = np.asarray(RNG.standard_normal((b, L, hkv, d)), np.float32)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
    kr, vr = _ringify(kf, lengths, RING), _ringify(vf, lengths, RING)
    k8, ks, krf = _quant_cache(kr)
    v8, vs, vrf = _quant_cache(vr)
    got = attn.decode_attention(q, k8, v8, jnp.asarray(lengths),
                                impl="blockwise", window=RWIN, ring=True,
                                block_size=128, k_scale=ks, v_scale=vs)
    want = attn.decode_attention(q, krf, vrf, jnp.asarray(lengths),
                                 impl="blockwise", window=RWIN, ring=True,
                                 block_size=128)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_pooled_int8_heterogeneous_entries():
    """int8 source-KV pool: slots mapping to different entries with
    heterogeneous lengths — including a shared entry and a length-0 row —
    equal the dequantized-pool read exactly."""
    e, s_src, hkv, hq, d, b = 3, 192, 2, 4, 64, 4
    kp = np.asarray(RNG.standard_normal((e, s_src, hkv, d)), np.float32)
    vp = np.asarray(RNG.standard_normal((e, s_src, hkv, d)), np.float32)
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), jnp.float32)
    entries = jnp.asarray([0, 2, 0, 1], jnp.int32)     # slots 0/2 share entry 0
    lengths = jnp.asarray([192, 57, 130, 0], jnp.int32)
    k8, ks, kf = _quant_cache(jnp.asarray(kp))         # scale [E, Hkv, S]
    v8, vs, vf = _quant_cache(jnp.asarray(vp))
    got = attn.decode_cross_attention(q, k8, v8, entries, lengths,
                                      impl="blockwise", block_size=64,
                                      k_scale=ks, v_scale=vs)
    want = attn.decode_cross_attention(q, kf, vf, entries, lengths,
                                       impl="blockwise", block_size=64)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the no-source row reads an exact zero either way
    np.testing.assert_array_equal(np.asarray(got)[3], np.zeros((hq, d)))


def test_kernel_int8_ring_consumed_zero_copy():
    """The int8 ring program must stay zero-copy: scales stream blockwise
    next to the rows — no gather / roll / sort materializing a dequantized
    or unrotated copy of the cache."""
    q = jnp.zeros((2, 4, 64), jnp.float32)
    kr = jnp.zeros((2, RING, 2, 64), jnp.int8)
    sc = jnp.zeros((2, 2, RING), jnp.float32)
    lengths = jnp.asarray([2 * RING + 5, 40], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q_, k_, s_, l_: ops.swiftkv_decode(
            q_, k_, k_, l_, window=RWIN, ring=True, block_k=128,
            k_scale=s_, v_scale=s_, interpret=True))(q, kr, sc, lengths)
    prims = _flat_primitives(jaxpr.jaxpr, set())
    assert not prims & {"gather", "roll", "sort", "scatter",
                        "scatter-add", "rev"}, prims


def test_int8_scales_require_both():
    q, k, v, lengths = mk(1, 4, 2, 256, 64, jnp.float32)
    sc = jnp.ones((1, 2, 256), jnp.float32)
    with pytest.raises(ValueError, match="both"):
        ops.swiftkv_decode(q, k, v, lengths, block_k=128, k_scale=sc,
                           interpret=True)
