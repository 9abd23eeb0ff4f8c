"""Pallas TPU kernel: SwiftKV single-pass decode attention.

TPU adaptation of the paper's per-token pipeline (DESIGN.md §2): the KV cache
streams HBM -> VMEM in ``(block_k, D)`` tiles; the running ``(mu, Z, Y)`` triple
lives in VMEM scratch across sequential grid steps. One pass, exactly-once
reads, no score materialization, deferred division at the last block — the
paper's invariants at MXU-friendly granularity.

Grid: ``(B, S // block_k)`` — batch parallel, KV blocks sequential
(``arbitrary``). Each program consumes one KV tile holding every KV head of
``block_k`` positions; inside it each head group's ``G = Hq/Hkv`` query heads
share that group's single KV read (for MQA this amortizes the whole cache
scan over 8 query heads — strictly better than the paper's per-head
duplication).

``lengths`` rides the scalar-prefetch channel: the KV index map *clamps* block
fetches past the valid prefix (re-fetching the last valid tile instead of
streaming garbage), so out-of-range blocks cost no HBM traffic beyond one tile
and are masked out of the math entirely.

``exp_mode="lut"`` reproduces the paper's Eq. 9-10 exponential (32-entry LUT +
linear interpolation) via a one-hot matmul gather that lowers to the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.exp2_lut import LOG2_E, LUT_SIZE, make_lut
from repro.core.swiftkv import NEG_INF

_LUT_VALS, _LUT_SLOPES = make_lut()


def _exp_lut(x, lut_vals, lut_slopes):
    """exp(x) for x <= 0, Eq. 9-10, MXU-lowerable (one-hot matmul gather).
    ``lut_vals``/``lut_slopes``: [LUT_SIZE] arrays (kernel inputs)."""
    y = x * LOG2_E
    n = jnp.ceil(y)
    f = y - n                                  # (-1, 0]
    u = -f * LUT_SIZE
    idx = jnp.clip(u.astype(jnp.int32), 0, LUT_SIZE - 1)
    f2 = u - idx.astype(x.dtype)
    onehot = (idx[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (*idx.shape, LUT_SIZE), len(idx.shape))).astype(x.dtype)
    base = onehot @ lut_vals.astype(x.dtype)
    slope = onehot @ lut_slopes.astype(x.dtype)
    frac = base + slope * f2
    # 2^n for n in [-126, 0]: exponent-bias arithmetic, no transcendental
    pow2n = jax.lax.bitcast_convert_type(
        ((jnp.clip(n, -126, 0) + 127.0).astype(jnp.int32)) << 23, jnp.float32)
    return frac * pow2n.astype(x.dtype)


def _kernel(lengths_ref,                     # scalar prefetch [B] int32
            *refs, block_k: int, n_blocks: int, n_kv: int,
            window: int | None, scale: float, exp_mode: str, ring: bool,
            quant: bool):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    if quant:
        # int8 KV: per-(row, head, position) dequant scales arrive as
        # (1, Hkv, block_k) tiles through the same clamped kv index map
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    if exp_mode == "lut":
        lut_ref, o_ref, m_scr, z_scr, y_scr = refs
        exp = functools.partial(_exp_lut, lut_vals=lut_ref[0],
                                lut_slopes=lut_ref[1])
    else:
        o_ref, m_scr, z_scr, y_scr = refs
        exp = jnp.exp
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        y_scr[...] = jnp.zeros_like(y_scr)

    @pl.when(i * block_k < length)           # blocks past the prefix: no math
    def _update():
        slot = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        if ring:
            # ring cache: slot s holds absolute position p - ((p - s) mod R)
            # for p = length - 1 (R = n_blocks * block_k). Validity comes
            # from that position, so a wrapped ring streams through the same
            # BlockSpec index maps untouched — no unrotate copy, and the
            # (mu, Z, Y) fold is order-independent so ring order is exact.
            r = n_blocks * block_k
            p = length - 1
            pos = p - jnp.mod(p - slot, r)
            valid = (pos >= 0) & (pos > p - window)
        else:
            pos = slot
            valid = pos < length
            if window is not None:
                valid &= pos >= length - window
        valid_f = valid.astype(jnp.float32)              # [1, block_k]

        # the tile holds every KV head of block_k positions in the cache's
        # native [B, S, Hkv, D] layout; each head group reads its own
        # (block_k, D) slice, and its G query heads share that one read
        for h in range(n_kv):                            # static unroll
            q = q_ref[0, h].astype(jnp.float32)          # [G, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)    # [block_k, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale                                # [G, block_k]
            if quant:
                # int8 dequant in VMEM: the per-position K scale multiplies
                # score column j exactly as it would multiply key row j,
                # and the V scale folds into p before the PV product below
                s = s * ks_ref[0, h:h + 1, :].astype(jnp.float32)
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_scr[h]                            # [G, 128] lane-bcast
            m_blk = jnp.max(s, axis=-1, keepdims=True)   # [G, 1]
            m_new = jnp.maximum(m_prev, m_blk)           # bcast -> [G, 128]
            alpha = exp(m_prev - m_new)                  # (0, 1]
            p = exp(s - m_new[:, :1]) * valid_f          # [G, block_k]
            z_scr[h] = alpha * z_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * vs_ref[0, h:h + 1, :].astype(jnp.float32)
            y_scr[h] = alpha[:, :1] * y_scr[h] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(i == n_blocks - 1)
    def _finalize():
        z = z_scr[:, :, :1]
        out = jnp.where(z > 0, y_scr[...] / jnp.where(z > 0, z, 1.0), 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def swiftkv_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                          lengths: jax.Array, *, block_k: int = 512,
                          window: int | None = None, ring: bool = False,
                          scale: float, exp_mode: str = "native",
                          k_scale: jax.Array | None = None,
                          v_scale: jax.Array | None = None,
                          interpret: bool = False) -> jax.Array:
    """q: [B, Hkv, G, D]; k, v: [B, S, Hkv, D] — the **cache-native**
    layout, consumed directly through the BlockSpec index maps (S a
    multiple of block_k); lengths: [B] int32. Returns [B, Hkv, G, D] in
    q.dtype. Feeding the cache layout straight to the grid is what lets the
    ops wrapper stop paying a whole-cache swapaxes+pad copy per layer per
    decode step. A KV tile spans all Hkv heads, ``(1, block_k, Hkv, D)``,
    so its last two dims are the array's own and the TPU's (8, 128) tiling
    rule holds at any head count and head_dim. ``ring=True`` consumes a
    ring cache of R = S slots in place (slot ``s`` holds position ``p -
    ((p - s) mod R)``, ``p = lengths-1``); only the validity mask changes —
    the same index maps stream the wrapped cache with zero copies. The
    unwrapped prefix clamp still applies: while ``lengths <= S`` blocks
    past the written prefix are neither fetched nor folded.

    ``k_scale`` / ``v_scale``: optional [B, Hkv, S] float (f32 or bf16)
    dequant scales for an **int8** cache — streamed as (1, Hkv, block_k)
    tiles through the same clamped index map and applied in VMEM, so the
    int8 form adds S x itemsize bytes of scale traffic per (row, head)
    against the 3 x S x D bytes it saves on the cache itself."""
    bsz, hkv, g, d = q.shape
    s_len = k.shape[1]
    assert s_len % block_k == 0, (s_len, block_k)
    n_blocks = s_len // block_k
    quant = k_scale is not None

    def q_map(b, i, lens):
        return (b, 0, 0, 0)

    def last_block(b, lens):
        # clamp fetches past the valid prefix: no wasted HBM traffic
        return jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)

    def kv_map(b, i, lens):
        return (b, jnp.minimum(i, last_block(b, lens)), 0, 0)

    def sc_map(b, i, lens):
        return (b, 0, jnp.minimum(i, last_block(b, lens)))

    in_specs = [
        pl.BlockSpec((1, hkv, g, d), q_map),
        pl.BlockSpec((1, block_k, hkv, d), kv_map),
        pl.BlockSpec((1, block_k, hkv, d), kv_map),
    ]
    operands = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, hkv, block_k), sc_map),
                     pl.BlockSpec((1, hkv, block_k), sc_map)]
        operands += [k_scale, v_scale]
    if exp_mode == "lut":
        lut = jnp.stack([jnp.asarray(_LUT_VALS, jnp.float32),
                         jnp.asarray(_LUT_SLOPES, jnp.float32)])
        in_specs.append(pl.BlockSpec((2, LUT_SIZE), lambda b, i, lens: (0, 0)))
        operands.append(lut)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # mu (lane-broadcast)
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # Z  (lane-broadcast)
            pltpu.VMEM((hkv, g, d), jnp.float32),     # Y
        ],
    )
    kernel = functools.partial(_kernel, block_k=block_k, n_blocks=n_blocks,
                               n_kv=hkv, window=window, scale=scale,
                               exp_mode=exp_mode, ring=ring, quant=quant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, *operands)
