"""Pallas TPU kernel: W4A8 quantized GEMV/GEMM (paper §IV-B).

TPU realization of the SKV Processor Array's low-precision mode: INT4-packed
weights unpack in VMEM, INT8 activations, int8 x int8 -> INT32 MXU
accumulation, group-wise (128-input-channel) f32 rescale on the way out.
The *same* MXU that runs the (f32) attention kernel runs this — the
dual-mode-array story, on a TPU (DESIGN.md §2).

Grid: ``(M // block_m, N // block_n, K // block_k)`` with sequential K
(``arbitrary``) accumulating into an f32 VMEM scratch tile (int32 partial
sums are rescaled per group *inside* the k-step, so the accumulator carries
the already-dequantized value — this is exactly the SFU's INT32->FXP32
conversion in Fig. 5(c), fused into the MAC loop).

Weights are packed two int4 output-channels per byte along N (matching
:mod:`repro.core.quantization`), so a ``(block_k, block_n)`` logical tile is a
``(block_k, block_n // 2)`` byte tile in HBM — half the weight traffic of
int8, which is the whole point on a bandwidth-bound decode step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import GROUP


def _kernel(x_ref, wp_ref, xs_ref, ws_ref, o_ref, acc_scr, *, n_k: int,
            group: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # unpack in int32 (no shift runs on uint8), sign-extend, then narrow to
    # int8 for the MXU: lo nibbles are the even output channels, hi the odd
    packed = wp_ref[...].astype(jnp.int32)               # [bk, bn//2]
    lo = packed & 0xF
    hi = packed >> 4
    lo = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.int8)
    hi = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.int8)

    x = x_ref[...]                                       # [bm, bk] int8
    bm, bk = x.shape
    n_groups = bk // group
    dims = (((1,), (0,)), ((), ()))
    acc = acc_scr[...]
    for g in range(n_groups):                            # static unroll
        sl = slice(g * group, (g + 1) * group)
        even = jax.lax.dot_general(x[:, sl], lo[sl, :], dims,
                                   preferred_element_type=jnp.int32)
        odd = jax.lax.dot_general(x[:, sl], hi[sl, :], dims,
                                  preferred_element_type=jnp.int32)
        # interleave the [bm, bn//2] partials back to channel order — a
        # small relayout, where interleaving the [bk, bn//2] weight tile
        # would cost a lane-padded copy of the whole tile
        part = jnp.stack([even, odd], axis=-1).reshape(bm, -1)
        ws = ws_ref[pl.ds(ik * n_groups + g, 1), :]      # [1, bn] f32
        acc = acc + part.astype(jnp.float32) * ws
    acc_scr[...] = acc

    @pl.when(ik == n_k - 1)
    def _finalize():
        out = acc_scr[...] * xs_ref[...].astype(jnp.float32)  # per-token scale
        o_ref[...] = out.astype(o_ref.dtype)


def gemv_w4a8_pallas(x, w_packed, x_scale, w_scale, *, block_m: int = 8,
                     block_n: int = 256, block_k: int = 512,
                     out_dtype=jnp.float32, interpret: bool = False):
    """x: [M, K] int8; w_packed: [K, N//2] uint8; x_scale: [M, 1] f32;
    w_scale: [K//GROUP, N] f32. Returns [M, N]. M/N/K multiples of blocks.

    The ``w_scale`` block spans every group row, ``(K//GROUP, block_n)``:
    its first dim is the array's own, so the TPU's (8, 128) tiling rule
    holds at any K. Its block index does not change along the sequential K
    axis, so it is fetched once per output tile, and each K step reads its
    own group rows from VMEM."""
    m, k = x.shape
    n = w_packed.shape[1] * 2
    n_m, n_n, n_k = m // block_m, n // block_n, k // block_k
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    assert block_k % GROUP == 0
    n_groups = w_scale.shape[0]
    assert n_groups == k // GROUP, (n_groups, k)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, group=GROUP),
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((block_k, block_n // 2), lambda im, jn, ik: (ik, jn)),
            pl.BlockSpec((block_m, 1), lambda im, jn, ik: (im, 0)),
            pl.BlockSpec((n_groups, block_n), lambda im, jn, ik: (0, jn)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda im, jn, ik: (im, jn)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_packed, x_scale, w_scale)
