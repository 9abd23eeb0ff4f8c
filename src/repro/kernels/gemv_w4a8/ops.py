"""Public jit'd wrapper: float activations in, quantize-on-the-fly A8, packed
W4 weights with group-wise scales, float out. Pads every axis to kernel block
multiples."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import GROUP, QuantizedLinear, quantize_a8
from .kernel import gemv_w4a8_pallas


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def gemv_w4a8(x: jax.Array, packed: jax.Array, w_scale: jax.Array,
              *, block_m: int = 8, block_n: int = 256, block_k: int = 512,
              out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """x: [..., K] float; packed: [K, N//2] uint8; w_scale: [K//GROUP, N] f32
    (group-wise, see quantization.quantize_w4). Returns [..., N]. Quantizes
    activations per-token to int8 (A8). ``interpret=True`` runs the Pallas
    interpreter (CPU tests); the default compiles the kernel for the TPU."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = packed.shape[1] * 2
    xf = x.reshape(-1, k)
    m = xf.shape[0]

    xq, xs = quantize_a8(xf)                      # [M, K] int8, [M, 1] f32

    bm = min(block_m, max(8, m))
    pad_m = (-m) % bm
    pad_k = (-k) % block_k
    pad_n = (-n) % block_n
    if pad_m or pad_k:
        xq = jnp.pad(xq, ((0, pad_m), (0, pad_k)))
        xs = jnp.pad(xs, ((0, pad_m), (0, 0)))
    if pad_k or pad_n:
        packed = jnp.pad(packed, ((0, pad_k), (0, pad_n // 2)))
    # group-scale rows for padded K (zero weights x any scale = 0) + padded N
    n_groups = (k + pad_k) // GROUP
    ws = w_scale
    if ws.shape[0] < n_groups:
        ws = jnp.pad(ws, ((0, n_groups - ws.shape[0]), (0, 0)),
                     constant_values=1.0)
    if pad_n:
        ws = jnp.pad(ws, ((0, 0), (0, pad_n)), constant_values=1.0)

    out = gemv_w4a8_pallas(xq, packed, xs, ws, block_m=bm, block_n=block_n,
                           block_k=block_k, out_dtype=out_dtype,
                           interpret=interpret)
    return out[:m, :n].reshape(*lead, n)


def linear_w4a8(x: jax.Array, qw: QuantizedLinear, **kw) -> jax.Array:
    out = gemv_w4a8(x, qw.packed, qw.scale, **kw)
    if qw.bias is not None:
        out = out + qw.bias
    return out
