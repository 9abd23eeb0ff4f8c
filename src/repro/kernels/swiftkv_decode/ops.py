"""Public jit'd wrapper for the SwiftKV decode kernel.

Handles GQA grouping and block-size selection. The kernel is compiled for
the TPU; interpret mode (the Pallas interpreter, for CPU tests) runs only
when the caller passes ``interpret=True``. KV caches flow through in
their native ``[B, S, Hkv, D]`` layout — the kernel's BlockSpec index maps
tile that layout directly, so there is **no** per-call ``swapaxes`` /
``pad`` (the old wrapper copied the entire cache per layer per decode
step). The flip side of zero-copy is an alignment contract: the cache's
``max_len`` must be divisible by a usable block size at *init* time —
misaligned caches raise instead of silently paying the copy back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import swiftkv_decode_pallas


# VMEM held by the double-buffered K and V tiles: the kernel's scoped VMEM
# is 16 MiB on v5e, and the tiles must leave room for in-kernel temporaries
_KV_TILE_VMEM = 8 << 20


def _tile_block_cap(hkv: int, d: int, dtype) -> int:
    """Largest power-of-two ``block_k`` whose (block_k, Hkv, D) K and V tiles,
    double-buffered and padded to the TPU tiling ((8 * 4 / itemsize)
    sublanes by 128 lanes), fit ``_KV_TILE_VMEM``."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    row = -(-hkv // sub) * sub * (-(-d // 128) * 128) * item
    cap = max(8, _KV_TILE_VMEM // (4 * row))
    return 1 << (cap.bit_length() - 1)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "scale",
                                             "exp_mode", "ring", "interpret"))
def swiftkv_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                   lengths: jax.Array, *, window: int | None = None,
                   block_k: int = 512, scale: float | None = None,
                   exp_mode: str = "native", ring: bool = False,
                   k_scale: jax.Array | None = None,
                   v_scale: jax.Array | None = None,
                   interpret: bool = False) -> jax.Array:
    """SwiftKV single-pass decode attention (Pallas).

    q: [B, Hq, D]; k_cache/v_cache: [B, S, Hkv, D]; lengths: [B] int32.
    Returns [B, Hq, D]. An exactly-dividing, sublane-aligned (multiple of
    8) ``block_k`` request is honored as-is; a non-dividing request snaps
    down to the largest power-of-two divisor of S, but never silently to a
    degenerate one — a snapped block below 128, or any block that leaves S
    misaligned, raises: allocate the cache block-aligned at ``init_cache``
    instead of paying a pad+copy (or an unaligned whole-cache stream) per
    layer per decode step.

    ``ring=True``: the cache is a ring of R = S slots (newest token at
    ``(lengths-1) % R``); ``lengths`` counts tokens seen, and may exceed S
    once wrapped. The ring streams through the same BlockSpec index maps as
    a linear cache — zero-copy, no host-side unrotate — with per-slot
    positions recovered arithmetically inside the kernel. Requires
    ``window`` (rings only exist for SWA configs).

    ``k_scale`` / ``v_scale``: optional [B, Hkv, S] float dequant scales for
    an int8 cache (``+w4a8`` serving) — streamed blockwise alongside the
    KV tiles and multiplied in VMEM; the alignment contract is unchanged
    (the scale's S axis tiles with the same block size).

    ``block_k`` is also capped so the double-buffered K/V tiles, which span
    every KV head, fit the kernel's VMEM (``_tile_block_cap``).
    ``interpret=True`` runs the Pallas interpreter (CPU tests); the default
    compiles the kernel, which needs a TPU backend.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("swiftkv_decode: pass both k_scale and v_scale "
                         "or neither")
    if ring and window is None:
        raise ValueError("swiftkv_decode: ring caches are windowed — pass "
                         "window with ring=True")
    b, hq, d = q.shape
    s_len, hkv = k_cache.shape[1], k_cache.shape[2]
    assert hq % hkv == 0
    g = hq // hkv
    scale = float(1.0 / (d ** 0.5)) if scale is None else scale

    bk = min(block_k, s_len, _tile_block_cap(hkv, d, k_cache.dtype))
    requested = bk
    if s_len % bk:
        # any power of two at or below (S & -S) divides S exactly
        bk = min(1 << (bk.bit_length() - 1), s_len & -s_len)
    if s_len % bk or bk % 8 or (bk < 128 and bk != requested):
        raise ValueError(
            f"swiftkv_decode: cache length {s_len} has no usable block for "
            f"block_k={block_k} (best candidate {bk}) — allocate the KV "
            "cache with a block-aligned max_len at init_cache (a multiple "
            "of 128) instead of paying a whole-cache pad+copy, or an "
            "unaligned whole-cache stream, per layer per decode step")
    block_k = bk

    qg = q.reshape(b, hkv, g, d)
    out = swiftkv_decode_pallas(qg, k_cache, v_cache,
                                lengths.astype(jnp.int32),
                                block_k=block_k, window=window, ring=ring,
                                scale=scale, exp_mode=exp_mode,
                                k_scale=k_scale, v_scale=v_scale,
                                interpret=interpret)
    return out.reshape(b, hq, d)
