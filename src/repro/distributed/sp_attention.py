"""Sequence-parallel SwiftKV decode attention (beyond-paper, DESIGN.md §2).

The KV cache shards along the *sequence* axis across the data mesh axes; each
device folds its shard with the single-pass blockwise recurrence into a
partial ``(mu, Z, Y)`` triple, and one tiny all-gather + associative
``state_merge`` tree produces the exact global attention output. Per-device
collective traffic is O(G·D) — independent of context length — which is what
makes the 500k-context decode shape run at all (a 300GB+ cache never moves).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import swiftkv
from repro.core.swiftkv import SwiftKVState, state_finalize, state_merge


def _local_partial_state(q, k_loc, v_loc, length, shard_offset, *,
                         window, block_size, scale, vary_axes=()):
    """One device's fold over its KV shard. q: [G, D]; k/v_loc: [S_loc, D];
    returns SwiftKVState with batch_shape (G,). ``vary_axes``: manual mesh
    axes the state varies over (shard_map vma tracking)."""
    g, d = q.shape
    s_loc = k_loc.shape[0]
    n_blocks = -(-s_loc // block_size)
    pad = n_blocks * block_size - s_loc
    if pad:
        k_loc = jnp.pad(k_loc, ((0, pad), (0, 0)))
        v_loc = jnp.pad(v_loc, ((0, pad), (0, 0)))
    qf = q.astype(jnp.float32)

    def body(i, state):
        start = i * block_size
        k_blk = jax.lax.dynamic_slice_in_dim(k_loc, start, block_size)
        v_blk = jax.lax.dynamic_slice_in_dim(v_loc, start, block_size)
        t_loc = start + jnp.arange(block_size)                  # local pos
        t = shard_offset + t_loc                                # global pos
        valid = (t < length) & (t_loc < s_loc)  # mask block padding too
        if window is not None:
            valid &= t >= length - window
        s_blk = jnp.einsum("gd,kd->gk", qf, k_blk.astype(jnp.float32)) * scale
        return swiftkv.state_update_block(
            state, jnp.where(valid[None, :], s_blk, swiftkv.NEG_INF),
            v_blk.astype(jnp.float32)[None], valid[None, :].astype(jnp.float32))

    init = swiftkv.state_init(d, batch_shape=(g,))
    if vary_axes:  # mark the carry as device-varying for shard_map's vma check
        init = jax.tree.map(
            lambda x: jax.lax.pcast(x, vary_axes, to="varying"), init)
    return jax.lax.fori_loop(0, n_blocks, body, init)


def decode_attention_sp(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        lengths: jax.Array, *, mesh: jax.sharding.Mesh,
                        seq_axes, batch_axes=None, window: int | None = None,
                        block_size: int = 512,
                        scale: float | None = None) -> jax.Array:
    """q: [B, Hq, D]; caches [B, S, Hkv, D] with S sharded over ``seq_axes``
    and B over ``batch_axes`` (both preserved — no resharding of the cache);
    lengths [B]. Returns [B, Hq, D] sharded over ``batch_axes``."""
    b, hq, d = q.shape
    s_len, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = (1.0 / d ** 0.5) if scale is None else scale
    seq_axes = tuple(seq_axes) if not isinstance(seq_axes, str) else (seq_axes,)
    if batch_axes is None:
        from repro.distributed.context import get_context
        ctx = get_context()
        batch_axes = ctx.batch_axes if ctx.active else ()
    bd_size = 1
    for a in batch_axes:
        bd_size *= mesh.shape[a]
    bd = tuple(batch_axes) if (bd_size > 1 and b % bd_size == 0) else None
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    s_loc = s_len // n_shards

    def shard_fn(q_s, k_s, v_s, len_s):
        # q_s: [B, Hkv, G, D]; k_s/v_s: [B, S_loc, Hkv, D] (this shard)
        idx = jax.lax.axis_index(seq_axes)
        offset = idx * s_loc

        def one(qh, kh, vh, ln):
            return _local_partial_state(qh, kh, vh, ln, offset, window=window,
                                        block_size=block_size, scale=scale,
                                        vary_axes=seq_axes)

        per_head = jax.vmap(one, in_axes=(0, 0, 0, None))       # Hkv
        per_batch = jax.vmap(per_head, in_axes=(0, 0, 0, 0))    # B
        st = per_batch(q_s, jnp.swapaxes(k_s, 1, 2), jnp.swapaxes(v_s, 1, 2),
                       len_s)                                    # [B,Hkv,G,...]
        # merge partial triples across the sequence shards (tiny collective)
        parts = jax.lax.all_gather(st, seq_axes, axis=0, tiled=False)
        acc = jax.tree.map(lambda x: x[0], parts)
        for i in range(1, n_shards):
            acc = state_merge(acc, jax.tree.map(lambda x: x[i], parts))
        return state_finalize(acc).astype(q_s.dtype)

    qg = q.reshape(b, hkv, g, d)
    spec_kv = P(bd, seq_axes, None, None)
    # check_vma=False: after the all-gather + associative merge every seq
    # shard holds the identical value, which the static vma analysis can't
    # infer. Batch stays sharded end to end — the cache never reshards.
    out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bd), spec_kv, spec_kv, P(bd)),
        out_specs=P(bd),
        check_vma=False,
    )(qg, k_cache, v_cache, lengths)
    return out.reshape(b, hq, d)
