"""Batched multi-head attention built on the SwiftKV primitives.

Two entry points used by every model:

  * ``decode_attention``  — one new token against a KV cache (the paper's
    target workload). GQA/MQA-aware; dispatches between the paper-faithful
    tokenwise scan, the blockwise TPU form, and the Pallas kernel.
  * ``prefill_attention`` — multi-token self/cross attention as a *single-pass
    blockwise* scan over KV blocks using the same ``(mu, Z, Y)`` recurrence
    (flash-style, no S x S score materialization), so 32k-token prefill lowers
    with O(S·D) live memory.

Layouts: activations ``[B, S, H, D]``; KV caches ``[B, S, Hkv, D]``.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from . import swiftkv
from .swiftkv import NEG_INF, SwiftKVState, state_init, state_update_block, state_finalize

DecodeImpl = Literal["tokenwise", "blockwise", "kernel", "naive", "sp"]


# ---------------------------------------------------------------------------
# Decode (one query token per sequence)
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, impl: DecodeImpl = "blockwise",
                     window: int | None = None, ring: bool = False,
                     block_size: int = 512,
                     scale: float | None = None,
                     k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None,
                     interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; k_cache/v_cache: [B, S, Hkv, D]; lengths: [B] int32.
    Returns [B, Hq, D]. Hq must be a multiple of Hkv (GQA groups).

    ``impl="kernel"`` compiles the Pallas kernel and so needs a TPU backend;
    elsewhere it raises unless the caller asks for the Pallas interpreter
    with ``interpret=True`` (CPU tests). The other impls ignore
    ``interpret``.

    ``ring=True``: the cache is a ring of R = S slots (SWA configs —
    ``window`` required); ``lengths`` counts tokens seen and may exceed S
    once wrapped. The blockwise and kernel paths consume the ring *in
    place* — per-slot absolute positions are recovered arithmetically, so
    there is no unrotate copy and the single-pass exactly-once contract
    holds on the wrapped layout. ``tokenwise`` / ``sp`` have no ring form
    and fall back to blockwise; ``naive`` uses the dense ring oracle.

    The blockwise path's KV loop is length-adaptive (see
    ``swiftkv_decode_blockwise``): under the vmap below each batch row runs
    ``cdiv(length, block)`` block steps, so a big preallocated cache costs
    attention work proportional to the longest *active* sequence — not to
    ``S`` — on every decode tick.

    ``k_scale`` / ``v_scale``: optional [B, Hkv, S] float (f32/bf16) per-(row, head,
    position) dequant scales for an **int8 KV cache** (the ``+w4a8``
    serving form, ``quantization.quantize_kv``). The scale multiply rides
    the blockwise/kernel block loads — no dequantized copy of the cache is
    materialized. ``tokenwise`` / ``sp`` have no int8 form and fall back to
    blockwise; ``naive`` dequantizes up front (it is the dense oracle)."""
    b, hq, d = q.shape
    hkv = k_cache.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv

    if k_scale is not None:
        if impl in ("sp", "tokenwise"):
            impl = "blockwise"   # no seq-sharded / per-token int8 form
        if impl == "naive":
            # dense oracle: dequantize whole (small, test-sized) caches
            sc = jnp.swapaxes(k_scale, 1, 2)[..., None]   # [B, S, Hkv, 1]
            return decode_attention(
                q, k_cache.astype(jnp.float32) * sc,
                v_cache.astype(jnp.float32) * jnp.swapaxes(
                    v_scale, 1, 2)[..., None],
                lengths, impl="naive", window=window, ring=ring,
                block_size=block_size, scale=scale)

    if ring:
        if window is None:
            raise ValueError("ring caches are windowed: pass window")
        if impl in ("sp", "tokenwise"):
            impl = "blockwise"   # no seq-sharded / per-token ring form
        if impl == "naive":
            return decode_attention_ring(q, k_cache, v_cache, lengths,
                                         window=window, scale=scale)

    if impl == "sp":
        # sequence-parallel monoid-merge decode: the KV cache stays
        # seq-sharded over the model axis; each shard folds its slice with
        # the single-pass recurrence and the partial (mu, Z, Y) triples merge
        # with one tiny collective (exact — DESIGN.md §2). Falls back to
        # blockwise outside a mesh context or on non-divisible caches.
        from repro.distributed.context import get_context
        ctx = get_context()
        s_len = k_cache.shape[1]
        if (ctx.active and ctx.model_axis is not None
                and s_len % ctx.axis_size(ctx.model_axis) == 0):
            from repro.distributed.sp_attention import decode_attention_sp
            return decode_attention_sp(
                q, k_cache, v_cache, lengths, mesh=ctx.mesh,
                seq_axes=ctx.model_axis, window=window,
                block_size=min(block_size,
                               s_len // ctx.axis_size(ctx.model_axis)),
                scale=scale)
        impl = "blockwise"

    if impl == "kernel":
        backend = jax.default_backend()
        if backend != "tpu" and not interpret:
            raise ValueError(
                f"decode_attention(impl='kernel') compiles a TPU kernel, but "
                f"the backend is {backend!r}: run on a TPU, or pass "
                "interpret=True for the Pallas interpreter")
        from repro.kernels.swiftkv_decode import ops as kops
        return kops.swiftkv_decode(q, k_cache, v_cache, lengths,
                                   window=window, ring=ring,
                                   block_k=block_size, scale=scale,
                                   k_scale=k_scale, v_scale=v_scale,
                                   interpret=interpret)

    # group queries: [B, Hkv, G, D]; caches to [B, Hkv, S, D]
    qg = q.reshape(b, hkv, g, d)
    kc = jnp.swapaxes(k_cache, 1, 2)
    vc = jnp.swapaxes(v_cache, 1, 2)

    if impl == "tokenwise":
        fn = functools.partial(swiftkv.swiftkv_decode_tokenwise, scale=scale)
        if window is not None:
            raise NotImplementedError("tokenwise path: use blockwise for SWA")
    elif impl == "blockwise":
        fn = functools.partial(swiftkv.swiftkv_decode_blockwise, scale=scale,
                               window=window, ring=ring,
                               block_size=block_size)
    elif impl == "naive":
        fn = functools.partial(swiftkv.softmax_attention_reference, scale=scale,
                               window=window)
    else:
        raise ValueError(impl)

    if k_scale is not None:
        # int8 blockwise: scales ride the same vmap nest, one [S] vector per
        # (row, head) shared across the head group
        per_group = jax.vmap(fn, in_axes=(0, None, None, None, None, None))
        per_head = jax.vmap(per_group, in_axes=(0, 0, 0, None, 0, 0))
        per_batch = jax.vmap(per_head, in_axes=(0, 0, 0, 0, 0, 0))
        out = per_batch(qg, kc, vc, lengths, k_scale, v_scale)
        return out.reshape(b, hq, d)

    # vmap: queries within a group share one KV scan (in_axes k/v None)
    per_group = jax.vmap(fn, in_axes=(0, None, None, None))      # over G
    per_head = jax.vmap(per_group, in_axes=(0, 0, 0, None))      # over Hkv
    per_batch = jax.vmap(per_head, in_axes=(0, 0, 0, 0))         # over B
    out = per_batch(qg, kc, vc, lengths)                          # [B, Hkv, G, D]
    return out.reshape(b, hq, d)


def decode_cross_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           entries: jax.Array, lengths: jax.Array, *,
                           impl: DecodeImpl = "blockwise",
                           block_size: int = 512,
                           scale: float | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None) -> jax.Array:
    """Ragged cross-attention decode read over a shared **source-KV pool**.

    q: [B, Hq, D] (one decoder token per slot); k_pool / v_pool:
    [E, S_src, Hkv, D] — E pooled encoder-side entries, NOT batched by slot;
    entries: [B] int32 maps each slot to its pool entry (requests sharing a
    source id share an entry — the :class:`repro.serving.slot_pool.
    SourceKVPool` contract); lengths: [B] int32 per-slot valid source
    prefix. Rows with *different* encoder lengths (and different entries)
    coexist in one static-shape dispatch: each row masks its own tail, a
    ``length == 0`` row (no source / inactive slot) reads an exact zero.

    Non-causal, unwindowed, and read-only — nothing is written back, which
    is what lets the pool be shared. The blockwise path folds the entry
    index into the KV block reads (``swiftkv_decode_pooled``), so no
    per-slot copy of the pool is ever materialized. ``tokenwise`` / ``sp``
    / ``kernel`` have no pooled form and fall back to blockwise; ``naive``
    gathers the per-slot entries and runs the dense oracle.

    ``k_scale`` / ``v_scale``: optional [E, Hkv, S] float (f32/bf16) per-(entry, head,
    position) dequant scales for an int8 source-KV pool — folded into the
    pooled block reads like the self-attention form."""
    b, hq, d = q.shape
    hkv = k_pool.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    entries = jnp.asarray(entries, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    if impl == "naive":
        # dense oracle: gather each slot's entry, then the batched reference
        kc = jnp.take(k_pool, entries, axis=0)           # [B, S, Hkv, D]
        vc = jnp.take(v_pool, entries, axis=0)
        return decode_attention(
            q, kc, vc, lengths, impl="naive", scale=scale,
            k_scale=(None if k_scale is None
                     else jnp.take(k_scale, entries, axis=0)),
            v_scale=(None if v_scale is None
                     else jnp.take(v_scale, entries, axis=0)))

    qg = q.reshape(b, hkv, g, d)
    kp = jnp.swapaxes(k_pool, 1, 2)                      # [E, Hkv, S, D]
    vp = jnp.swapaxes(v_pool, 1, 2)
    fn = functools.partial(swiftkv.swiftkv_decode_pooled,
                           block_size=block_size, scale=scale)
    if k_scale is not None:
        # pooled int8: the [E, S] scale planes broadcast like the pool
        per_group = jax.vmap(fn, in_axes=(0, None, None, None, None,
                                          None, None))             # over G
        per_head = jax.vmap(per_group, in_axes=(0, 1, 1, None, None,
                                                1, 1))             # over Hkv
        per_batch = jax.vmap(per_head, in_axes=(0, None, None, 0, 0,
                                                None, None))       # over B
        out = per_batch(qg, kp, vp, entries, lengths, k_scale, v_scale)
        return out.reshape(b, hq, d)
    # vmap: queries within a group share one pooled scan; the pool itself is
    # broadcast (in_axes None) — only (q, entry, length) are per-row
    per_group = jax.vmap(fn, in_axes=(0, None, None, None, None))  # over G
    per_head = jax.vmap(per_group, in_axes=(0, 1, 1, None, None))  # over Hkv
    per_batch = jax.vmap(per_head, in_axes=(0, None, None, 0, 0))  # over B
    out = per_batch(qg, kp, vp, entries, lengths)        # [B, Hkv, G, D]
    return out.reshape(b, hq, d)


def decode_attention_ring(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, lengths: jax.Array, *,
                          window: int, scale: float | None = None) -> jax.Array:
    """Sliding-window decode over a RING KV cache (beyond-paper).

    q: [B, Hq, D]; k/v_cache: [B, R, Hkv, D] with R >= window+1 ring slots;
    ``lengths``: tokens seen so far (the newest token lives at slot
    (lengths-1) % R). Slot s holds absolute position p - ((p - s) mod R)
    where p = lengths-1; a slot is attended iff its position is in
    [lengths-window, lengths). R is ~window, independent of context, so a
    500k-token decode reads ~window KV entries per step — the exactly-once
    property with an O(window) working set."""
    b, hq, d = q.shape
    r, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = (1.0 / d ** 0.5) if scale is None else scale

    p = (lengths - 1)[:, None]                            # [B, 1]
    s = jnp.arange(r)[None, :]                            # [1, R]
    pos = p - jnp.mod(p - s, r)                           # [B, R] absolute
    valid = (pos >= 0) & (pos > p - window)               # in-window slots

    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    kc = k_cache.astype(jnp.float32)
    vc = v_cache.astype(jnp.float32)
    sc = jnp.einsum("bhgd,bshd->bhgs", qg, kc) * scale    # [B,Hkv,G,R]
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    pr = jnp.where(valid[:, None, None, :], pr, 0.0)
    out = jnp.einsum("bhgs,bshd->bhgd", pr, vc)
    return out.reshape(b, hq, d).astype(q.dtype)


def prefill_attention_ring(q: jax.Array, k_ring: jax.Array, v_ring: jax.Array,
                           q_positions: jax.Array, p_max: jax.Array, *,
                           window: int, scale: float | None = None) -> jax.Array:
    """Causal SWA attention of a prompt *chunk* over a RING KV cache.

    q: [B, C, Hq, D] — chunk queries at absolute positions ``q_positions``
    [C]; k/v_ring: [B, R, Hkv, D] ring caches that already contain this
    chunk's keys (written at ``pos % R``) on top of the slot's history;
    ``p_max``: the last *real* (non-padding) position written. Slot ``s``
    holds absolute position ``p_max - ((p_max - s) mod R)``; a slot is
    attended by query row ``c`` iff that position is in
    ``(q_positions[c] - window, q_positions[c]]`` — which also masks (a)
    slots a later in-chunk token overwrote (their lost position is provably
    out of the earlier query's window when R >= window + C - 1, the
    engine-enforced ring slack), (b) a previous occupant's stale slots
    (their recovered position is negative until this request wraps), and
    (c) padded tail rows (never written: ``keep``-masked by the caller).

    C and R are both small (a prefill chunk against ~window ring slots), so
    this materializes the [C, R] score block directly — the chunk analogue
    of the dense ring decode oracle, not a streamed pass."""
    b, c, hq, d = q.shape
    r, hkv = k_ring.shape[1], k_ring.shape[2]
    g = hq // hkv
    scale = (1.0 / d ** 0.5) if scale is None else scale

    s_idx = jnp.arange(r)[None, :]                        # [1, R]
    pos = p_max - jnp.mod(p_max - s_idx, r)               # [1, R] absolute
    qp = q_positions[:, None]                             # [C, 1]
    valid = (pos >= 0) & (pos <= qp) & (pos > qp - window)  # [C, R]

    qg = q.reshape(b, c, hkv, g, d).astype(jnp.float32)
    kc = k_ring.astype(jnp.float32)
    vc = v_ring.astype(jnp.float32)
    sc = jnp.einsum("bchgd,brhd->bchgr", qg, kc) * scale  # [B,C,Hkv,G,R]
    sc = jnp.where(valid[None, :, None, None, :], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    pr = jnp.where(valid[None, :, None, None, :], pr, 0.0)
    out = jnp.einsum("bchgr,brhd->bchgd", pr, vc)
    return out.reshape(b, c, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Prefill (blockwise single-pass over KV; SwiftKV state per query row)
# ---------------------------------------------------------------------------

def _heads_constrain(x: jax.Array):
    """Pin [B, H, ...] activations to (batch over DP axes, heads over the
    model axis) — the TPU analogue of the paper's one-head-per-processor
    layout. Without it the reshape chain around GQA grouping loses the head
    sharding and every chip materializes all-head score tensors."""
    from repro.distributed.context import get_context
    ctx = get_context()
    if not ctx.active:
        return x
    bd = ctx.batch_axes if x.shape[0] % ctx.axis_size(ctx.batch_axes) == 0 \
        else None
    h_ax = ctx.model_axis if x.shape[1] % ctx.axis_size(ctx.model_axis) == 0 \
        else None
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            x, P(bd, h_ax, *([None] * (x.ndim - 2))))
    except Exception:
        return x


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int | None = None,
                      kv_lengths: jax.Array | None = None,
                      q_offset: jax.Array | None = None,
                      kv_block: int = 512, scale: float | None = None) -> jax.Array:
    """q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] -> [B, Sq, Hq, D].

    Single pass over KV blocks with the SwiftKV ``(mu, Z, Y)`` state per
    query row (flash-style; no Sq x Skv score materialization). GQA KV heads
    are repeated to the full query-head count so the head axis stays
    TP-shardable (Hkv < TP cannot be expressed through the grouped layout);
    each KV-block step is rematted, so backward recomputes scores blockwise
    instead of saving every block's score tensor.

    ``kv_lengths``: [B] valid KV prefix (cross-attention padding / appended
    decode). ``q_offset``: [B] absolute position of q row 0 (0 for prefill)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = (1.0 / (d ** 0.5)) if scale is None else scale
    kv_lengths = jnp.full((b,), skv, jnp.int32) if kv_lengths is None else kv_lengths
    q_offset = jnp.zeros((b,), jnp.int32) if q_offset is None else q_offset

    qh = _heads_constrain(jnp.swapaxes(q, 1, 2))       # [B, Hq, Sq, D]
    kh = jnp.swapaxes(k, 1, 2)                          # [B, Hkv, Skv, D]
    vh = jnp.swapaxes(v, 1, 2)
    if g > 1:                                           # repeat KV to q heads
        kh = jnp.repeat(kh, g, axis=1)
        vh = jnp.repeat(vh, g, axis=1)
    kh = _heads_constrain(kh)
    vh = _heads_constrain(vh)

    n_blocks = -(-skv // kv_block)
    pad = n_blocks * kv_block - skv
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))

    qf = qh.astype(jnp.float32) * scale
    pos_q = q_offset[:, None] + jnp.arange(sq)[None]    # [B, Sq]

    def step(state, j):
        k_blk = jax.lax.dynamic_slice_in_dim(kh, j * kv_block, kv_block,
                                             axis=2).astype(jnp.float32)
        v_blk = jax.lax.dynamic_slice_in_dim(vh, j * kv_block, kv_block,
                                             axis=2).astype(jnp.float32)
        pos_k = j * kv_block + jnp.arange(kv_block)     # [Bk]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk)    # [B, H, Sq, Bk]
        valid = pos_k[None, None, :] < kv_lengths[:, None, None]  # [B, 1, Bk]
        valid = jnp.broadcast_to(valid, (b, sq, kv_block))
        if causal:
            valid &= pos_k[None, None, :] <= pos_q[:, :, None]
        if window is not None:
            valid &= pos_k[None, None, :] > pos_q[:, :, None] - window
        valid = valid[:, None]                           # [B, 1, Sq, Bk]
        s = jnp.where(valid, s, NEG_INF)
        mu, z, y = state
        mu_blk = jnp.max(s, axis=-1)
        mu_new = jnp.maximum(mu, mu_blk)
        alpha = jnp.exp(mu - mu_new)
        p = jnp.exp(s - mu_new[..., None]) * valid       # [B, H, Sq, Bk]
        z_new = alpha * z + jnp.sum(p, axis=-1)
        y_new = (alpha[..., None] * y
                 + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk))
        return SwiftKVState(mu=mu_new, z=z_new, y=y_new), None

    init = state_init(d, batch_shape=(b, hq, sq))
    # remat each block step: backward recomputes the [B,H,Sq,Bk] scores
    # per block instead of saving n_blocks of them
    state, _ = jax.lax.scan(jax.checkpoint(step), init,
                            jnp.arange(n_blocks))
    out = state_finalize(state).astype(q.dtype)          # [B, Hq, Sq, D]
    return jnp.swapaxes(out, 1, 2)
