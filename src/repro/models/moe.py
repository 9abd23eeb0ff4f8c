"""Token-choice top-k MoE with capacity-based scatter dispatch.

Dispatch avoids the GShard ``[tokens, E, C]`` one-hot blowup: position-in-expert
comes from a cumulative sum over the ``[T, E]`` assignment one-hot, tokens
scatter into an ``[E, C, d]`` buffer (expert-parallel: E shards over the
``model`` mesh axis; the scatter/gather is where the all-to-all lives), experts
run as one batched einsum, results gather back with router weights.

Over-capacity tokens drop (standard GShard semantics, ``capacity_factor``
controls head-room); the smoke tests compare against a dense loop-over-experts
reference on under-capacity inputs where the two agree exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import act_fn, dense_init


def _maybe_constrain(x: jax.Array, *axes):
    """Sharding constraint against the ambient mesh context. ``axes`` are
    mesh-axis names or None, one per array dim. GSPMD pads non-divisible
    internal values, so no divisibility guard is needed here. No-op on
    meshless (single-device test) traces, where the raw PartitionSpec can't
    resolve."""
    try:
        return jax.lax.with_sharding_constraint(x, P(*axes))
    except Exception:
        return x


def moe_init(key, d_model: int, d_ff: int, n_experts: int, gated: bool = True,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    def stack(k, din, dout):
        keys = jax.random.split(k, n_experts)
        return jnp.stack([dense_init(kk, din, dout, dtype) for kk in keys])
    p = {
        "router": dense_init(ks[0], d_model, n_experts, dtype),
        "up": stack(ks[1], d_model, d_ff),
        "down": stack(ks[2], d_ff, d_model),
    }
    if gated:
        p["gate"] = stack(ks[3], d_model, d_ff)
    return p


def _route(xf: jax.Array, router: jax.Array, top_k: int):
    """Router: top-k expert ids + renormalized weights + Switch aux loss."""
    e = router.shape[-1]
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)                     # [T, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    assign1 = jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(assign1, axis=0) * jnp.mean(probs, axis=0))
    return top_e, top_w, aux


def _queue_positions(top_e: jax.Array, e: int, c: int):
    """Position of each (token, slot) in its expert queue + keep mask."""
    flat_e = top_e.reshape(-1)                                  # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                        # exclusive count
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < c
    return flat_e, pos_in_e, keep


def _expert_ffn(p: dict, buf: jax.Array, act: str, gated: bool,
                dtype) -> jax.Array:
    """buf: [E?, C, d] -> [E?, C, d] batched expert einsums."""
    up = jnp.einsum("ecd,edf->ecf", buf, p["up"].astype(dtype))
    if gated:
        up = act_fn(act)(jnp.einsum("ecd,edf->ecf", buf,
                                    p["gate"].astype(dtype))) * up
    else:
        up = act_fn(act)(up)
    return jnp.einsum("ecf,efd->ecd", up, p["down"].astype(dtype))


def _dispatch_ffn_combine(p, xf, top_e, top_w, *, e_lo: int, e_loc: int,
                          c: int, top_k: int, act: str, gated: bool):
    """Scatter tokens into the [e_lo, e_lo+e_loc) expert queues, run those
    experts, gather weighted results back to token rows. Pure local math —
    used directly on one device and inside the shard_map EP region (where
    each model shard owns a contiguous expert range)."""
    t, d = xf.shape
    e_total = p["router"].shape[-1]
    flat_e, pos_in_e, keep = _queue_positions(top_e, e_total, c)
    mine = keep & (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
    slot = jnp.where(mine, (flat_e - e_lo) * c + pos_in_e, e_loc * c)

    xe = jnp.repeat(xf, top_k, axis=0) if top_k > 1 else xf     # [T*k, d]
    buf = jnp.zeros((e_loc * c + 1, d), xf.dtype).at[slot].add(xe)
    buf = buf[: e_loc * c].reshape(e_loc, c, d)

    out = _expert_ffn(p, buf, act, gated, xf.dtype)             # [E_loc, C, d]

    out_flat = out.reshape(e_loc * c, d)
    gathered = jnp.where(mine[:, None],
                         out_flat[jnp.minimum(slot, e_loc * c - 1)], 0.0)
    w = top_w.reshape(-1)[:, None].astype(xf.dtype)
    return (gathered * w).reshape(t, top_k, d).sum(axis=1)      # [T, d]


def moe_apply(p: dict, x: jax.Array, *, top_k: int, act: str = "silu",
              gated: bool = True, capacity_factor: float = 1.25,
              capacity: int | None = None) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y: [B, S, d], aux_loss: scalar load-balance loss).

    Under an active DistContext (launchers) this routes to the shard_map EP
    path — GSPMD cannot shard the dispatch scatter ("involuntary full
    rematerialization"), so expert parallelism is explicit: each model shard
    owns E/ep experts, dispatch/FFN/combine run shard-local, and one psum
    over the model axis merges the expert-partial token outputs."""
    from repro.distributed.context import get_context
    ctx = get_context()
    b, s, d = x.shape
    e = p["router"].shape[-1]

    if (ctx.active and capacity is None and ctx.model_axis is not None
            and e % ctx.axis_size(ctx.model_axis) == 0
            and b % ctx.axis_size(ctx.batch_axes) == 0):
        return _moe_apply_ep(p, x, top_k=top_k, act=act, gated=gated,
                             capacity_factor=capacity_factor, ctx=ctx)

    t = b * s
    xf = x.reshape(t, d)
    top_e, top_w, aux = _route(xf, p["router"], top_k)
    c = capacity if capacity is not None else max(
        int(t * top_k / e * capacity_factor), 8)
    y = _dispatch_ffn_combine(p, xf, top_e, top_w, e_lo=0, e_loc=e, c=c,
                              top_k=top_k, act=act, gated=gated)
    return y.reshape(b, s, d), aux


def _moe_apply_ep(p, x, *, top_k, act, gated, capacity_factor, ctx):
    """Expert-parallel MoE via shard_map (see moe_apply docstring).

    Token batch stays sharded over the batch axes; every model shard sees the
    same tokens (router math is replicated — cheap) but scatters/runs only
    its own expert slice; the combine is one psum of [T_loc, d] per layer.
    Capacity is per (data shard, expert): C = T_loc*k/E*cf, which matches the
    global-path capacity in expectation."""
    import jax.sharding as jsh
    P = jsh.PartitionSpec
    b, s, d = x.shape
    e = p["router"].shape[-1]
    ep = ctx.axis_size(ctx.model_axis)
    dp = ctx.axis_size(ctx.batch_axes)
    e_loc = e // ep
    b_loc = b // dp
    t_loc = b_loc * s
    c = max(int(t_loc * top_k / e * capacity_factor), 8)
    bd = ctx.batch_axes if dp > 1 else None

    has_gate = gated and "gate" in p

    def shard_fn(x_s, router, *experts):
        pl = {"router": router, "up": experts[0], "down": experts[1]}
        if has_gate:
            pl["gate"] = experts[2]
        m_idx = jax.lax.axis_index(ctx.model_axis)
        e_lo = m_idx * e_loc
        xf = x_s.reshape(t_loc, d)
        top_e, top_w, aux = _route(xf, router, top_k)
        y = _dispatch_ffn_combine(pl, xf, top_e, top_w, e_lo=e_lo,
                                  e_loc=e_loc, c=c, top_k=top_k, act=act,
                                  gated=gated)
        y = jax.lax.psum(y, ctx.model_axis)       # combine expert partials
        if bd:
            aux = jax.lax.pmean(aux, bd)
        return y.reshape(b_loc, s, d), aux

    espec = P(ctx.model_axis, None, None)
    operands = [x, p["router"], p["up"], p["down"]]
    in_specs = [P(bd, None, None), P(), espec, espec]
    if has_gate:
        operands.append(p["gate"])
        in_specs.append(espec)
    y, aux = jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(bd, None, None), P()),
        check_vma=False,
    )(*operands)
    return y, aux


def moe_apply_rowwise(p: dict, x: jax.Array, *, top_k: int, act: str = "silu",
                      gated: bool = True) -> tuple[jax.Array, jax.Array]:
    """Capacity-free per-row top-k dispatch: x [T, d] -> (y [T, d], aux).

    Each row dense-gathers its own k expert weight matrices and runs them as
    a [T, k]-batched einsum — no expert queue, no capacity, and therefore no
    cross-row coupling: a row's output depends only on that row. That is the
    property ragged continuous batching needs (per-request equivalence must
    hold while slot membership changes every step — and, under multi-tick
    decode (``TransformerLM.decode_multi``), while rows retire *mid-scan*:
    a parked row's garbage routing can't steal capacity from live rows
    because there is no capacity to steal), and at decode batch sizes
    (T = n_slots) the gather of k·(2-3)·d·d_ff weights is cheaper than
    materializing the [E, C, d] queue buffer. The math matches the capacity
    path exactly whenever that path drops nothing."""
    t, d = x.shape
    top_e, top_w, aux = _route(x, p["router"], top_k)           # [T, k]
    up = jnp.einsum("td,tkdf->tkf", x, p["up"][top_e].astype(x.dtype))
    if gated:
        up = act_fn(act)(jnp.einsum("td,tkdf->tkf", x,
                                    p["gate"][top_e].astype(x.dtype))) * up
    else:
        up = act_fn(act)(up)
    y = jnp.einsum("tkf,tkfd->tkd", up, p["down"][top_e].astype(x.dtype))
    y = (y * top_w[..., None].astype(x.dtype)).sum(axis=1)
    return y, aux


def moe_apply_dense_ref(p: dict, x: jax.Array, *, top_k: int, act: str = "silu",
                        gated: bool = True) -> jax.Array:
    """Dense loop-over-experts oracle (no capacity drops). Test-only."""
    b, s, d = x.shape
    e = p["router"].shape[-1]
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    y = jnp.zeros_like(xf)
    for ei in range(e):
        up = xf @ p["up"][ei]
        if gated:
            up = act_fn(act)(xf @ p["gate"][ei]) * up
        else:
            up = act_fn(act)(up)
        oi = up @ p["down"][ei]
        wi = jnp.sum(jnp.where(top_e == ei, top_w, 0.0), axis=-1)[:, None]
        y = y + oi * wi.astype(x.dtype)
    return y.reshape(b, s, d)
