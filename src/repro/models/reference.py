"""Plain f32 reference forward for the dense decoder family.

A straightforward implementation of what :class:`TransformerLM` computes for
``family == "dense"``, written without any of the model's own code: a scan
over layers, a full softmax over the causal (and windowed) score matrix, no
blockwise attention, no caches, no serving machinery, every matmul at f32
with the highest precision. Served logits are checked against it:
``tests/test_reference.py`` on CPU at reduced sizes, ``chip_smoke.py`` on the
chip at published widths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig

_ACT = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, base: float, rotary_dim: int):
    """Half-split rotary embedding on the first ``rotary_dim`` channels of
    x: [S, H, D] at ``positions`` [S]."""
    half = rotary_dim // 2
    freqs = base ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs   # [S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rotary_dim:]], axis=-1)


def dense_reference_logits(cfg: ModelConfig, params: dict,
                           tokens: jax.Array) -> jax.Array:
    """tokens: [S] int32 -> logits [S, V] f32 for one unpadded sequence."""
    if cfg.family != "dense":
        raise ValueError(f"{cfg.name}: the reference covers the dense "
                         f"family only, not {cfg.family!r}")
    f32 = lambda a: jnp.asarray(a, jnp.float32)               # noqa: E731
    act = _ACT[cfg.act]
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    s = tokens.shape[0]
    pos = jnp.arange(s)
    allowed = pos[None, :] <= pos[:, None]                    # [query, key]
    if cfg.window is not None:
        allowed &= pos[None, :] > pos[:, None] - cfg.window

    def layer(x, bp):
        a = bp["attn"]
        h = _rms_norm(x, f32(bp["ln1"]), cfg.norm_eps)
        q = (h @ f32(a["wq"])).reshape(s, hq, dh)
        k = (h @ f32(a["wk"])).reshape(s, hkv, dh)
        v = (h @ f32(a["wv"])).reshape(s, hkv, dh)
        if cfg.qk_norm:
            q = _rms_norm(q, f32(a["qn"]), cfg.norm_eps)
            k = _rms_norm(k, f32(a["kn"]), cfg.norm_eps)
        if cfg.rotary_dim:
            q = _rope(q, pos, cfg.rope_base, cfg.rotary_dim)
            k = _rope(k, pos, cfg.rope_base, cfg.rotary_dim)
        qg = q.reshape(s, hkv, hq // hkv, dh)
        scores = jnp.einsum("qhgd,khd->hgqk", qg, k) / jnp.sqrt(
            jnp.float32(dh))
        scores = jnp.where(allowed, scores, -jnp.inf)
        out = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, -1), v)
        x = x + out.reshape(s, hq * dh) @ f32(a["wo"])
        f = bp["ffn"]
        h = _rms_norm(x, f32(bp["ln2"]), cfg.norm_eps)
        up = h @ f32(f["up"])
        up = act(h @ f32(f["gate"])) * up if cfg.gated_mlp else act(up)
        return x + up @ f32(f["down"]), None

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = _rms_norm(x, f32(params["ln_f"]), cfg.norm_eps)
        w = (f32(params["embed"]).T if cfg.tie_embeddings
             else f32(params["unembed"]))
        return x @ w
