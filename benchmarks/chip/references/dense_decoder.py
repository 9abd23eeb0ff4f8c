"""Plain reference for a dense decoder (GQA, optional qk-norm and sliding
window), and the weights that both the server and this reference run.

Written from the architecture's equations, importing nothing of the server:
pre-norm residual blocks, RMSNorm, half-split rotary embedding on the first
``rotary_dim`` channels of each head, grouped-query softmax attention under a
causal (and windowed) mask, a gated SiLU MLP, an untied output head. Every
matmul is float32 at ``highest`` precision, so the TPU runs it in full f32.
Attention is computed in blocks of queries against the whole key range, so an
8192-token sequence never holds a full score matrix.

``make_params`` lays the weights out as the server's dense ``TransformerLM``
stores them (layers stacked on a leading axis, float32 storage): that layout
is the interface between the two, and the harness checks it against the
server's own parameter shapes before it serves.

``precision="int8"`` and ``"fp8"`` are the check's controls, the step below
the server's bfloat16: the same forward with every projection's weights
rounded per output channel and its inputs per row (W8A8), and keys and
values per position and head, to symmetric int8 or to float8 e4m3 scaled to
its range.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "int8", "fp8")


def rotary_dim(m: dict) -> int:
    rd = int(m["head_dim"] * m.get("rotary_frac", 1.0))
    return rd - rd % 2


def make_params(m: dict, key: jax.Array) -> dict:
    """Seeded weights in the server's layout, float32. Norm weights are
    1 + 0.1 N(0, 1), so a path that drops one does not agree."""
    d, dh, hq, hkv, ff = (m["d_model"], m["head_dim"], m["n_heads"],
                          m["n_kv_heads"], m["d_ff"])
    n, v = m["n_layers"], m["vocab_size"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return jax.random.normal(next(ks), shape, jnp.float32) / math.sqrt(
            fan_in)

    def norm(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    attn = {"wq": mat((n, d, hq * dh), d), "wk": mat((n, d, hkv * dh), d),
            "wv": mat((n, d, hkv * dh), d), "wo": mat((n, hq * dh, d),
                                                     hq * dh)}
    if m["qk_norm"]:
        attn["qn"], attn["kn"] = norm((n, dh)), norm((n, dh))
    ffn = {"up": mat((n, d, ff), d), "down": mat((n, ff, d), ff)}
    if m["gated_mlp"]:
        ffn["gate"] = mat((n, d, ff), d)
    params = {"ln_f": norm((d,)),
              "embed": jax.random.normal(next(ks), (v, d), jnp.float32),
              "blocks": {"ln1": norm((n, d)), "attn": attn,
                         "ln2": norm((n, d)), "ffn": ffn}}
    if not m["tie_embeddings"]:
        params["unembed"] = mat((d, v), d)
    return params


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, base, rd):
    """x: [S, H, D]; rotate channel i with i + rd/2 for i < rd/2."""
    half = rd // 2
    freqs = base ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rd)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rd:]], -1)


def _int8(x, axis):
    """Symmetric int8 round trip with one absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fp8(x, axis):
    """float8 e4m3 round trip with one absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_ROUND = {"int8": _int8, "fp8": _fp8}


def _matmul(x, w, precision):
    if precision in _ROUND:
        x, w = _ROUND[precision](x, -1), _ROUND[precision](w, 0)
    return x @ w


def hidden(m: dict, params: dict, tokens: jax.Array, *,
           precision: str = "f32", q_block: int = 512) -> jax.Array:
    """tokens [S] (S a multiple of ``q_block``) -> final normed hidden
    states [S, d] f32. Padding at the end changes no earlier row."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    s = tokens.shape[0]
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    hq, hkv, dh, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], \
        m["norm_eps"]
    rd, window = rotary_dim(m), m.get("window")
    pos = jnp.arange(s)
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[m["act"]]

    def attention(q, k, v):
        # q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq*D], one query block a step
        kpos = pos[None, :]

        def block(_, start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
            qpos = (start + jnp.arange(q_block))[:, None]
            ok = kpos <= qpos
            if window is not None:
                ok &= kpos > qpos - window
            qg = qb.reshape(q_block, hkv, hq // hkv, dh)
            sc = jnp.einsum("qhgd,khd->hgqk", qg, k) / jnp.sqrt(
                jnp.float32(dh))
            p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
            out = jnp.einsum("hgqk,khd->qhgd", p, v)
            return None, out.reshape(q_block, hq * dh)

        _, out = jax.lax.scan(block, None, jnp.arange(0, s, q_block))
        return out.reshape(s, hq * dh)

    def layer(x, bp):
        a, f = bp["attn"], bp["ffn"]
        h = _rms_norm(x, bp["ln1"], eps)
        q = _matmul(h, a["wq"], precision).reshape(s, hq, dh)
        k = _matmul(h, a["wk"], precision).reshape(s, hkv, dh)
        v = _matmul(h, a["wv"], precision).reshape(s, hkv, dh)
        if m["qk_norm"]:
            q, k = _rms_norm(q, a["qn"], eps), _rms_norm(k, a["kn"], eps)
        if rd:
            q = _rope(q, pos, m["rope_base"], rd)
            k = _rope(k, pos, m["rope_base"], rd)
        if precision in _ROUND:
            k, v = _ROUND[precision](k, -1), _ROUND[precision](v, -1)
        x = x + _matmul(attention(q, k, v), a["wo"], precision)
        h = _rms_norm(x, bp["ln2"], eps)
        up = _matmul(h, f["up"], precision)
        up = (act(_matmul(h, f["gate"], precision)) * up if m["gated_mlp"]
              else act(up))
        return x + _matmul(up, f["down"], precision), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, params["embed"][tokens], params["blocks"])
        return _rms_norm(x, params["ln_f"], eps)


def logits(m: dict, params: dict, h: jax.Array, *,
           precision: str = "f32") -> jax.Array:
    """Output head over hidden rows h [N, d] -> [N, V] f32."""
    w = params["embed"].T if m["tie_embeddings"] else params["unembed"]
    with jax.default_matmul_precision("highest"):
        return _matmul(h, w, precision)
