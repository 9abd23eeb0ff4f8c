"""Operations and bytes the served model needs, from its shapes, and the
chip's published peaks.

``m`` is a configuration's ``model`` block (``configs/<name>.json``). Counts
are of what the algorithm needs, not of what the program happens to move:
bfloat16 weights read once per decode tick or prefill dispatch, keys and
values read at each row's own context length (inside the window) and written
once per new token, 2 operations per multiply-add. The server stores its
parameters in float32 and converts them on every dispatch; that traffic is
not needed, so it shows as a lower roofline share and is not counted here.
"""
from __future__ import annotations

BF16 = 2

# Published peaks per chip, keyed by jax's ``device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 matmul
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a device that is not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def _ctx(m: dict, length: int) -> int:
    w = m.get("window")
    return length if w is None else min(length, w)


def layer_weights(m: dict) -> int:
    """Projection weights of one layer (attention and MLP)."""
    d, dh, hq, hkv, ff = (m["d_model"], m["head_dim"], m["n_heads"],
                          m["n_kv_heads"], m["d_ff"])
    mlp = (3 if m["gated_mlp"] else 2) * d * ff
    return 2 * d * hq * dh + 2 * d * hkv * dh + mlp


def head_weights(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def projection(m: dict, tokens: int) -> tuple[float, float]:
    """(flops, bytes) of every layer's projections over ``tokens`` rows
    (bf16 weights read once, activations ignored)."""
    w = m["n_layers"] * layer_weights(m)
    return 2.0 * w * tokens, float(BF16 * w)


def head(m: dict, rows: int) -> tuple[float, float]:
    """(flops, bytes) of the output head over ``rows`` rows."""
    w = head_weights(m)
    return 2.0 * w * rows, float(BF16 * w)


def attention(m: dict, q_positions: list[int] | range) -> tuple[float, float]:
    """(flops, bytes) of self attention, all layers, for queries at the
    given 0-based positions of one sequence: query i attends to the
    ``min(i + 1, window)`` keys before it and itself. Bytes: the keys and
    values read once for the call (the longest query's context)."""
    hq, hkv, dh, n = m["n_heads"], m["n_kv_heads"], m["head_dim"], \
        m["n_layers"]
    ctx = sum(_ctx(m, i + 1) for i in q_positions)
    longest = _ctx(m, max(q_positions) + 1) if len(q_positions) else 0
    return (4.0 * hq * dh * ctx * n,
            float(2 * hkv * dh * BF16 * longest * n))


def kv_write(m: dict, tokens: int) -> float:
    return float(2 * m["n_kv_heads"] * m["head_dim"] * BF16 * tokens
                 * m["n_layers"])


def decode_tick(m: dict, positions: list[int]) -> tuple[float, float]:
    """(flops, bytes) of one decode tick whose live rows each feed one token
    at the given 0-based position (the row's cache then holds position + 1
    keys). Weights are read once for the tick, each row's keys and values
    at its own length."""
    rows = len(positions)
    fl, by = projection(m, rows)
    hf, hb = head(m, rows)
    fl, by = fl + hf, by + hb
    for p in positions:
        af, ab = attention(m, [p])
        fl, by = fl + af, by + ab
    return fl, by + kv_write(m, rows)


def prefill_chunk(m: dict, offset: int, tokens: int,
                  last_row: bool) -> tuple[float, float]:
    """(flops, bytes) of one prompt chunk of ``tokens`` rows at ``offset``;
    the head runs only for the prompt's last chunk."""
    fl, by = projection(m, tokens)
    af, ab = attention(m, range(offset, offset + tokens))
    fl, by = fl + af, by + ab + kv_write(m, tokens)
    if last_row:
        hf, hb = head(m, 1)
        fl, by = fl + hf, by + hb
    return fl, by


def bound_seconds(flops: float, bytes_: float, peak: dict) -> tuple[float,
                                                                    str]:
    """The least time the chip could take, and which peak sets it."""
    tc = flops / peak["flops_per_s"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
