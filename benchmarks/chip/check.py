"""Whether what the timed path served is right: served tokens against the
plain reference.

After the window has closed, a sample of the requests it finished, drawn from
the seed and with the longest among them, is run once through the
configuration's plain f32 reference (``references/<name>.py``) over its prompt
and served tokens. For every served token the number compared is the gap by
which the reference's logit of that token lies below the reference's best
logit at that position. Two numbers are compared, each with its limit in
``checks/<cell>.json``: the widest gap in the sample, and the mean gap over
its served tokens (0 where the served token is the reference's best). The tokens come from the served
path: the first from ``prefill_chunks_batched``, the rest from the
``decode_multi`` blocks at the cell's K, at the cell's lengths and with as
many slots in use as the window had.

The controls put the reference in the server's place at the precisions
below the server's bfloat16: int8 and float8 e4m3 (W8A8 projections, keys
and values, each scaled to the format's range). At each position of the same
prompts and tokens each reads the gap of the token that its forward puts
first. The int8 control sets the mean gap's upper reading; its widest gap
lies too near the served one's, so the fp8 control sets the widest gap's.
Controls run only on request (``calibrate.py``), never in a benchmark run.
"""
from __future__ import annotations

import functools

import numpy as np

Q_BLOCK = 512              # sequences are padded to a multiple of this


def sample(records: list, seed: int, min_tokens: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample holds ``min_tokens`` served tokens."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.n, r.n))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, total = [longest], longest.n
    for i in order:
        if total >= min_tokens:
            break
        out.append(rest[i])
        total += rest[i].n
    return out


def _gaps(ref, m, controls, params, toks, start, served, n):
    """Gaps at rows start .. start+R-1 of one padded sequence, of the served
    tokens and of each control's first choice; rows past ``n`` read 0."""
    import jax.numpy as jnp
    r = served.shape[0]
    rows = jnp.minimum(start + jnp.arange(r), toks.shape[0] - 1)
    lg = ref.logits(m, params, ref.hidden(m, params, toks)[rows])
    best = lg.max(-1)
    valid = jnp.arange(r) < n
    gap = jnp.where(valid, best - jnp.take_along_axis(
        lg, served[:, None], -1)[:, 0], 0.0)
    out = [gap]
    for prec in controls:
        hc = ref.hidden(m, params, toks, precision=prec)[rows]
        pick = ref.logits(m, params, hc, precision=prec).argmax(-1)
        out.append(jnp.where(valid, best - jnp.take_along_axis(
            lg, pick[:, None], -1)[:, 0], 0.0))
    return out


def compare(ref, m: dict, params, picked: list, seq_len: int,
            out_len: int, controls: tuple = ()) -> dict:
    """Widest and mean gap of the served tokens over the sampled requests,
    and of each control precision's first choices (``control_<p>_...``).
    ``seq_len``/``out_len``: the mix's longest prompt plus output and
    longest output, so that one program serves every request."""
    import jax
    import jax.numpy as jnp
    s = -(-seq_len // Q_BLOCK) * Q_BLOCK
    fn = jax.jit(functools.partial(_gaps, ref, m, tuple(controls)))
    names = ["", *(f"control_{p}_" for p in controls)]
    widest, total, n_tok = [0.0] * len(names), [0.0] * len(names), 0
    for r in picked:
        seq = np.zeros(s, np.int32)
        full = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        seq[:len(full)] = full
        toks = np.zeros(out_len, np.int32)
        toks[:r.n] = r.tokens
        gaps = fn(params, jnp.asarray(seq), jnp.int32(len(r.prompt) - 1),
                  jnp.asarray(toks), jnp.int32(r.n))
        for i, g in enumerate(gaps):
            g = np.asarray(g, np.float64)
            widest[i], total[i] = max(widest[i], g.max()), total[i] + g.sum()
        n_tok += r.n
    out = {"requests": len(picked), "tokens": n_tok}
    for i, name in enumerate(names):
        out[name + "max_logit_gap"] = float(widest[i])
        out[name + "mean_logit_gap"] = total[i] / max(1, n_tok)
    return out
