"""Generic decoder-only transformer covering the dense / moe / vlm / hybrid
families (whisper composes two of these stacks — see whisper.py).

Layer stacks are ``lax.scan``s over parameter pytrees with a leading ``[L]``
axis, so 100-layer configs lower to compact HLO. VLM-style dedicated
cross-attention layers (every Nth layer) scan over *groups* of
``(cross_attn_every - 1) self + 1 cross`` layers.

Decode (the paper's workload) maintains a KV cache ``[L, B, Smax, Hkv, Dh]``;
keys are cached *post-RoPE* (paper §IV-C) and the query/key rotation for the
new token uses the incremental Eq. 11 recurrence carried in the cache
(``rope_mode="incremental"``) or direct cos/sin (``"direct"``). The decode
layer loop does not slice and restack the cache: the whole stack (and an
int8 cache's scale planes) rides the loop's carry, each layer writes its new
token into it in place at ``(layer, row, position)``, and attention reads
that layer back out of the carry.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import attention as attn_lib
from repro.core import rope as rope_lib
from repro.core.quantization import quantize_kv
from .config import ModelConfig
from .layers import (batch_vocab_constrain, dense_init, embed_init, linear,
                     mlp_apply, mlp_init, rms_norm)
from . import mamba as mamba_lib
from . import moe as moe_lib
from . import rwkv6 as rwkv_lib

Params = dict[str, Any]
Cache = dict[str, Any]

# ``jax.named_scope`` names of the serving programs' sublayers
# (``decode_step``/``decode_multi`` and ``prefill_chunk``). They change op
# metadata only: a device trace's ops carry them in their ``tf_op`` path.
# embed: the token lookup; qkv: ln1, q/k/v projections, qk-norm, rope;
# kv_write: the cache writes; attention: the attention read; attn_out: wo
# and its residual; mlp: ln2, the MLP or MoE, and its residual; lm_head:
# ln_f and the unembedding; sample: the token pick, the finite check and
# the on-device retirement (decode only). Cross-attention reads and the
# hybrid family's Mamba branch stay outside every scope.
SCOPE_NAMES = ("embed", "qkv", "kv_write", "attention", "attn_out", "mlp",
               "lm_head", "sample")


def seeded_gumbel_pick(rng_key: jax.Array, logits: jax.Array,
                       serial: jax.Array, token_idx: jax.Array,
                       temperature: float) -> jax.Array:
    """One exact softmax(logits/temperature) draw as Gumbel-max, keyed on
    ``(rng_key, serial, token_idx)`` — request-intrinsic, so the draw for a
    request's token i cannot depend on batch composition, scheduling, or
    the decode tick horizon. The single definition is shared by the fused
    multi-tick decode (:meth:`TransformerLM.decode_multi`, tokens 1..n) and
    the serving engine's prefill first-token pick (token 0): both sides of
    a request's stream MUST come from this one key derivation."""
    key = jax.random.fold_in(jax.random.fold_in(rng_key, serial), token_idx)
    g = jax.random.gumbel(key, logits.shape, logits.dtype)
    return jnp.argmax(logits / temperature + g).astype(jnp.int32)


def make_remat(cfg: ModelConfig):
    """Layer-boundary rematerialization with a configurable policy:
    'full' recomputes everything (min memory), 'dots' saves matmul outputs
    (halves the recompute FLOPs/bytes at higher live memory)."""
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return lambda f: jax.checkpoint(f, policy=pol)
    return jax.checkpoint


def layer_scan(step, carry, xs, *, unroll: bool):
    """``lax.scan`` over a stacked-layer pytree, or a Python unroll.

    Unrolling exists for the dry-run cost model: XLA's ``cost_analysis``
    counts a while-loop body once, so scanned stacks under-report FLOPs /
    bytes / collective traffic by a factor of L. Runtime paths keep the scan
    (compact HLO); the dry-run lowers with ``cfg.unroll_layers=True``.
    """
    if not unroll:
        return jax.lax.scan(step, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = step(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        stacked = None
    return carry, stacked


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * dh),
        "wk": dense_init(ks[1], d, hkv * dh),
        "wv": dense_init(ks[2], d, hkv * dh),
        "wo": dense_init(ks[3], hq * dh, d),
    }
    if cfg.qk_norm:
        p["qn"] = jnp.ones((dh,), jnp.float32)
        p["kn"] = jnp.ones((dh,), jnp.float32)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)  # gated cross-attn (llama-vision)
    return p


def _ffn_init(key, cfg: ModelConfig) -> Params:
    if cfg.n_experts:
        return moe_lib.moe_init(key, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                gated=cfg.gated_mlp)
    return mlp_init(key, cfg.d_model, cfg.d_ff, cfg.gated_mlp)


def _self_block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": _attn_init(ks[0], cfg),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "ffn": _ffn_init(ks[1], cfg),
    }
    if cfg.family == "hybrid":
        p["mamba"] = mamba_lib.mamba_init(ks[2], cfg.d_model, state=cfg.ssm_state,
                                          conv=cfg.ssm_conv, expand=cfg.ssm_expand)
        p["ln_attn_out"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["ln_mamba_out"] = jnp.ones((cfg.d_model,), jnp.float32)
    if cfg.cross_attn_every == 1:   # whisper-style: cross-attn inside the layer
        p["ln_cross"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["cross"] = _attn_init(ks[3], cfg, cross=True)
    return p


def _cross_block_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "cross": _attn_init(ks[0], cfg, cross=True),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "ffn": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp),
    }


class TransformerLM:
    """cfg.family in {dense, moe, hybrid, vlm, ssm}. ``ssm`` -> RWKV6 stack."""

    def __init__(self, cfg: ModelConfig, *, causal: bool = True,
                 with_embedding: bool = True):
        self.cfg = cfg
        self.causal = causal
        self.with_embedding = with_embedding

    # ---- init ------------------------------------------------------------
    def init_params(self, rng) -> Params:
        cfg = self.cfg
        k_embed, k_blocks, k_cross, k_out = jax.random.split(rng, 4)
        params: Params = {"ln_f": jnp.ones((cfg.d_model,), jnp.float32)}
        if self.with_embedding:
            params["embed"] = embed_init(k_embed, cfg.vocab_size, cfg.d_model)
            if not cfg.tie_embeddings:
                params["unembed"] = dense_init(k_out, cfg.d_model, cfg.vocab_size)

        if cfg.family == "ssm":
            keys = jax.random.split(k_blocks, cfg.n_layers)
            params["blocks"] = jax.vmap(
                lambda k: {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                           "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                           "mix": rwkv_lib.rwkv_layer_init(
                               k, cfg.d_model, cfg.d_ff, cfg.rwkv_head_dim)})(keys)
            return params

        n_cross = self._n_cross_groups()
        n_self = cfg.n_layers - n_cross
        keys = jax.random.split(k_blocks, n_self)
        params["blocks"] = jax.vmap(lambda k: _self_block_init(k, cfg))(keys)
        if n_cross:
            ckeys = jax.random.split(k_cross, n_cross)
            params["cross_blocks"] = jax.vmap(
                lambda k: _cross_block_init(k, cfg))(ckeys)
        return params

    def _n_cross_groups(self) -> int:
        cfg = self.cfg
        if cfg.cross_attn_every > 1:          # vlm: dedicated cross layers
            return cfg.n_layers // cfg.cross_attn_every
        return 0

    # ---- shared attention math --------------------------------------------
    def _qkv(self, p: Params, x: jax.Array):
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        b, s, _ = x.shape
        q = linear(p, "wq", x).reshape(b, s, cfg.n_heads, dh)
        k = linear(p, "wk", x).reshape(b, s, cfg.n_kv_heads, dh)
        v = linear(p, "wv", x).reshape(b, s, cfg.n_kv_heads, dh)
        if cfg.qk_norm:
            q = rms_norm(q, p["qn"], cfg.norm_eps)
            k = rms_norm(k, p["kn"], cfg.norm_eps)
        return q, k, v

    def _qkv_rope(self, p: Params, x: jax.Array, positions: jax.Array):
        """Projection + qk-norm + direct RoPE for a [B, S, d] sequence —
        shared by full-sequence attention and chunked slot prefill (keys
        leave here post-RoPE, paper §IV-C)."""
        cfg = self.cfg
        q, k, v = self._qkv(p, x)
        if cfg.rotary_dim:
            rot = functools.partial(rope_lib.apply_rope, base=cfg.rope_base,
                                    rotary_dim=cfg.rotary_dim)
            q = jnp.swapaxes(rot(jnp.swapaxes(q, 1, 2), positions), 1, 2)
            k = jnp.swapaxes(rot(jnp.swapaxes(k, 1, 2), positions), 1, 2)
        return q, k, v

    def _ffn_out(self, bp: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """ln2 + (MoE | MLP) block tail, shared by the training block, the
        prefill step, and chunked slot prefill. Returns (y, moe aux)."""
        cfg = self.cfg
        h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            return moe_lib.moe_apply(bp["ffn"], h2, top_k=cfg.top_k,
                                     act=cfg.act, gated=cfg.gated_mlp,
                                     capacity_factor=cfg.capacity_factor)
        return (mlp_apply(bp["ffn"], h2, cfg.act, cfg.gated_mlp),
                jnp.zeros((), jnp.float32))

    def _self_attn_full(self, p: Params, x: jax.Array,
                        positions: jax.Array,
                        kv_length: jax.Array | None = None) -> jax.Array:
        """Full-sequence self attention (training / encoder). ``kv_length``:
        optional [B] valid prefix — a bidirectional encoder run over padded
        inputs masks keys past each row's true length so valid positions'
        outputs are independent of the padding (queries at padded positions
        produce garbage that downstream reads mask out)."""
        cfg = self.cfg
        b, s, _ = x.shape
        q, k, v = self._qkv_rope(p, x, positions)
        out = attn_lib.prefill_attention(q, k, v, causal=self.causal,
                                         window=cfg.window,
                                         kv_lengths=kv_length,
                                         kv_block=cfg.attn_block or 512)
        return linear(p, "wo", out.reshape(b, s, -1))

    def _cross_attn_full(self, p: Params, x: jax.Array,
                         source: jax.Array) -> jax.Array:
        """Cross attention to a stub-frontend source sequence (no RoPE)."""
        b, s, _ = x.shape
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        q, _, _ = self._qkv(p, x)
        k = linear(p, "wk", source).reshape(
            b, source.shape[1], cfg.n_kv_heads, dh)
        v = linear(p, "wv", source).reshape(
            b, source.shape[1], cfg.n_kv_heads, dh)
        if cfg.qk_norm:
            k = rms_norm(k, p["kn"], cfg.norm_eps)
        out = attn_lib.prefill_attention(q, k, v, causal=False,
                                         kv_block=cfg.attn_block or 512)
        out = linear(p, "wo", out.reshape(b, s, -1))
        return jnp.tanh(p["gate"]).astype(x.dtype) * out

    @staticmethod
    def _seq_shard(x: jax.Array):
        """Megatron-style sequence-sharded residual stream (train path):
        constrain [B, S, d] activations to (batch over DP, S over model)
        between blocks. GSPMD then reduce-scatters the row-parallel partial
        sums in bf16 *before* the f32 norm region and all-gathers before the
        next matmul — replacing f32 activation all-reduces with bf16 RS+AG
        (half the ICI bytes) and sharding the norm compute (§Perf)."""
        from repro.distributed.context import get_context
        ctx = get_context()
        if not ctx.active or x.ndim != 3 or x.shape[1] == 1:
            return x
        bd = ctx.batch_axes if x.shape[0] % ctx.axis_size(ctx.batch_axes) == 0 \
            else None
        s_ax = ctx.model_axis if x.shape[1] % ctx.axis_size(ctx.model_axis) == 0 \
            else None
        try:
            from jax.sharding import PartitionSpec as P
            return jax.lax.with_sharding_constraint(x, P(bd, s_ax, None))
        except Exception:
            return x

    # ---- full-sequence blocks (training / prefill math) --------------------
    def _self_block(self, p: Params, x: jax.Array, positions: jax.Array,
                    source: jax.Array | None,
                    kv_length: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        attn_out = self._self_attn_full(p["attn"], h, positions, kv_length)
        if cfg.family == "hybrid":
            mamba_out = mamba_lib.mamba_forward(p["mamba"], h)
            mixed = 0.5 * (rms_norm(attn_out, p["ln_attn_out"], cfg.norm_eps)
                           + rms_norm(mamba_out, p["ln_mamba_out"], cfg.norm_eps))
            x = x + mixed
        else:
            x = x + attn_out
        if "cross" in p and source is not None:   # whisper-style in-layer cross
            x = x + self._cross_attn_full(
                p["cross"], rms_norm(x, p["ln_cross"], cfg.norm_eps), source)
        y, aux = self._ffn_out(p, x)
        return self._seq_shard(x + y), aux

    def _cross_block(self, p: Params, x: jax.Array,
                     source: jax.Array | None) -> jax.Array:
        cfg = self.cfg
        if source is not None:
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            x = x + self._cross_attn_full(p["cross"], h, source)
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        return self._seq_shard(x + mlp_apply(p["ffn"], h2, cfg.act,
                                             cfg.gated_mlp))

    # ---- forward (training) -----------------------------------------------
    def forward(self, params: Params, tokens: jax.Array | None = None, *,
                embeds: jax.Array | None = None,
                source: jax.Array | None = None,
                kv_length: jax.Array | None = None,
                remat: bool = True) -> tuple[jax.Array, jax.Array]:
        """Returns (hidden-or-logits [B,S,*], moe aux loss). ``tokens`` XOR
        ``embeds``; ``source``: [B, S_src, d] stub-frontend features;
        ``kv_length``: [B] valid input prefix for masked (padded) encoder
        runs — see :meth:`_self_attn_full`."""
        cfg = self.cfg
        x = (params["embed"].astype(self._dt)[tokens] if embeds is None
             else embeds.astype(self._dt))
        b, s, _ = x.shape
        positions = jnp.arange(s)

        if cfg.family == "ssm":
            x, aux = self._rwkv_forward(params, x, remat=remat)
        else:
            n_cross = self._n_cross_groups()
            group = cfg.cross_attn_every if n_cross else 0

            def self_step(carry, bp):
                x, aux = carry
                x, a = self._self_block(bp, x, positions, source, kv_length)
                return (x, aux + a), None

            step = make_remat(cfg)(self_step) if remat else self_step

            if not n_cross:
                (x, aux), _ = layer_scan(step, (x, 0.0), params["blocks"], unroll=cfg.unroll_layers)
            else:
                n_self_per = group - 1

                def group_step(carry, gp):
                    sp, cp = gp
                    (x, aux), _ = layer_scan(step, carry, sp, unroll=cfg.unroll_layers)
                    x = self._cross_block(cp, x, source)
                    return (x, aux), None

                gstep = make_remat(cfg)(group_step) if remat else group_step
                # reshape self blocks [n_self] -> [n_cross, n_self_per]
                sp = jax.tree.map(
                    lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:]),
                    params["blocks"])
                (x, aux), _ = layer_scan(gstep, (x, 0.0),
                                         (sp, params["cross_blocks"]),
                                         unroll=cfg.unroll_layers)

        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._unembed(params, x), aux

    def _rwkv_forward(self, params, x, remat: bool = True):
        cfg = self.cfg
        b = x.shape[0]

        def step(carry, bp):
            x = carry
            st = rwkv_lib.RWKVLayerState(
                x_prev_att=jnp.zeros((b, cfg.d_model), x.dtype),
                x_prev_ffn=jnp.zeros((b, cfg.d_model), x.dtype),
                wkv=jnp.zeros((b, cfg.d_model // cfg.rwkv_head_dim,
                               cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32))
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            y, st = rwkv_lib.rwkv_time_mix(bp["mix"], h, st, cfg.rwkv_head_dim)
            x = x + y
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            y2, _ = rwkv_lib.rwkv_channel_mix(bp["mix"], h2, st)
            return x + y2, None

        step_fn = make_remat(cfg)(step) if remat else step
        x, _ = layer_scan(step_fn, x, params["blocks"],
                          unroll=cfg.unroll_layers)
        return x, jnp.zeros((), jnp.float32)

    @property
    def _dt(self):
        return jnp.dtype(self.cfg.compute_dtype)

    def _unembed(self, params: Params, x: jax.Array) -> jax.Array:
        if not self.with_embedding:
            return x
        w = (params["embed"].T if self.cfg.tie_embeddings
             else params["unembed"])
        logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
        # pin (batch over DP, vocab over model): see layers.batch_vocab_constrain
        return batch_vocab_constrain(logits)

    # =======================================================================
    # Serving: KV cache init / prefill / decode_step
    # =======================================================================
    def init_cache(self, batch: int, max_len: int,
                   source_len: int | None = None, *,
                   n_sources: int | None = None,
                   chunk: int | None = None,
                   kv_dtype=None) -> Cache:
        """Preallocated decode state. KV tensors [L, B, Smax, Hkv, Dh] in the
        KV storage dtype; per-row lengths; incremental-RoPE angle state
        (Eq. 11); family-specific recurrent states.

        ``kv_dtype``: storage dtype for the self-attention KV cache.
        Defaults to ``int8`` for ``+w4a8`` configs (``cfg.w4a8_serve``),
        else the compute dtype — the old behavior *assumed* compute dtype
        everywhere, which is exactly the latent coupling this parameter
        removes. An int8 cache additionally allocates per-(layer, slot,
        head, position) **bf16** dequant scales ``k_scale/v_scale
        [L, B, Hkv, Smax]`` (position last: it is the blocked axis every
        consumer tiles over) plus pooled-source twins
        ``src_k_scale/src_v_scale [Lc, E, Hkv, S_src]`` when a source-KV
        pool exists. Scales are computed in f32 and stored bf16 — the
        per-Dh-element overhead halves to 2 bytes, so the int8 footprint
        is ``0.25 + 0.5/Dh`` of fp32 (vs ``0.25 + 1/Dh`` with f32
        scales, which overshoots the 0.3x budget at small head dims);
        consumers dequantize in f32, promotion covers the mixed multiply. Per-row lock-step ``cross_k/cross_v`` stay in the
        compute dtype: they are written once per ``prefill`` batch and
        carry no per-slot lifecycle, so quantizing them buys nothing the
        pool form doesn't already cover.

        Cross-attention source KV comes in two forms. ``source_len`` alone
        (lock-step serving) allocates per-row ``cross_k/cross_v``
        ``[Lc, B, S_src, Hkv, Dh]`` + per-row ``source_len``, filled by
        ``prefill``. ``n_sources`` (continuous serving) instead allocates a
        **pooled** form: ``src_k/src_v [Lc, n_sources, S_src, Hkv, Dh]``
        entries shared across slots, ``src_len [n_sources]`` valid prefixes,
        and ``src_index [B]`` mapping each slot to its entry — written once
        per source by :meth:`ingest_source`, read-only at decode
        (``repro.serving.slot_pool.SourceKVPool`` owns the host ledger).

        ``chunk``: the serving engine's prefill chunk size. Ring caches use
        it to size the ring as ``round128(window + chunk)`` so the chunked-
        prefill exactness bound ``ring_len >= window + chunk - 1`` holds by
        construction — a window just under a 128 boundary no longer forces
        the engine to reject large chunks (the ring simply takes the next
        128 step, degenerating to the full cache when that reaches
        ``max_len``)."""
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        dt = self._dt
        cache: Cache = {"len": jnp.zeros((batch,), jnp.int32)}
        if cfg.family == "ssm":
            h = cfg.d_model // cfg.rwkv_head_dim
            cache.update(
                rwkv_att=jnp.zeros((cfg.n_layers, batch, cfg.d_model), dt),
                rwkv_ffn=jnp.zeros((cfg.n_layers, batch, cfg.d_model), dt),
                rwkv_wkv=jnp.zeros((cfg.n_layers, batch, h, cfg.rwkv_head_dim,
                                    cfg.rwkv_head_dim), jnp.float32))
            return cache
        n_cross = self._n_cross_groups()
        n_self = cfg.n_layers - n_cross
        kv_len = max_len
        if cfg.kv_ring and cfg.window:
            # ring cache: ~window slots regardless of context (SWA archs).
            # Decode needs ring_len >= window + 1 (the new token's write
            # must only ever evict the position leaving the window); chunked
            # serving additionally needs ring_len >= window + chunk - 1 for
            # prefill exactness under wraparound, so when the caller passes
            # its chunk the ring is sized round128(window + chunk) and the
            # bound holds by construction. The 128-rounding keeps the
            # sublane dimension aligned either way.
            want = cfg.window + (chunk if chunk else 1)
            kv_len = min(max_len, -(-want // 128) * 128)
        if cfg.decode_impl == "kernel":
            # kernel-path alignment contract (kernels/swiftkv_decode/ops.py):
            # the cache streams zero-copy through BlockSpec index maps, so
            # max_len must be block-divisible at init — a 128 multiple always
            # admits a power-of-two block, and a small cache (<= 128, one
            # block) needs only sublane alignment (multiple of 8); a
            # misaligned cache would raise at the first decode step instead
            # of silently paying a per-step whole-cache pad+copy
            mult = 128 if kv_len > 128 else 8
            kv_len = -(-kv_len // mult) * mult
        kv_dt = (jnp.dtype(kv_dtype) if kv_dtype is not None
                 else (jnp.dtype(jnp.int8) if cfg.w4a8_serve else dt))
        cache["k"] = jnp.zeros((n_self, batch, kv_len, cfg.n_kv_heads, dh),
                               kv_dt)
        cache["v"] = jnp.zeros_like(cache["k"])
        if kv_dt == jnp.int8:
            cache["k_scale"] = jnp.zeros(
                (n_self, batch, cfg.n_kv_heads, kv_len), jnp.bfloat16)
            cache["v_scale"] = jnp.zeros_like(cache["k_scale"])
        if cfg.rotary_dim:
            rs = rope_lib.rope_state_init(dh, cfg.rope_base, 0, cfg.rotary_dim)
            cache["rope_cos"] = jnp.broadcast_to(rs.cos_m, (batch, rs.cos_m.shape[0]))
            cache["rope_sin"] = jnp.broadcast_to(rs.sin_m, (batch, rs.sin_m.shape[0]))
        if cfg.family == "hybrid":
            d_inner = cfg.ssm_expand * cfg.d_model
            cache["mamba_conv"] = jnp.zeros(
                (n_self, batch, cfg.ssm_conv - 1, d_inner), jnp.float32)
            cache["mamba_ssm"] = jnp.zeros(
                (n_self, batch, d_inner, cfg.ssm_state), jnp.float32)
        n_cross_kv = (n_cross if cfg.cross_attn_every > 1
                      else (cfg.n_layers if cfg.cross_attn_every == 1 else 0))
        if n_cross_kv and source_len and n_sources:
            # pooled source KV (continuous serving): entries keyed by source
            # id on the host side, shared read-only across slots
            cache["src_k"] = jnp.zeros(
                (n_cross_kv, n_sources, source_len, cfg.n_kv_heads, dh),
                kv_dt)
            cache["src_v"] = jnp.zeros_like(cache["src_k"])
            cache["src_len"] = jnp.zeros((n_sources,), jnp.int32)
            cache["src_index"] = jnp.zeros((batch,), jnp.int32)
            if kv_dt == jnp.int8:
                cache["src_k_scale"] = jnp.zeros(
                    (n_cross_kv, n_sources, cfg.n_kv_heads, source_len),
                    jnp.bfloat16)
                cache["src_v_scale"] = jnp.zeros_like(cache["src_k_scale"])
        elif n_cross_kv and source_len:
            cache["cross_k"] = jnp.zeros(
                (n_cross_kv, batch, source_len, cfg.n_kv_heads, dh), dt)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
            cache["source_len"] = jnp.full((batch,), source_len, jnp.int32)
        return cache

    def _rope_qk_decode(self, cache: Cache, q: jax.Array, k: jax.Array,
                        lengths: jax.Array):
        """Rotate the new token's q/k at its absolute position. ``incremental``
        uses the cached Eq. 11 angle state; ``direct`` recomputes cos/sin."""
        cfg = self.cfg
        if not cfg.rotary_dim:
            return q, k
        if cfg.rope_mode == "incremental":
            cos, sin = cache["rope_cos"], cache["rope_sin"]      # [B, rd/2]
            rd = 2 * cos.shape[-1]
            def rot(x):                                          # x: [B, H, Dh]
                xr, xp = x[..., :rd], x[..., rd:]
                x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
                c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
                return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, xp], -1)
            return rot(q), rot(k)
        rot = lambda x: jax.vmap(
            lambda xx, m: rope_lib.apply_rope(xx, m[None], cfg.rope_base,
                                              cfg.rotary_dim))(
            x[:, :, None, :], lengths)[:, :, 0, :]
        return rot(q), rot(k)

    def _advance_rope(self, cache: Cache) -> Cache:
        cfg = self.cfg
        if cfg.rotary_dim and cfg.rope_mode == "incremental":
            rs = rope_lib.RopeState(
                cos_m=cache["rope_cos"], sin_m=cache["rope_sin"],
                a=jnp.cos(rope_lib.rope_freqs(self.cfg.resolved_head_dim,
                                              cfg.rope_base, cfg.rotary_dim)),
                b=jnp.sin(rope_lib.rope_freqs(self.cfg.resolved_head_dim,
                                              cfg.rope_base, cfg.rotary_dim)))
            rs = rope_lib.rope_state_advance(rs)
            cache = dict(cache, rope_cos=rs.cos_m, rope_sin=rs.sin_m)
        return cache

    @staticmethod
    def _write_token(c: jax.Array, layer: jax.Array, x: jax.Array,
                     pos: jax.Array, active: jax.Array | None = None
                     ) -> jax.Array:
        """Write each row's new token into layer ``layer`` of a stacked
        cache: K/V ``[L, B, Smax, Hkv, Dh]`` with ``x`` [B, Hkv, Dh], or an
        int8 cache's bf16 scale plane ``[L, B, Hkv, Smax]`` (position last)
        with ``x`` [B, Hkv]. Row ``b`` lands at position ``pos[b]`` mod the
        position axis (a full-context cache never wraps; a ring cache
        overwrites the slot that just left the window). It is one scatter
        of B tokens into the buffer the layer loop carries, so the update
        happens in place and nothing else of the stack moves.

        ``active``: optional [B] bool **per-slot write mask** — rows with
        ``active=False`` rewrite their old value (an in-place no-op). This
        is the ragged-decode parking mechanism for ring caches: a ring has
        no dead tail row to park on (every slot is, or will wrap into, a
        live window position), so a parked write must not move data at
        all. Full caches park on the reserved tail row instead, whose write
        target already encodes the parking, and pass ``active=None``."""
        rows = jnp.arange(x.shape[0])
        if c.ndim == 5:                                  # K/V
            at = (layer, rows, pos % c.shape[2])
        else:                                            # scale plane
            at = (layer, rows, slice(None), pos % c.shape[3])
        x = x.astype(c.dtype)
        if active is not None:
            x = jnp.where(active.reshape(-1, *(1,) * (x.ndim - 1)), x, c[at])
        return c.at[at].set(x, indices_are_sorted=True, unique_indices=True)

    def _decode_self_attn(self, p: Params, h: jax.Array, kv: Cache,
                          layer: jax.Array, cache: Cache,
                          active: jax.Array | None = None):
        """Self-attention of layer ``layer`` for the new token. ``kv`` is
        the whole self-attention cache stack (``k``, ``v`` and, for an int8
        cache, ``k_scale``/``v_scale``); the new token is written into it
        in place and attention reads layer ``layer`` back out of it.
        Returns ``(out, kv)``."""
        cfg = self.cfg
        b, d = h.shape
        dh = cfg.resolved_head_dim
        with jax.named_scope("qkv"):
            q = linear(p, "wq", h).reshape(b, cfg.n_heads, dh)
            k = linear(p, "wk", h).reshape(b, cfg.n_kv_heads, dh)
            v = linear(p, "wv", h).reshape(b, cfg.n_kv_heads, dh)
            if cfg.qk_norm:
                q = rms_norm(q, p["qn"], cfg.norm_eps)
                k = rms_norm(k, p["kn"], cfg.norm_eps)
            q, k = self._rope_qk_decode(cache, q, k, cache["len"])
        ring = bool(cfg.kv_ring and cfg.window)
        write_mask = None
        if active is None:
            write_at, attn_len = cache["len"], cache["len"] + 1
        elif ring:
            # ragged ring batch: inactive rows have no dead row to park on
            # (the tail is a live window slot once wrapped), so parking is a
            # per-slot write *mask* — the row rewrites its old value in
            # place — plus a 1-token stub attention length
            write_at = cache["len"]
            attn_len = jnp.where(active, cache["len"] + 1, 1)
            write_mask = active
        else:
            # ragged batch: inactive rows (free / mid-prefill slots) park
            # their discarded KV write on the reserved tail row and attend a
            # 1-token stub — the batch keeps its static shape while slot
            # membership changes (serving/slot_pool.py reserves the tail)
            write_at = jnp.where(active, cache["len"], kv["k"].shape[2] - 1)
            attn_len = jnp.where(active, cache["len"] + 1, 1)
        kv = dict(kv)
        with jax.named_scope("kv_write"):
            if "k_scale" in kv:
                # int8 cache: quantize the new token's K/V over Dh per head
                # — the write parks/wraps exactly like the fp path, and the
                # scale plane parks with it so released rows stay
                # (0, scale 0)
                k, k_s = quantize_kv(k)
                v, v_s = quantize_kv(v)
                kv["k_scale"] = self._write_token(kv["k_scale"], layer, k_s,
                                                  write_at, write_mask)
                kv["v_scale"] = self._write_token(kv["v_scale"], layer, v_s,
                                                  write_at, write_mask)
            kv["k"] = self._write_token(kv["k"], layer, k, write_at,
                                        write_mask)
            kv["v"] = self._write_token(kv["v"], layer, v, write_at,
                                        write_mask)
        with jax.named_scope("attention"):
            read = {name: jax.lax.dynamic_index_in_dim(a, layer,
                                                       keepdims=False)
                    for name, a in kv.items()}
            out = attn_lib.decode_attention(q, read["k"], read["v"], attn_len,
                                            impl=cfg.decode_impl,
                                            window=cfg.window, ring=ring,
                                            block_size=cfg.attn_block or 512,
                                            k_scale=read.get("k_scale"),
                                            v_scale=read.get("v_scale"))
        with jax.named_scope("attn_out"):
            out = linear(p, "wo", out.reshape(b, -1))
        return out, kv

    def _decode_cross_attn(self, p: Params, h: jax.Array, ck, cv,
                           source_len: jax.Array) -> jax.Array:
        """Per-row (lock-step) cross read: ck/cv are [B, S_src, Hkv, Dh]
        caches written by :meth:`prefill`; ``source_len`` [B] masks each
        row's padded source tail."""
        cfg = self.cfg
        b, d = h.shape
        dh = cfg.resolved_head_dim
        q = linear(p, "wq", h).reshape(b, cfg.n_heads, dh)
        if cfg.qk_norm:
            q = rms_norm(q, p["qn"], cfg.norm_eps)
        impl = "blockwise" if cfg.decode_impl == "sp" else cfg.decode_impl
        out = attn_lib.decode_attention(q, ck, cv, source_len,
                                        impl=impl,
                                        block_size=cfg.attn_block or 512)
        out = linear(p, "wo", out.reshape(b, -1))
        return jnp.tanh(p["gate"]).astype(h.dtype) * out

    def _decode_cross_attn_pooled(self, p: Params, h: jax.Array, sk, sv,
                                  entries: jax.Array, src_len: jax.Array,
                                  sk_sc=None, sv_sc=None) -> jax.Array:
        """Pooled (continuous-serving) cross read: sk/sv are one layer's
        slice of the source-KV pool, ``[n_entries, S_src, Hkv, Dh]`` —
        shared across slots, NOT batched — and ``entries``/``src_len`` map
        each slot to its entry and that entry's valid source prefix. The
        blockwise read streams each row's entry straight out of the pool
        (``swiftkv_decode_pooled``); a ``src_len == 0`` row (no source, or
        a freed slot pointing at a zeroed entry) reads an exact zero, so
        the gated output vanishes for it."""
        cfg = self.cfg
        b, d = h.shape
        dh = cfg.resolved_head_dim
        q = linear(p, "wq", h).reshape(b, cfg.n_heads, dh)
        if cfg.qk_norm:
            q = rms_norm(q, p["qn"], cfg.norm_eps)
        impl = ("naive" if cfg.decode_impl == "naive" else "blockwise")
        out = attn_lib.decode_cross_attention(
            q, sk, sv, entries, jnp.take(src_len, entries), impl=impl,
            block_size=cfg.attn_block or 512, k_scale=sk_sc, v_scale=sv_sc)
        out = linear(p, "wo", out.reshape(b, -1))
        return jnp.tanh(p["gate"]).astype(h.dtype) * out

    def _decode_block(self, bp: Params, kv: Cache, layer: jax.Array,
                      slices: dict, x: jax.Array, cache: Cache,
                      active: jax.Array | None = None
                      ) -> tuple[jax.Array, Cache, dict]:
        """One self block at decode time. ``kv`` is the whole self-attention
        cache stack, carried by the layer loop and updated in place at layer
        ``layer``; ``slices`` holds this layer's other state (Mamba state,
        read-only source KV). Returns ``(x, kv, new)``, ``new`` being the
        updated Mamba state as scan ys."""
        cfg = self.cfg
        new = {}
        with jax.named_scope("qkv"):
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
        attn_out, kv = self._decode_self_attn(bp["attn"], h, kv, layer, cache,
                                              active)
        if cfg.family == "hybrid":
            st = mamba_lib.MambaState(conv=slices["mamba_conv"],
                                      ssm=slices["mamba_ssm"])
            # ragged batch: inactive rows carry their recurrent state through
            # unchanged — masked at the state-update site in mamba.py
            m_out, st = mamba_lib.mamba_decode_step(bp["mamba"], h, st,
                                                    active=active)
            new["mamba_conv"], new["mamba_ssm"] = st.conv, st.ssm
            x = x + 0.5 * (rms_norm(attn_out, bp["ln_attn_out"], cfg.norm_eps)
                           + rms_norm(m_out, bp["ln_mamba_out"], cfg.norm_eps))
        else:
            with jax.named_scope("attn_out"):
                x = x + attn_out
        if "cross" in bp and "src_k" in slices:
            # pooled source KV (continuous serving): read-only, per-slot
            # entry indirection via cache["src_index"]
            hc = rms_norm(x, bp["ln_cross"], cfg.norm_eps)
            x = x + self._decode_cross_attn_pooled(
                bp["cross"], hc, slices["src_k"], slices["src_v"],
                cache["src_index"], cache["src_len"],
                slices.get("src_k_scale"), slices.get("src_v_scale"))
        elif "cross" in bp and "cross_k" in slices:
            hc = rms_norm(x, bp["ln_cross"], cfg.norm_eps)
            x = x + self._decode_cross_attn(bp["cross"], hc, slices["cross_k"],
                                            slices["cross_v"],
                                            cache["source_len"])
        with jax.named_scope("mlp"):
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            if cfg.n_experts:
                # capacity-free per-row dispatch at decode: identical math to
                # the capacity path when nothing drops, but a row's output
                # depends only on that row — batch composition can't perturb
                # a request (ragged serving's per-request-equivalence
                # contract), and at B = n_slots it is also the cheaper form
                y, _ = moe_lib.moe_apply_rowwise(
                    bp["ffn"], h2, top_k=cfg.top_k, act=cfg.act,
                    gated=cfg.gated_mlp)
            else:
                y = mlp_apply(bp["ffn"], h2, cfg.act, cfg.gated_mlp)
            return x + y, kv, new

    def decode_step(self, params: Params, tokens: jax.Array,
                    cache: Cache, active: jax.Array | None = None
                    ) -> tuple[jax.Array, Cache]:
        """tokens: [B] int32 -> (logits [B, V] f32, updated cache).

        ``active``: optional [B] bool — the ragged continuous-batching form.
        Active rows decode normally; inactive rows (free or mid-prefill
        slots) ride through with a parked KV write, a stub attention length,
        and *no* ``len`` advance, so the jit'd step keeps a static [B] shape
        while slot membership changes between steps. Recurrent-state
        families (ssm / hybrid) have no parking row — the row *is* the
        state — so inactive rows carry their (wkv / conv, ssm) state through
        unchanged via ``jnp.where`` selects. Ring KV caches (``kv_ring``
        SWA configs) have no parking row either — every ring slot is, or
        wraps into, a live window position — so their inactive rows park
        via a per-slot write *mask* (:meth:`_write_token` ``active=``), the
        row rewriting its old value in place. Cross-attention source KV
        needs no parking at all: the pooled ``src_k/src_v`` entries are
        read-only at decode (each row reads its ``src_index`` entry masked
        to that entry's ``src_len``; an inactive row's read is discarded),
        so nothing an inactive row does can corrupt shared source state.
        The per-row incremental-RoPE
        state still advances for every row; a slot's state is reseeded by
        ``finalize_slot`` when a new request fills it."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"].astype(self._dt)[tokens]         # [B, d]

        if cfg.family == "ssm":
            return self._rwkv_decode_step(params, x, cache, active)

        n_cross = self._n_cross_groups()

        # the self-attention KV stack rides the layer loop's carry whole and
        # each layer writes its new token into it in place; only per-layer
        # state that is not the stack (Mamba state, source KV) is scanned
        def step(carry, xs):
            x, kv = carry
            bp, layer, slices = xs
            x, kv, new = self._decode_block(bp, kv, layer, slices, x, cache,
                                            active)
            return (x, kv), new

        kv = {key: cache[key] for key in ("k", "v", "k_scale", "v_scale")
              if key in cache}
        layers = jnp.arange(cache["k"].shape[0])
        self_slices = {}
        if cfg.family == "hybrid":
            self_slices["mamba_conv"] = cache["mamba_conv"]
            self_slices["mamba_ssm"] = cache["mamba_ssm"]
        if cfg.cross_attn_every == 1:                  # whisper-style
            if "src_k" in cache:                       # pooled source KV
                self_slices["src_k"] = cache["src_k"]
                self_slices["src_v"] = cache["src_v"]
                if "src_k_scale" in cache:
                    self_slices["src_k_scale"] = cache["src_k_scale"]
                    self_slices["src_v_scale"] = cache["src_v_scale"]
            elif "cross_k" in cache:                   # per-row (lock-step)
                self_slices["cross_k"] = cache["cross_k"]
                self_slices["cross_v"] = cache["cross_v"]
            # neither: no source was ever provided — cross-attn contributes
            # nothing (matches prefill/forward with source=None)

        if not n_cross:
            (x, kv), new = layer_scan(step, (x, kv),
                                      (params["blocks"], layers, self_slices),
                                      unroll=cfg.unroll_layers)
        else:
            group = cfg.cross_attn_every
            n_self_per = group - 1
            if "src_k" in cache:
                cross_xs, cross_mode = (cache["src_k"], cache["src_v"]), "pooled"
                if "src_k_scale" in cache:
                    cross_xs += (cache["src_k_scale"], cache["src_v_scale"])
            elif "cross_k" in cache:
                cross_xs, cross_mode = (cache["cross_k"], cache["cross_v"]), "perrow"
            else:
                # sourceless decode: the dedicated cross layer still applies
                # its FFN; only the (gated) cross-attention term vanishes
                cross_xs, cross_mode = (), "none"

            def group_step(carry, xs):
                gp, gl, gs, cp, *ckv = xs
                (x, kv), new = layer_scan(step, carry, (gp, gl, gs),
                                          unroll=cfg.unroll_layers)
                h = rms_norm(x, cp["ln1"], cfg.norm_eps)
                if cross_mode == "pooled":
                    x = x + self._decode_cross_attn_pooled(
                        cp["cross"], h, ckv[0], ckv[1],
                        cache["src_index"], cache["src_len"],
                        *(ckv[2:4] if len(ckv) > 2 else (None, None)))
                elif cross_mode == "perrow":
                    x = x + self._decode_cross_attn(cp["cross"], h, ckv[0],
                                                    ckv[1],
                                                    cache["source_len"])
                h2 = rms_norm(x, cp["ln2"], cfg.norm_eps)
                x = x + mlp_apply(cp["ffn"], h2, cfg.act, cfg.gated_mlp)
                return (x, kv), new

            # layer index g * n_self_per + i: the self layer's place in the
            # stack the cache is laid out by
            grouped = lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:])
            (x, kv), new = layer_scan(
                group_step, (x, kv),
                (jax.tree.map(grouped, params["blocks"]), grouped(layers),
                 jax.tree.map(grouped, self_slices), params["cross_blocks"],
                 *cross_xs),
                unroll=cfg.unroll_layers)
            new = jax.tree.map(
                lambda a: a.reshape(n_cross * n_self_per, *a.shape[2:]), new)

        cache = dict(cache, **kv, **new)
        cache["len"] = cache["len"] + (1 if active is None
                                       else active.astype(jnp.int32))
        cache = self._advance_rope(cache)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
            return self._unembed(params, x), cache

    # ---- multi-tick decode: K fused ticks, one dispatch --------------------
    def decode_multi(self, params: Params, tok: jax.Array, cache: Cache,
                     active: jax.Array, budget: jax.Array,
                     serials: jax.Array, emitted: jax.Array, n_ticks: int,
                     *, eos_id: int | None = None, temperature: float = 0.0,
                     rng_key: jax.Array | None = None,
                     poison: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array, Cache]:
        """Fuse ``n_ticks`` ragged decode ticks into one program: a
        ``lax.scan`` over the :meth:`decode_step` body with per-tick
        Gumbel-max sampling and **on-device retirement**, so the host syncs
        once per K tokens instead of once per token.

        Control state is device-resident for the whole block: per tick, an
        active row decodes, samples its next token (greedy argmax when
        ``temperature == 0``, else Gumbel-max keyed on
        ``(rng_key, serial, token index)`` — request-intrinsic, so draws are
        tick-horizon-independent by construction), advances its ``emitted``
        counter, and *retires itself mid-scan* when the sampled token hits
        ``eos_id`` or the counter reaches its ``budget`` — the row's
        ``active`` bit flips and from the next tick it parks its KV writes /
        carries its recurrent state exactly like any other inactive row.
        Works unchanged for every ragged family because the scanned body IS
        ``decode_step(active=...)``: MHA/GQA/SWA park KV on the reserved
        tail row, ssm/hybrid rows mask their state carries
        (rwkv6.rwkv_*_step / mamba.mamba_decode_step ``active=``), and MoE
        rows use the capacity-free per-row dispatch, so a row's tokens
        cannot depend on when its neighbours retire inside the block.

        tok/serials/emitted: [B] int32; active: [B] bool; budget: [B] int32
        (per-slot total token allowance, i.e. ``max_new_tokens``).
        Returns ``(tok_block [K, B] int32, active [B], emitted [B], cache)``
        where ``tok_block[t, b]`` is the token row ``b`` emitted at tick
        ``t``, or ``-1`` if the row was inactive — the host replays
        retirement from the block alone, no per-tick sync.

        **On-device health check**: every tick verifies each active row's
        logits are finite before trusting the sampled token. A row whose
        logits contain NaN/inf emits the sentinel ``-2`` in ``tok_block``
        and self-retires (its ``active`` bit flips, ``emitted`` does not
        advance, its KV writes park from the next tick) — the quarantine
        signal rides the existing ``[K, B]`` sync at zero extra transfers,
        and with all-finite logits every output is bit-identical to the
        uncheck'd program. ``poison``: optional [B] bool fault-injection
        mask (see :mod:`repro.serving.faults`) that overwrites masked rows'
        logits with NaN each tick, exercising exactly that detection path;
        ``None`` (the default) compiles no poisoning code."""
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)

        def pick_tokens(logits, emitted):
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.vmap(
                lambda row, serial, idx: seeded_gumbel_pick(
                    rng_key, row, serial, idx, temperature)
            )(logits, serials, emitted)

        def tick(carry, _):
            tok, cache, active, emitted = carry
            logits, cache = self.decode_step(params, tok, cache, active)
            with jax.named_scope("sample"):
                if poison is not None:
                    logits = jnp.where(poison[:, None], jnp.nan, logits)
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                pick = pick_tokens(logits, emitted)
                ok = active & finite
                emitted = jnp.where(ok, emitted + 1, emitted)
                done = emitted >= budget
                if eos_id is not None:
                    done |= pick == eos_id
                # healthy rows report their token; a non-finite row reports
                # the -2 quarantine sentinel; inactive rows stay -1
                out = jnp.where(active,
                                jnp.where(finite, pick, jnp.int32(-2)),
                                jnp.int32(-1))
                active = ok & ~done
                # a retired row's final token is emitted but never fed back
                # — exactly the single-tick engine's contract
                tok = jnp.where(active, pick, tok)
            return (tok, cache, active, emitted), out

        (tok, cache, active, emitted), tok_block = jax.lax.scan(
            tick, (tok, cache, active, emitted), None, length=n_ticks)
        return tok_block, active, emitted, cache

    # ---- prefill: full-prompt forward that also fills the cache ------------
    def prefill(self, params: Params, tokens: jax.Array, cache: Cache,
                source: jax.Array | None = None,
                source_len: jax.Array | None = None) -> tuple[jax.Array, Cache]:
        """tokens: [B, Sp] (uniform prompt length — serving drivers pad to
        length groups); returns (last-position logits [B, V] f32, filled
        cache). Keys are cached post-RoPE (paper §IV-C).

        ``source``: [B, S_src, d] frontend features for cross-attention
        stacks; ``source_len``: optional [B] valid source prefixes when
        rows carry sources of different true lengths padded to S_src —
        cross reads mask the padded tails here and ``cache['source_len']``
        records them so decode masks identically. ``source=None`` on a
        cross config means *no source*: the (gated) cross-attention term
        contributes nothing, while a dedicated (vlm-style) cross layer
        still applies its FFN."""
        cfg = self.cfg
        b, sp = tokens.shape
        x = params["embed"].astype(self._dt)[tokens]
        positions = jnp.arange(sp)

        if cfg.family == "ssm":
            return self._rwkv_prefill(params, x, cache)

        n_cross = self._n_cross_groups()
        dh = cfg.resolved_head_dim

        def kv_for(p, h, with_rope: bool):
            k = linear(p, "wk", h).reshape(b, -1, cfg.n_kv_heads, dh)
            v = linear(p, "wv", h).reshape(b, -1, cfg.n_kv_heads, dh)
            if cfg.qk_norm:
                k = rms_norm(k, p["kn"], cfg.norm_eps)
            if with_rope and cfg.rotary_dim:
                k = jnp.swapaxes(rope_lib.apply_rope(
                    jnp.swapaxes(k, 1, 2), positions, cfg.rope_base,
                    cfg.rotary_dim), 1, 2)
            return k, v

        def fill_kv(ck, kv):
            # full cache: contiguous write at 0; ring cache: write the last
            # R tokens at their (pos % R) slots
            r = ck.shape[2] if ck.ndim == 5 else ck.shape[1]
            if kv.shape[1] <= r:
                return jax.lax.dynamic_update_slice(
                    ck, kv.astype(ck.dtype), (0,) * ck.ndim)
            import numpy as _np
            m = r
            pos = _np.arange(kv.shape[1] - m, kv.shape[1])
            slots = pos % r
            order = _np.argsort(slots)
            return ck.at[:, slots[order]].set(
                kv[:, kv.shape[1] - m:][:, order].astype(ck.dtype))

        def fill_scale(csc, sc):
            # scale twin of fill_kv: csc [B, Hkv, R] (position last), sc
            # [B, Sp, Hkv] from quantize_kv — same contiguous-or-ring write
            sc = jnp.swapaxes(sc, 1, 2).astype(csc.dtype)  # [B, Hkv, Sp]
            r = csc.shape[-1]
            if sc.shape[-1] <= r:
                return jax.lax.dynamic_update_slice(csc, sc, (0, 0, 0))
            import numpy as _np
            pos = _np.arange(sc.shape[-1] - r, sc.shape[-1])
            slots = pos % r
            order = _np.argsort(slots)
            return csc.at[:, :, slots[order]].set(
                sc[:, :, sc.shape[-1] - r:][:, :, order])

        def self_step(x, xs):
            bp, slices = xs
            new = {}
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            q = linear(bp["attn"], "wq", h).reshape(
                b, sp, cfg.n_heads, dh)
            if cfg.qk_norm:
                q = rms_norm(q, bp["attn"]["qn"], cfg.norm_eps)
            if cfg.rotary_dim:
                q = jnp.swapaxes(rope_lib.apply_rope(
                    jnp.swapaxes(q, 1, 2), positions, cfg.rope_base,
                    cfg.rotary_dim), 1, 2)
            k, v = kv_for(bp["attn"], h, with_rope=True)
            if "k_scale" in slices:
                # int8 cache: the cache write quantizes; attention below
                # still consumes the fresh fp K/V, so full-prefill logits
                # are untouched by the storage format
                kq, k_s = quantize_kv(k)
                vq, v_s = quantize_kv(v)
                new["k"] = fill_kv(slices["k"], kq)
                new["v"] = fill_kv(slices["v"], vq)
                new["k_scale"] = fill_scale(slices["k_scale"], k_s)
                new["v_scale"] = fill_scale(slices["v_scale"], v_s)
            else:
                new["k"] = fill_kv(slices["k"], k)
                new["v"] = fill_kv(slices["v"], v)
            attn = attn_lib.prefill_attention(q, k, v, causal=True,
                                              window=cfg.window,
                                              kv_block=cfg.attn_block or 512)
            attn_out = linear(bp["attn"], "wo", attn.reshape(b, sp, -1))
            if cfg.family == "hybrid":
                m_out, mst = mamba_lib.mamba_forward(bp["mamba"], h,
                                                     return_state=True)
                new["mamba_conv"], new["mamba_ssm"] = mst.conv, mst.ssm
                x = x + 0.5 * (rms_norm(attn_out, bp["ln_attn_out"], cfg.norm_eps)
                               + rms_norm(m_out, bp["ln_mamba_out"], cfg.norm_eps))
            else:
                x = x + attn_out
            if "cross" in bp and source is not None:
                hc = rms_norm(x, bp["ln_cross"], cfg.norm_eps)
                ck, cv = kv_for(bp["cross"], source.astype(h.dtype),
                                with_rope=False)
                new["cross_k"] = ck.astype(slices["cross_k"].dtype)
                new["cross_v"] = cv.astype(slices["cross_v"].dtype)
                qc = linear(bp["cross"], "wq", hc).reshape(
                    b, sp, cfg.n_heads, dh)
                if cfg.qk_norm:
                    qc = rms_norm(qc, bp["cross"]["qn"], cfg.norm_eps)
                c_out = attn_lib.prefill_attention(
                    qc, ck, cv, causal=False, kv_lengths=source_len,
                    kv_block=cfg.attn_block or 512)
                c_out = linear(bp["cross"], "wo", c_out.reshape(b, sp, -1))
                x = x + jnp.tanh(bp["cross"]["gate"]).astype(h.dtype) * c_out
            y, _ = self._ffn_out(bp, x)
            return x + y, new

        self_slices = {"k": cache["k"], "v": cache["v"]}
        if "k_scale" in cache:
            self_slices["k_scale"] = cache["k_scale"]
            self_slices["v_scale"] = cache["v_scale"]
        if cfg.family == "hybrid":
            self_slices["mamba_conv"] = cache["mamba_conv"]
            self_slices["mamba_ssm"] = cache["mamba_ssm"]
        if cfg.cross_attn_every == 1 and "cross_k" in cache:
            self_slices["cross_k"] = cache["cross_k"]
            self_slices["cross_v"] = cache["cross_v"]

        if not n_cross:
            x, new = layer_scan(self_step, x, (params["blocks"], self_slices), unroll=cfg.unroll_layers)
        else:
            group = cfg.cross_attn_every
            n_self_per = group - 1

            def group_step(x, xs):
                gp, gs, cp = xs
                x, new = layer_scan(self_step, x, (gp, gs), unroll=cfg.unroll_layers)
                h = rms_norm(x, cp["ln1"], cfg.norm_eps)
                if source is not None:
                    ck, cv = kv_for(cp["cross"], source.astype(x.dtype),
                                    with_rope=False)
                    qc = linear(cp["cross"], "wq", h).reshape(
                        b, sp, cfg.n_heads, dh)
                    c_out = attn_lib.prefill_attention(
                        qc, ck, cv, causal=False, kv_lengths=source_len,
                        kv_block=cfg.attn_block or 512)
                    c_out = linear(cp["cross"], "wo", c_out.reshape(b, sp, -1))
                    x = x + jnp.tanh(cp["cross"]["gate"]).astype(x.dtype) * c_out
                h2 = rms_norm(x, cp["ln2"], cfg.norm_eps)
                x = x + mlp_apply(cp["ffn"], h2, cfg.act, cfg.gated_mlp)
                if source is not None:
                    new["cross_k"] = ck.astype(cache["cross_k"].dtype)
                    new["cross_v"] = cv.astype(cache["cross_v"].dtype)
                return x, new

            gp = jax.tree.map(
                lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:]),
                params["blocks"])
            gs = jax.tree.map(
                lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:]),
                self_slices)
            x, new = layer_scan(group_step, x, (gp, gs, params["cross_blocks"]), unroll=cfg.unroll_layers)
            if source is not None:
                cross_new = {"cross_k": new.pop("cross_k"),
                             "cross_v": new.pop("cross_v")}
            new = jax.tree.map(
                lambda a: a.reshape(n_cross * n_self_per, *a.shape[2:]), new)
            if source is not None:
                new.update(cross_new)

        cache = dict(cache)
        for key, val in new.items():
            cache[key] = val
        if source is not None and "source_len" in cache:
            cache["source_len"] = (
                jnp.asarray(source_len, jnp.int32) if source_len is not None
                else jnp.full_like(cache["source_len"], source.shape[1]))
        cache["len"] = jnp.full_like(cache["len"], sp)
        if cfg.rotary_dim and cfg.rope_mode == "incremental":
            rs = rope_lib.rope_state_init(cfg.resolved_head_dim, cfg.rope_base,
                                          sp, cfg.rotary_dim)
            cache["rope_cos"] = jnp.broadcast_to(rs.cos_m, cache["rope_cos"].shape)
            cache["rope_sin"] = jnp.broadcast_to(rs.sin_m, cache["rope_sin"].shape)
        x = rms_norm(x[:, -1, :], params["ln_f"], cfg.norm_eps)
        return self._unembed(params, x), cache

    # ---- slot-targeted ragged prefill (continuous batching) ----------------
    def supports_ragged_serving(self) -> bool:
        """Every family serves ragged — the gated set is empty
        (``tests/test_serving_conformance.py`` pins that).

        Chunked slot prefill + masked ragged decode cover the dense-KV
        families; the recurrent-state families (ssm / hybrid) thread
        per-slot state in ``prefill_chunk`` and mask ``jnp.where`` state
        carries in ``decode_step``. The continuous MoE path is *drop-free
        by construction* (per-row dispatch at decode, capacity=C dispatch
        in chunk prefill), so a request's tokens never depend on batch
        composition; greedy equivalence against the lock-step engine is
        exact whenever the lock-step capacity-factor prefill itself drops
        nothing — under routing imbalance at low ``capacity_factor`` the
        *reference* drops tokens and the drop-free continuous output is the
        more faithful one.

        Ring KV caches (``kv_ring`` SWA configs) serve ragged too: parked
        rows use a per-slot write mask instead of the reserved tail row,
        chunked prefill writes at ``pos % ring_len`` with wrap, and the
        decode paths consume the ring in place (no unrotate copy).

        Cross-attention stacks (vlm / audio) — the last family to join —
        serve through the **source-KV pool**: encoder-side K/V is ingested
        once at admission into a refcounted pool entry keyed by source id
        (``init_cache(n_sources=...)`` + :meth:`ingest_source`), each
        slot's ``src_index`` points at its entry, and the decode /
        chunk-prefill cross reads mask per-slot source lengths so rows
        with different encoder lengths coexist in one static-shape
        dispatch (``attn_lib.decode_cross_attention``)."""
        return True

    def prefill_chunk(self, params: Params, tokens: jax.Array, cache: Cache,
                      slot: jax.Array, offset: jax.Array, last: jax.Array
                      ) -> tuple[jax.Array, Cache]:
        """Prefill one prompt chunk into a single cache slot at its own
        offset: tokens [C] run at absolute positions [offset, offset+C),
        K/V land in rows ``cache[k|v][:, slot, offset:offset+C]``, and the
        chunk attends causally to the slot's already-written prefix via
        ``prefill_attention``'s ``kv_lengths`` / ``q_offset`` raggedness.

        Chunking long prompts keeps each call small so in-flight decodes
        interleave instead of stalling behind a monolithic prefill. The
        caller pads the final chunk: padded positions write dead KV past the
        committed length (never attended — decode overwrites them).
        ``cache['len']`` is untouched until ``finalize_slot`` commits the
        full prompt length, so concurrent decode steps treat the slot as
        inactive throughout.

        Only chunk position ``last`` is unembedded (the caller needs one
        row of logits, on the final chunk — anything else would burn a
        [C, V] projection per chunk). Returns (logits [V] f32, cache).

        Recurrent families thread per-slot state: the ssm (RWKV) stack has
        no KV at all and runs :meth:`_rwkv_prefill_chunk`; hybrid layers
        continue the slot's (conv, ssm) Mamba state chunk to chunk, with
        padded tail positions masked into exact state no-ops. MoE FFNs use
        the capacity-free per-row dispatch (a padded position must not steal
        expert capacity from a real token).

        Ring KV configs (``kv_ring`` SWA) fill the slot's ring chunk by
        chunk at ``pos % ring_len`` — a prompt longer than the ring wraps
        and overwrites its own oldest (out-of-window) entries, which is
        what makes the long-context scenario (prompt >> window) servable at
        all. Padded tail positions are *keep*-masked (they rewrite the old
        slot value), so only real tokens ever occupy ring slots, and the
        chunk attends through :func:`attn_lib.prefill_attention_ring` —
        exact as long as ``ring_len >= window + chunk - 1`` (a later
        in-chunk token then only ever overwrites positions already outside
        every live query's window; the serving engine enforces the bound at
        construction — and ``init_cache(chunk=...)`` sizes the ring so it
        holds by construction).

        Cross-attention configs (vlm / audio) read the slot's **source-KV
        pool** entry: the chunk's queries cross-attend (non-causal, masked
        to the entry's ``src_len``) to ``src_k/src_v[:, src_index[slot]]``
        — already ingested at admission, never written here. A slot whose
        entry has ``src_len == 0`` (no source) gets an exact-zero cross
        term; a dedicated (vlm-style) cross layer still applies its FFN."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return self._rwkv_prefill_chunk(params, tokens, cache, slot, last)
        (c,) = tokens.shape
        dh = cfg.resolved_head_dim
        smax, hkv = cache["k"].shape[2], cfg.n_kv_heads
        with jax.named_scope("embed"):
            x = params["embed"].astype(self._dt)[tokens][None]   # [1, C, d]
        positions = offset + jnp.arange(c)
        kv_len = jnp.reshape(offset + c, (1,)).astype(jnp.int32)
        q_off = jnp.reshape(offset, (1,)).astype(jnp.int32)
        n_valid = last + 1

        ring = bool(cfg.kv_ring and cfg.window)
        pooled_src = "src_k" in cache
        if pooled_src:
            s_src = cache["src_k"].shape[2]
            entry = jnp.take(cache["src_index"], slot)
            src_n = jnp.reshape(jnp.take(cache["src_len"], entry),
                                (1,)).astype(jnp.int32)

        def cross_read(cp, hc, sk_all, sv_all, sks_all=None, svs_all=None):
            """Chunk queries (pre-normed ``hc`` [1, C, d]) against this
            slot's pool entry in one layer's source KV ([E, S_src, Hkv,
            Dh]) — read-only, masked to the entry's valid prefix. An int8
            pool (``sks_all/svs_all`` [E, Hkv, S_src] scales) dequantizes
            just this entry's slice — one [S_src, Hkv, Dh] f32
            materialization per layer per chunk, not the whole pool."""
            qc = linear(cp, "wq", hc).reshape(1, c, cfg.n_heads, dh)
            if cfg.qk_norm:
                qc = rms_norm(qc, cp["qn"], cfg.norm_eps)
            sk = jax.lax.dynamic_slice(sk_all, (entry, 0, 0, 0),
                                       (1, s_src, hkv, dh))
            sv = jax.lax.dynamic_slice(sv_all, (entry, 0, 0, 0),
                                       (1, s_src, hkv, dh))
            if sks_all is not None:
                sks = jax.lax.dynamic_slice(sks_all, (entry, 0, 0),
                                            (1, hkv, s_src))
                svs = jax.lax.dynamic_slice(svs_all, (entry, 0, 0),
                                            (1, hkv, s_src))
                sk = sk.astype(jnp.float32) * jnp.swapaxes(sks, 1, 2)[..., None]
                sv = sv.astype(jnp.float32) * jnp.swapaxes(svs, 1, 2)[..., None]
            out = attn_lib.prefill_attention(qc, sk, sv, causal=False,
                                             kv_lengths=src_n,
                                             kv_block=cfg.attn_block or 512)
            out = linear(cp, "wo", out.reshape(1, c, -1))
            return jnp.tanh(cp["gate"]).astype(hc.dtype) * out

        def ring_fill(slices, new, k, v):
            """The chunk's keys and values into this slot's ring: token at
            absolute position p lands in ring slot p % R (wrap-aware
            scatter); padded tail rows (> last) keep the old slot value so
            only real tokens occupy ring slots. An int8 cache quantizes per
            (position, head) and scatters the scale planes the same way
            (into ``new``). Returns what the chunk attends — the slot's
            ring, on an int8 cache dequantized with the chunk's own fresh
            fp values overlaid (as the full-cache path) — and the updated
            K/V caches."""
            quant = "k_scale" in slices
            k_fp, v_fp = k, v
            if quant:
                k, k_s = quantize_kv(k)                  # k_s [1, C, Hkv]
                v, v_s = quantize_kv(v)
                k_s = k_s.astype(slices["k_scale"].dtype)
                v_s = v_s.astype(slices["v_scale"].dtype)
            idx = jnp.mod(positions, smax)                       # [C]
            keep = (jnp.arange(c) <= last)[:, None, None]
            k_slot = jax.lax.dynamic_slice(slices["k"], (slot, 0, 0, 0),
                                           (1, smax, hkv, dh))
            v_slot = jax.lax.dynamic_slice(slices["v"], (slot, 0, 0, 0),
                                           (1, smax, hkv, dh))
            k_slot = k_slot.at[0, idx].set(
                jnp.where(keep, k[0].astype(k_slot.dtype), k_slot[0, idx]))
            v_slot = v_slot.at[0, idx].set(
                jnp.where(keep, v[0].astype(v_slot.dtype), v_slot[0, idx]))
            kc = jax.lax.dynamic_update_slice(slices["k"], k_slot,
                                              (slot, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(slices["v"], v_slot,
                                              (slot, 0, 0, 0))
            if not quant:
                return k_slot, v_slot, kc, vc
            # same keep-masked ring scatter on the scale planes,
            # position-major for the gather then back to [1, Hkv, R]
            keep_s = (jnp.arange(c) <= last)[:, None]
            ks_t = jnp.swapaxes(jax.lax.dynamic_slice(
                slices["k_scale"], (slot, 0, 0), (1, hkv, smax))[0], 0, 1)
            vs_t = jnp.swapaxes(jax.lax.dynamic_slice(
                slices["v_scale"], (slot, 0, 0), (1, hkv, smax))[0], 0, 1)
            ks_t = ks_t.at[idx].set(jnp.where(keep_s, k_s[0], ks_t[idx]))
            vs_t = vs_t.at[idx].set(jnp.where(keep_s, v_s[0], vs_t[idx]))
            new["k_scale"] = jax.lax.dynamic_update_slice(
                slices["k_scale"], jnp.swapaxes(ks_t, 0, 1)[None],
                (slot, 0, 0))
            new["v_scale"] = jax.lax.dynamic_update_slice(
                slices["v_scale"], jnp.swapaxes(vs_t, 0, 1)[None],
                (slot, 0, 0))
            k_att = k_slot.astype(jnp.float32) * ks_t[None, :, :, None]
            v_att = v_slot.astype(jnp.float32) * vs_t[None, :, :, None]
            # fresh-fp overlay of the current chunk's ring slots
            k_att = k_att.at[0, idx].set(
                jnp.where(keep, k_fp[0].astype(jnp.float32), k_att[0, idx]))
            v_att = v_att.at[0, idx].set(
                jnp.where(keep, v_fp[0].astype(jnp.float32), v_att[0, idx]))
            return k_att, v_att, kc, vc

        def step(x, xs):
            bp, slices = xs
            new = {}
            ap = bp["attn"]
            with jax.named_scope("qkv"):
                h = rms_norm(x, bp["ln1"], cfg.norm_eps)
                q, k, v = self._qkv_rope(ap, h, positions)
            if ring:
                with jax.named_scope("kv_write"):
                    k_att, v_att, kc, vc = ring_fill(slices, new, k, v)
                with jax.named_scope("attention"):
                    attn = attn_lib.prefill_attention_ring(
                        q, k_att, v_att, positions, offset + last,
                        window=cfg.window)
            else:
                with jax.named_scope("kv_write"):
                    k_fp, v_fp = k, v
                    quant = "k_scale" in slices
                    if quant:
                        # int8 cache: chunk K/V quantize per (position,
                        # head); the scale planes land at the chunk's rows
                        k, k_s = quantize_kv(k)          # k_s [1, C, Hkv]
                        v, v_s = quantize_kv(v)
                        new["k_scale"] = jax.lax.dynamic_update_slice(
                            slices["k_scale"],
                            jnp.swapaxes(k_s, 1, 2).astype(
                                slices["k_scale"].dtype), (slot, 0, offset))
                        new["v_scale"] = jax.lax.dynamic_update_slice(
                            slices["v_scale"],
                            jnp.swapaxes(v_s, 1, 2).astype(
                                slices["v_scale"].dtype), (slot, 0, offset))
                    kc = jax.lax.dynamic_update_slice(
                        slices["k"], k.astype(slices["k"].dtype),
                        (slot, offset, 0, 0))
                    vc = jax.lax.dynamic_update_slice(
                        slices["v"], v.astype(slices["v"].dtype),
                        (slot, offset, 0, 0))
                with jax.named_scope("attention"):
                    k_slot = jax.lax.dynamic_slice(kc, (slot, 0, 0, 0),
                                                   (1, smax, hkv, dh))
                    v_slot = jax.lax.dynamic_slice(vc, (slot, 0, 0, 0),
                                                   (1, smax, hkv, dh))
                    if quant:
                        # the chunk attends *through the cache slot* (unlike
                        # full prefill), so the slot reads dequantize
                        # whole-row, and the current chunk's own positions
                        # are overlaid with their fresh fp values —
                        # quantization noise enters a chunk's attention only
                        # through the *already-written* prefix, the part
                        # that is genuinely stored int8 at read time. This
                        # keeps single-chunk prompts bit-identical to the
                        # lock-step quantized prefill (which attends fp K/V
                        # throughout) and the measured agreement tier tight.
                        ks_slot = jax.lax.dynamic_slice(
                            new["k_scale"], (slot, 0, 0), (1, hkv, smax))
                        vs_slot = jax.lax.dynamic_slice(
                            new["v_scale"], (slot, 0, 0), (1, hkv, smax))
                        k_slot = (k_slot.astype(jnp.float32)
                                  * jnp.swapaxes(ks_slot, 1, 2)[..., None])
                        v_slot = (v_slot.astype(jnp.float32)
                                  * jnp.swapaxes(vs_slot, 1, 2)[..., None])
                        k_slot = jax.lax.dynamic_update_slice(
                            k_slot, k_fp.astype(jnp.float32),
                            (0, offset, 0, 0))
                        v_slot = jax.lax.dynamic_update_slice(
                            v_slot, v_fp.astype(jnp.float32),
                            (0, offset, 0, 0))
                    attn = attn_lib.prefill_attention(
                        q, k_slot, v_slot, causal=True, window=cfg.window,
                        kv_lengths=kv_len, q_offset=q_off,
                        kv_block=cfg.attn_block or 512)
            with jax.named_scope("attn_out"):
                attn_out = linear(ap, "wo", attn.reshape(1, c, -1))
            new["k"], new["v"] = kc, vc
            if cfg.family == "hybrid":
                d_inner = cfg.ssm_expand * cfg.d_model
                conv0 = jax.lax.dynamic_slice(
                    slices["mamba_conv"], (slot, 0, 0),
                    (1, cfg.ssm_conv - 1, d_inner))
                ssm0 = jax.lax.dynamic_slice(
                    slices["mamba_ssm"], (slot, 0, 0),
                    (1, d_inner, cfg.ssm_state))
                m_out, mst = mamba_lib.mamba_forward(
                    bp["mamba"], h, return_state=True,
                    state=mamba_lib.MambaState(conv=conv0, ssm=ssm0),
                    n_valid=n_valid)
                new["mamba_conv"] = jax.lax.dynamic_update_slice(
                    slices["mamba_conv"], mst.conv, (slot, 0, 0))
                new["mamba_ssm"] = jax.lax.dynamic_update_slice(
                    slices["mamba_ssm"], mst.ssm, (slot, 0, 0))
                x = x + 0.5 * (rms_norm(attn_out, bp["ln_attn_out"],
                                        cfg.norm_eps)
                               + rms_norm(m_out, bp["ln_mamba_out"],
                                          cfg.norm_eps))
            else:
                with jax.named_scope("attn_out"):
                    x = x + attn_out
            if "cross" in bp and "src_k" in slices:   # whisper-style in-layer
                hc = rms_norm(x, bp["ln_cross"], cfg.norm_eps)
                x = x + cross_read(bp["cross"], hc, slices["src_k"],
                                   slices["src_v"],
                                   slices.get("src_k_scale"),
                                   slices.get("src_v_scale"))
            with jax.named_scope("mlp"):
                h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
                if cfg.n_experts:
                    # capacity = chunk length C: each token assigns an
                    # expert at most once, so per-expert load <= C and
                    # nothing can drop — drop-free capacity dispatch equals
                    # the per-row form exactly, padded positions can't evict
                    # real tokens, and the [E, C, d] queue stays small (the
                    # per-row dense gather would materialize C*k full expert
                    # matrices per layer)
                    y, _ = moe_lib.moe_apply(bp["ffn"], h2, top_k=cfg.top_k,
                                             act=cfg.act,
                                             gated=cfg.gated_mlp, capacity=c)
                else:
                    y = mlp_apply(bp["ffn"], h2, cfg.act, cfg.gated_mlp)
                return x + y, new

        self_slices = {"k": cache["k"], "v": cache["v"]}
        if "k_scale" in cache:
            self_slices["k_scale"] = cache["k_scale"]
            self_slices["v_scale"] = cache["v_scale"]
        if cfg.family == "hybrid":
            self_slices["mamba_conv"] = cache["mamba_conv"]
            self_slices["mamba_ssm"] = cache["mamba_ssm"]
        if cfg.cross_attn_every == 1 and pooled_src:   # whisper-style
            self_slices["src_k"] = cache["src_k"]
            self_slices["src_v"] = cache["src_v"]
            if "src_k_scale" in cache:
                self_slices["src_k_scale"] = cache["src_k_scale"]
                self_slices["src_v_scale"] = cache["src_v_scale"]

        n_cross = self._n_cross_groups()
        if not n_cross:
            x, new = layer_scan(step, x, (params["blocks"], self_slices),
                                unroll=cfg.unroll_layers)
        else:                                          # vlm: dedicated cross
            group = cfg.cross_attn_every
            n_self_per = group - 1
            cross_xs = ((cache["src_k"], cache["src_v"]) if pooled_src
                        else ())
            if pooled_src and "src_k_scale" in cache:
                cross_xs += (cache["src_k_scale"], cache["src_v_scale"])

            def group_step(x, xs):
                gp, gs, cp, *skv = xs
                x, new = layer_scan(step, x, (gp, gs),
                                    unroll=cfg.unroll_layers)
                if pooled_src:
                    hc = rms_norm(x, cp["ln1"], cfg.norm_eps)
                    x = x + cross_read(cp["cross"], hc, skv[0], skv[1],
                                       *(skv[2:4] if len(skv) > 2
                                         else (None, None)))
                h2 = rms_norm(x, cp["ln2"], cfg.norm_eps)
                x = x + mlp_apply(cp["ffn"], h2, cfg.act, cfg.gated_mlp)
                return x, new

            gp = jax.tree.map(
                lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:]),
                params["blocks"])
            gs = jax.tree.map(
                lambda a: a.reshape(n_cross, n_self_per, *a.shape[1:]),
                self_slices)
            x, new = layer_scan(group_step, x,
                                (gp, gs, params["cross_blocks"], *cross_xs),
                                unroll=cfg.unroll_layers)
            new = jax.tree.map(
                lambda a: a.reshape(n_cross * n_self_per, *a.shape[2:]), new)
        cache = dict(cache)
        for key, val in new.items():
            cache[key] = val
        with jax.named_scope("lm_head"):
            x_last = jax.lax.dynamic_slice(x, (0, last, 0),
                                           (1, 1, cfg.d_model))[:, 0]
            x_last = rms_norm(x_last, params["ln_f"], cfg.norm_eps)
            return self._unembed(params, x_last)[0], cache

    def _rwkv_prefill_chunk(self, params: Params, tokens: jax.Array,
                            cache: Cache, slot: jax.Array, last: jax.Array
                            ) -> tuple[jax.Array, Cache]:
        """One prompt chunk through the RWKV stack for a single slot: the
        slot's per-layer (x_prev, wkv) state seeds the chunk scan and the
        post-chunk state is written back, so successive chunks compose into
        exactly the full-prompt recurrence. Positions past ``last`` are
        padding — masked into state no-ops inside the mix kernels. The slot
        has no KV rows; ``offset`` is implicit in the carried state."""
        cfg = self.cfg
        x = params["embed"].astype(self._dt)[tokens][None]       # [1, C, d]
        n_valid = last + 1
        att0 = jax.lax.dynamic_slice_in_dim(cache["rwkv_att"], slot, 1, axis=1)
        ffn0 = jax.lax.dynamic_slice_in_dim(cache["rwkv_ffn"], slot, 1, axis=1)
        wkv0 = jax.lax.dynamic_slice_in_dim(cache["rwkv_wkv"], slot, 1, axis=1)

        def step(x, xs):
            bp, att_prev, ffn_prev, wkv = xs                     # [1, ...]
            st = rwkv_lib.RWKVLayerState(att_prev.astype(self._dt),
                                         ffn_prev.astype(self._dt), wkv)
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            y, st = rwkv_lib.rwkv_time_mix(bp["mix"], h, st,
                                           cfg.rwkv_head_dim, n_valid=n_valid)
            x = x + y
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            y2, st = rwkv_lib.rwkv_channel_mix(bp["mix"], h2, st,
                                               n_valid=n_valid)
            return x + y2, (st.x_prev_att.astype(att_prev.dtype),
                            st.x_prev_ffn.astype(ffn_prev.dtype), st.wkv)

        x, (att, ffn, wkv) = layer_scan(step, x,
                                        (params["blocks"], att0, ffn0, wkv0),
                                        unroll=cfg.unroll_layers)
        cache = dict(
            cache,
            rwkv_att=jax.lax.dynamic_update_slice_in_dim(
                cache["rwkv_att"], att, slot, axis=1),
            rwkv_ffn=jax.lax.dynamic_update_slice_in_dim(
                cache["rwkv_ffn"], ffn, slot, axis=1),
            rwkv_wkv=jax.lax.dynamic_update_slice_in_dim(
                cache["rwkv_wkv"], wkv, slot, axis=1))
        x_last = jax.lax.dynamic_slice(x, (0, last, 0),
                                       (1, 1, cfg.d_model))[:, 0]
        x_last = rms_norm(x_last, params["ln_f"], cfg.norm_eps)
        return self._unembed(params, x_last)[0], cache

    def prefill_chunks_batched(self, params: Params, tokens: jax.Array,
                               cache: Cache, slots: jax.Array,
                               offsets: jax.Array, lasts: jax.Array,
                               valid: jax.Array) -> tuple[jax.Array, Cache]:
        """Advance N mid-prefill slots one prompt chunk each in a *single*
        dispatch: a ``lax.scan`` over rows, each applying the
        :meth:`prefill_chunk` body for its own (slot, offset). Slots write
        disjoint cache rows / state entries, so the sequential in-program
        application is exactly equivalent to N separate ``prefill_chunk``
        calls — it just costs one host round-trip instead of N (the
        continuous engine's per-step prefill loop was one dispatch *per
        slot* before this). Rows with ``valid=False`` are skipped via
        ``lax.cond`` (zero logits, cache untouched), so the program
        compiles once at a fixed N = n_slots regardless of how many slots
        are mid-prefill.

        tokens: [N, C] int32; slots/offsets/lasts: [N] int32; valid: [N]
        bool. Returns (logits [N, V] f32 — row i meaningful only on request
        i's final chunk, matching prefill_chunk's contract — and the
        updated cache)."""
        vocab = self.cfg.vocab_size

        def row(cache, xs):
            toks, slot, off, last, ok = xs

            def run(c):
                return self.prefill_chunk(params, toks, c, slot, off, last)

            def skip(c):
                return jnp.zeros((vocab,), jnp.float32), c

            logits, cache = jax.lax.cond(ok, run, skip, cache)
            return cache, logits

        cache, logits = jax.lax.scan(
            row, cache, (tokens, slots, offsets, lasts, valid))
        return logits, cache

    # ---- source-KV pool (cross-attention continuous serving) ---------------
    def ingest_source(self, params: Params, source: jax.Array, cache: Cache,
                      entry: jax.Array, length: jax.Array) -> Cache:
        """Write one source's encoder-side cross K/V into pool entry
        ``entry`` — the write-once half of the source-KV pool contract
        (computed at admission, read-only for every decode tick after).

        source: [S_max, d] frontend features, padded to the pool row size;
        ``length``: the valid prefix. Each cross layer's ``wk``/``wv``
        projects the source once (no RoPE — cross keys are position-free,
        matching :meth:`prefill`'s ``with_rope=False``); rows past
        ``length`` are zeroed so a pool entry's device state is exactly
        (real K/V, zeros) — never a previous occupant's tail — and every
        read additionally masks by ``src_len``. The caller
        (``repro.serving.continuous``) owns the host-side ledger
        (``SourceKVPool``): which entry a source id maps to, refcounts, and
        when :meth:`release_source` may zero the entry."""
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        src = source.astype(self._dt)                        # [S_max, d]
        stacked = (params["cross_blocks"] if cfg.cross_attn_every > 1
                   else params["blocks"])

        def proj(bp):
            p = bp["cross"]
            k = linear(p, "wk", src).reshape(-1, cfg.n_kv_heads, dh)
            v = linear(p, "wv", src).reshape(-1, cfg.n_kv_heads, dh)
            if cfg.qk_norm:
                k = rms_norm(k, p["kn"], cfg.norm_eps)
            return k, v

        ks, vs = jax.vmap(proj)(stacked)                     # [Lc, S, Hkv, Dh]
        keep = (jnp.arange(ks.shape[1]) < length)[None, :, None, None]
        ks = jnp.where(keep, ks, 0)
        vs = jnp.where(keep, vs, 0)
        cache = dict(cache)
        if "src_k_scale" in cache:
            # int8 pool: quantize after the tail zeroing so padded rows get
            # (0, scale 0) — the entry's device state stays inspectably zero
            ks, k_s = quantize_kv(ks)                    # k_s [Lc, S, Hkv]
            vs, v_s = quantize_kv(vs)
            cache["src_k_scale"] = jax.lax.dynamic_update_slice(
                cache["src_k_scale"],
                jnp.swapaxes(k_s, 1, 2)[:, None].astype(
                    cache["src_k_scale"].dtype),
                (0, entry, 0, 0))
            cache["src_v_scale"] = jax.lax.dynamic_update_slice(
                cache["src_v_scale"],
                jnp.swapaxes(v_s, 1, 2)[:, None].astype(
                    cache["src_v_scale"].dtype),
                (0, entry, 0, 0))
        ks = ks.astype(cache["src_k"].dtype)
        vs = vs.astype(cache["src_v"].dtype)
        cache["src_k"] = jax.lax.dynamic_update_slice(
            cache["src_k"], ks[:, None], (0, entry, 0, 0, 0))
        cache["src_v"] = jax.lax.dynamic_update_slice(
            cache["src_v"], vs[:, None], (0, entry, 0, 0, 0))
        cache["src_len"] = cache["src_len"].at[entry].set(
            jnp.asarray(length, jnp.int32))
        return cache

    def assign_source(self, cache: Cache, slot: jax.Array,
                      entry: jax.Array) -> Cache:
        """Point a slot's cross-attention reads at pool entry ``entry``
        (``src_index[slot] = entry``). Sharing is this one int: any number
        of slots may map to the same entry."""
        return dict(cache, src_index=cache["src_index"].at[slot].set(
            jnp.asarray(entry, jnp.int32)))

    def release_source(self, cache: Cache, entry: jax.Array) -> Cache:
        """Zero a pool entry's source K/V rows and its ``src_len`` — called
        only when the entry's last reference retired (the ``SourceKVPool``
        ledger decides). After this, any slot still pointing at the entry
        (an inactive slot whose output is discarded anyway) reads a
        fully-masked zero; a backfilled request can never see the previous
        occupant's encoder state."""
        cache = dict(cache)
        cache["src_k"] = cache["src_k"].at[:, entry].set(0)
        cache["src_v"] = cache["src_v"].at[:, entry].set(0)
        cache["src_len"] = cache["src_len"].at[entry].set(0)
        for key in ("src_k_scale", "src_v_scale"):
            if key in cache:
                cache[key] = cache[key].at[:, entry].set(0)
        return cache

    def finalize_slot(self, cache: Cache, slot: jax.Array,
                      length: jax.Array) -> Cache:
        """Commit a slot's chunked prefill: set its live length and reseed
        its incremental-RoPE angle state at position ``length`` (direct mode
        recomputes from ``len`` and needs no per-slot state). Everything in
        the slot past ``length`` is dead until decode overwrites it."""
        cfg = self.cfg
        length = jnp.asarray(length, jnp.int32)
        cache = dict(cache, len=cache["len"].at[slot].set(length))
        if cfg.rotary_dim and cfg.rope_mode == "incremental":
            rs = rope_lib.rope_state_init(cfg.resolved_head_dim,
                                          cfg.rope_base, length,
                                          cfg.rotary_dim)
            cache["rope_cos"] = cache["rope_cos"].at[slot].set(rs.cos_m)
            cache["rope_sin"] = cache["rope_sin"].at[slot].set(rs.sin_m)
        return cache

    def release_slot(self, cache: Cache, slot: jax.Array) -> Cache:
        """Reset-on-release: drop the slot's length to zero so nothing in
        its KV rows is attended again; the next occupant's prefill
        overwrites the contents in place. Recurrent state (RWKV x_prev/wkv,
        Mamba conv/ssm) is *zeroed*, not just ignored — unlike KV rows it
        feeds forward multiplicatively, so the next occupant's first chunk
        must start from the empty-context state. Ring KV rows are zeroed
        too: the ring position-recovery formula already masks a previous
        occupant's stale slots (their recovered position is negative until
        the new request wraps), but zeroing keeps the reset contract
        uniform and inspectable — after release a slot's device state is
        all-zeros for every family."""
        cache = dict(cache, len=cache["len"].at[slot].set(0))
        for key in ("rwkv_att", "rwkv_ffn", "rwkv_wkv",
                    "mamba_conv", "mamba_ssm"):
            if key in cache:
                cache[key] = cache[key].at[:, slot].set(0)
        if (self.cfg.kv_ring and self.cfg.window) or "k_scale" in cache:
            # ring caches zero for the uniform-reset contract; int8 caches
            # additionally zero so a released slot's (rows, scales) pair is
            # all-zeros — scale 0 means a stale row can never dequantize to
            # a previous occupant's value even if misread
            for key in ("k", "v", "k_scale", "v_scale"):
                if key in cache:
                    cache[key] = cache[key].at[:, slot].set(0)
        return cache

    def _rwkv_prefill(self, params: Params, x: jax.Array,
                      cache: Cache) -> tuple[jax.Array, Cache]:
        cfg = self.cfg
        b, sp, _ = x.shape
        h_heads = cfg.d_model // cfg.rwkv_head_dim

        def step(x, bp):
            st0 = rwkv_lib.RWKVLayerState(
                x_prev_att=jnp.zeros((b, cfg.d_model), x.dtype),
                x_prev_ffn=jnp.zeros((b, cfg.d_model), x.dtype),
                wkv=jnp.zeros((b, h_heads, cfg.rwkv_head_dim,
                               cfg.rwkv_head_dim), jnp.float32))
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            y, st = rwkv_lib.rwkv_time_mix(bp["mix"], h, st0, cfg.rwkv_head_dim)
            x = x + y
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            y2, st = rwkv_lib.rwkv_channel_mix(bp["mix"], h2, st)
            return x + y2, (st.x_prev_att, st.x_prev_ffn, st.wkv)

        x, (att, ffn, wkv) = layer_scan(step, x, params["blocks"], unroll=cfg.unroll_layers)
        cache = dict(cache, rwkv_att=att, rwkv_ffn=ffn, rwkv_wkv=wkv,
                     len=jnp.full_like(cache["len"], sp))
        x = rms_norm(x[:, -1, :], params["ln_f"], cfg.norm_eps)
        return self._unembed(params, x), cache

    def _rwkv_decode_step(self, params: Params, x: jax.Array, cache: Cache,
                          active: jax.Array | None = None
                          ) -> tuple[jax.Array, Cache]:
        cfg = self.cfg

        def step(x, xs):
            bp, att_prev, ffn_prev, wkv = xs
            st = rwkv_lib.RWKVLayerState(att_prev.astype(self._dt),
                                         ffn_prev.astype(self._dt), wkv)
            h = rms_norm(x, bp["ln1"], cfg.norm_eps)
            # ragged batch: inactive rows are exact state no-ops — masked at
            # the state-update site in rwkv6.py
            y, st = rwkv_lib.rwkv_time_mix_step(bp["mix"], h, st,
                                                cfg.rwkv_head_dim,
                                                active=active)
            x = x + y
            h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
            y2, st = rwkv_lib.rwkv_channel_mix_step(bp["mix"], h2, st,
                                                    active=active)
            return x + y2, (st.x_prev_att, st.x_prev_ffn, st.wkv)

        x, (att, ffn, wkv) = layer_scan(
            step, x, (params["blocks"], cache["rwkv_att"], cache["rwkv_ffn"],
                      cache["rwkv_wkv"]), unroll=cfg.unroll_layers)
        cache = dict(cache, rwkv_att=att, rwkv_ffn=ffn, rwkv_wkv=wkv,
                     len=cache["len"] + (1 if active is None
                                         else active.astype(jnp.int32)))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._unembed(params, x), cache
