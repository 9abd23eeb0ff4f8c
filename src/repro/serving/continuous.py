"""Continuous-batching serving engine: slot pool -> scheduler -> ragged
chunked prefill -> static-shape ragged decode, with **multi-tick decode
blocks** — the per-token host round-trip collapsed into one dispatch per K
tokens.

The jit'd decode program always runs at ``[n_slots]`` batch shape; an
``active`` mask carries which slots hold live requests. Each engine step:

1. **admit** — backfill free slots from the admission queue. Cross-
   attention configs (vlm / audio) also resolve each admitted request's
   **source-KV pool** entry here: an already-resident source id is shared
   by refcount (zero encoder work), a fresh one is ingested once
   (``TransformerLM.ingest_source``) and the slot's ``src_index`` pointed
   at it — before the request's first prefill chunk, whose cross reads
   need the entry resident;
2. **prefill** — every mid-prefill slot advances by one prompt chunk in a
   *single* batched dispatch (``TransformerLM.prefill_chunks_batched``), so
   long prompts never stall in-flight decodes for more than one chunk's
   latency and N prefilling slots cost one host round-trip, not N; a
   request whose final chunk lands is committed (``finalize_slot``), its
   first token sampled from the chunk logits, and its slot joins the active
   set;
3. **decode** — one ``decode_multi`` block of K ragged ticks
   (``lax.scan`` over the decode step with fused sampling and *on-device
   retirement*: per-slot EOS / budget counters flip a row's ``active`` bit
   mid-scan, the freed row parking its writes exactly like any inactive
   row), then one host sync consumes the ``[K, n_slots]`` token block
   post-hoc — per-tick retirement bookkeeping replayed from the block,
   slots released, freed slots backfilled at the next step's admission.

The tick horizon adapts per dispatch::

    K = min(decode_ticks, min remaining budget among active rows)
    K = 1 while admissions or prefill chunks are waiting   # TTFT first
    K floored to a power of two                            # bounded compiles

so at most ``log2(decode_ticks) + 1`` decode programs ever compile and a
freed or newly-prefilled slot joins the batch at the next tick, never K
ticks late.

Greedy outputs are token-for-token identical to per-request
``ServingEngine.generate`` at every tick horizon (tested in
tests/test_serving_continuous.py and tests/test_decode_multi.py): the
scanned block body IS the single-tick ``decode_step(active=...)``, so
chunked prefill reuses the same blockwise ``prefill_attention`` math,
masked-out cache rows are exact no-ops in the (mu, Z, Y) recurrence,
recurrent-state rows (ssm / hybrid) carry through masked ticks unchanged,
and MoE rows use the capacity-free per-row dispatch.

Ring KV configs (``kv_ring`` SWA archs) serve with **O(window) slots**:
``init_cache(chunk=...)`` allocates ``[n_slots, round128(window + chunk),
Hkv, D]`` rings (the chunked-prefill exactness bound ``ring_len >= window
+ chunk - 1`` holds by construction), chunked prefill writes at ``pos %
ring_len`` (a prompt longer than the window wraps over its own
out-of-window entries), parked rows use a per-slot write mask instead of
the reserved tail row, and the decode kernels consume the ring in place.
``report()``'s ``kv_bytes_per_slot`` / ``kv_rows_per_slot`` lines make the
memory win a measured number.

Cross-attention stacks serve through the source-KV pool: slots map to
refcounted, read-only encoder-side K/V entries keyed by source id
(``slot_pool.SourceKVPool`` holds the ledger; ``docs/serving.md`` the
lifecycle). Rows with heterogeneous source lengths coexist in one
static-shape dispatch — each read masks its own entry's ``src_len`` — and
entries are zeroed only when their last holder retires, so slot reuse
never leaks a predecessor's encoder state. ``source_ingests`` /
``source_shares`` in ``report()`` carry the dedup win.

Sampling (temperature > 0) is fused into the jit'd block as seeded per-slot
Gumbel-max (``argmax(logits/T + g)`` with ``g ~ Gumbel(0,1)`` is exactly a
softmax(logits/T) draw). Keys derive from ``(seed, request admission
serial, token index)`` — properties of the *request*, not of the engine's
step counters or the tick horizon — so a request's sampled tokens are
independent of batch composition, of how prefill chunks and decode blocks
interleave, *and of K itself*: the same (seed, trace) replays
token-for-token at decode_ticks 1, 4, or 8.

Host syncs are **block-granular**: a K-block's tokens all become available
at the block's one sync. Per-token timestamps inside a block are attributed
by **even subdivision** of the block's wall span (token at tick t stamped
``block_start + (t+1)/K * span``; labeled ``itl_source: "subdivided"`` in
the report), so ITL percentiles estimate per-token latency instead of
quantizing to ~K-token blocks; ``itl_effective_ms`` (wall seconds per
generated token) remains the exact denominator. TTFT / ITL percentiles come
from fixed-size mergeable log-bucket histograms
(``repro.serving.telemetry.LogHistogram`` — O(1) insert, exact to within
one ~15% bucket), not unbounded sorted lists. Dispatch accounting
(``dispatches``, ``host_syncs``, ``dispatches_per_token``) makes the
round-trip collapse measurable, and ``parked_ticks`` (ticks issued minus
tokens emitted) measures the mid-block-retirement waste the eos-aware
horizon would recover.

Observability, two layers. Each step's phases are profiler spans, always
on: ``serve.admit``, ``serve.prefill``, ``serve.first_token`` (per finished
prompt), ``serve.decode``, ``serve.sync``, ``serve.retire``
(``repro.serving.telemetry.SPAN_NAMES``). Each is one
``jax.profiler.TraceAnnotation`` and records nothing unless a profiler
session is active; under ``jax.profiler.trace`` they share the device
trace's clock, and the decode and prefill programs' ops carry the model's
named scopes (``repro.models.transformer.SCOPE_NAMES``). Pass
``telemetry=Telemetry()`` to also record the structured lifecycle event
stream (enqueue/admit/backfill, source pool ledger events, prefill_chunk,
first_token, decode_block, eos/budget_retire/release) plus per-block engine
gauges, exportable to Chrome/Perfetto trace format — see
``repro.serving.telemetry`` and ``docs/serving.md``; ``decode_block`` and
``prefill_chunk`` take their ``dur`` from the span boundaries. Every
emission site is guarded, so the default (``telemetry=None``) path builds
no event objects: byte-identical tokens, zero events.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import seeded_gumbel_pick

from .audit import EngineAuditor
from .faults import FaultInjected, FaultPlan
from .scheduler import (OverloadConfig, Request, RequestState, Scheduler,
                        DECODING, PREFILLING, QUEUED)
from .slot_pool import KVSlotPool, SourceKVPool
from .telemetry import LogHistogram, Telemetry, span


def _pct(xs, q):
    """Nearest-rank percentile of an ascending-sorted list: element
    ceil(q*n)-1 (so p50 of [a, b] is a, and p95 only hits the max within
    5% of the tail) — truncation indexing overshoots on short lists.

    ``report()`` now takes its percentiles from the fixed-size
    ``LogHistogram`` stream instead of unbounded sorted lists; this exact
    form remains the reference the histogram is tested against
    (``tests/test_telemetry.py``: agreement within one bucket)."""
    if not xs:
        return None
    return round(float(xs[max(0, math.ceil(q * len(xs)) - 1)]), 4)


class ContinuousBatchingEngine:
    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 chunk: int = 16, eos_id: int | None = None,
                 pad_id: int = 0, temperature: float = 0.0, seed: int = 0,
                 decode_ticks: int = 1, source_len: int | None = None,
                 telemetry: Telemetry | None = None,
                 overload: OverloadConfig | None = None,
                 faults: FaultPlan | None = None,
                 auditor: EngineAuditor | None = None):
        if not getattr(model, "supports_ragged_serving", lambda: False)():
            raise ValueError(
                f"{model.cfg.name}: model does not claim ragged serving "
                "(supports_ragged_serving() is False)")
        if chunk < 1 or max_len % chunk:
            raise ValueError(f"chunk ({chunk}) must divide max_len "
                             f"({max_len}) so padded chunks stay in range")
        if decode_ticks < 1:
            raise ValueError(f"decode_ticks must be >= 1, got {decode_ticks}")
        if getattr(model.cfg, "w4a8_serve", False):
            # +w4a8 config: one-shot W4 weight quantization at engine
            # construction. Deterministic (no RNG), so the seeded-sampling
            # replay contract survives unchanged; the int8 KV side rides on
            # init_cache's dtype default below. The fp32 host loop is
            # untouched — quantization is entirely a params/cache property.
            from repro.models.quantized import quantize_params
            params = quantize_params(params)
        self.model, self.params = model, params
        self.chunk, self.eos_id, self.pad_id = chunk, eos_id, pad_id
        self.temperature = temperature
        self.max_ticks = decode_ticks
        self._t0 = time.perf_counter()          # reset by run()
        # telemetry: self._sink is None when disabled, so every emission
        # site below is a single falsy check — the disabled path builds no
        # event objects (the profiler spans are separate and always on)
        self.tel = telemetry
        if telemetry is None:
            self._sink = None
        else:
            def _sink(kind, t=None, **data):
                telemetry.emit(
                    kind, t=(time.perf_counter() - self._t0
                             if t is None else t), **data)
            self._sink = _sink
        self.pool = KVSlotPool(n_slots, max_len)
        self.sched = Scheduler(self.pool, on_event=self._sink,
                               overload=overload)
        # robustness knobs — all default-off; every consult site below is a
        # single falsy/None check, so the disabled engine runs the exact
        # pre-robustness host loop (same contract as telemetry)
        self.faults = faults          # FaultPlan | None; settable post-warmup
        self.auditor = auditor        # EngineAuditor | None
        self._draining = False
        self._interrupted = False
        self._cancels: set = set()
        self._n_deadlined = 0         # submitted requests carrying an SLO
        self._shed_seen = 0           # sched.shed prefix whose serials are
                                      # already reclaimed
        self.dispatch_retries = 0
        # service-time EWMAs for the submit-time predicted-TTFT gate:
        # per-prefill-chunk dispatch wall and per-request slot-hold time
        self._chunk_s = 0.0
        self._svc_s = 0.0
        self._prefill_batched = jax.jit(model.prefill_chunks_batched,
                                        donate_argnums=(2,))
        self._finalize = jax.jit(model.finalize_slot, donate_argnums=(0,))
        self._release = jax.jit(model.release_slot, donate_argnums=(0,))

        # cross-attention stacks (vlm / audio): a second, refcounted pool
        # holds the encoder-side K/V, keyed by source id — ingested once at
        # admission, shared read-only by every slot whose request presents
        # the same id, zeroed when the last holder retires. n_entries ==
        # n_slots, so an entry is always available when a slot is
        # (each live request holds at most one reference).
        from repro.models.api import needs_source
        cfg = model.cfg
        self.needs_source = needs_source(cfg)
        self.src_pool = None
        if self.needs_source:
            self.src_max = source_len or cfg.source_len
            self.src_pool = SourceKVPool(n_slots, self.src_max,
                                         on_event=self._sink)
            self._srcs: dict = {}           # rid -> held source id
            self._ingest = jax.jit(model.ingest_source, donate_argnums=(2,))
            self._assign = jax.jit(model.assign_source, donate_argnums=(0,))
            self._src_release = jax.jit(model.release_source,
                                        donate_argnums=(0,))

        # sampler keys: (seed, request admission serial, token index) —
        # request-intrinsic, so a draw can't depend on batch composition,
        # on how the scheduler interleaved prefill chunks with decode
        # blocks, or on the tick horizon K
        self._base_key = jax.random.PRNGKey(seed)
        self._decode_fns: dict = {}     # (tick horizon K, poisoned) -> jit

        def _prefill_pick(logits_row, serial):
            # first token off a finalized prefill: [V] -> scalar int32.
            # Token index 0 of the SAME (seed, serial, idx) key stream the
            # fused decode draws tokens 1..n from (seeded_gumbel_pick is
            # the single shared definition)
            if temperature == 0.0:
                return jnp.argmax(logits_row).astype(jnp.int32)
            return seeded_gumbel_pick(self._base_key, logits_row, serial,
                                      jnp.int32(0), temperature)
        self._prefill_pick = jax.jit(_prefill_pick)

        self.cache = model.init_cache(
            n_slots, max_len,
            self.src_max if self.needs_source else None,
            n_sources=n_slots if self.needs_source else None,
            chunk=chunk)
        if cfg.kv_ring and cfg.window and "k" in self.cache:
            # ring-prefill exactness bound: a chunk's later tokens may
            # overwrite ring slots its earlier queries still need unless
            # the overwritten positions are already outside every live
            # window — guaranteed iff ring_len >= window + chunk - 1.
            # init_cache(chunk=...) sizes the ring as round128(window +
            # chunk) precisely so this holds by construction (degenerating
            # to the never-wrapping full cache when that reaches max_len),
            # so the check below is a safety invariant, not a user-facing
            # constraint.
            ring_len = int(self.cache["k"].shape[2])
            if ring_len < max_len and chunk > ring_len - cfg.window + 1:
                raise ValueError(
                    f"chunk ({chunk}) too large for the ring: a "
                    f"{ring_len}-slot ring over window {cfg.window} "
                    f"supports chunks up to {ring_len - cfg.window + 1} "
                    "(ring_len >= window + chunk - 1 keeps chunked "
                    "prefill exact under wraparound)")
        # gauge precompute: self-attention KV bytes per (slot, row) — the
        # live-KV gauge is sum_over_active(min(len, rows)) * this
        self._kv_rows = (int(self.cache["k"].shape[2])
                         if "k" in self.cache else 0)
        kv_self = [self.cache[k] for k in ("k", "v", "k_scale", "v_scale")
                   if k in self.cache]
        self._kv_row_bytes = (
            sum(int(a.size) * a.dtype.itemsize for a in kv_self)
            // (n_slots * self._kv_rows) if self._kv_rows else 0)
        # streaming latency stats: fixed-size mergeable log-bucket
        # histograms (seconds), reset per run — report() percentiles come
        # from these, not from unbounded per-token lists
        self.hist_ttft = LogHistogram()
        self.hist_itl = LogHistogram()
        self.tok = np.full((n_slots,), pad_id, np.int32)
        self.active = np.zeros((n_slots,), bool)
        # per-slot sampler / retirement state, mirrored on device per block:
        # admission serial of the occupying request, tokens emitted so far
        # (the next draw's token index), and the request's total allowance
        self.serial = np.zeros((n_slots,), np.int32)
        self.emitted = np.zeros((n_slots,), np.int32)
        self.budget = np.zeros((n_slots,), np.int32)
        self._serials: dict = {}        # rid -> serial, mid-prefill only
        self._serial_ctr = 0
        # EWMA of per-tick wall time, measured off each block dispatch —
        # used to cap the horizon so a block doesn't overshoot the next
        # timed arrival when a free slot is waiting for it
        self._tick_s = 0.0
        self._zero_counters()

    def _zero_counters(self) -> None:
        # occupancy / utilization / dispatch-accounting counters
        self.decode_steps = 0           # executed ticks with >=1 live row
        self.decode_dispatches = 0      # decode block programs launched
        self.prefill_chunks = 0         # chunk advances (rows, not launches)
        self.prefill_dispatches = 0     # batched prefill programs launched
        self.active_row_steps = 0
        self.dispatches = 0             # every jit'd program launch
        self.host_syncs = 0             # blocking device -> host transfers
        self.issued_ticks = 0           # K * active rows, per decode block
        self.parked_ticks = 0           # issued - emitted: mid-block-retire
                                        # waste (eos-aware-horizon target)

    # ---- intake -----------------------------------------------------------
    def submit(self, request: Request, now: float = 0.0) -> RequestState:
        """Typed submit-time validation: every constraint the trace can
        violate terminates as a structured rejection (``code`` +
        ``finish_reason``) at submit, never an assert mid-trace. Overload
        decisions (drain in progress, bounded queue, unattainable TTFT
        deadline) terminate as ``shed`` instead — the request was feasible,
        the engine chose to drop it."""
        reject = shed = None
        if len(request.prompt) > self.pool.capacity:
            reject = ("prompt_too_long",
                      f"rejected: prompt of {len(request.prompt)} tokens > "
                      f"slot capacity {self.pool.capacity}")
        elif self.needs_source:
            if (request.source is not None
                    and len(request.source) > self.src_max):
                reject = ("source_too_long",
                          f"rejected: source of {len(request.source)} rows "
                          f"> source-KV pool rows {self.src_max}")
            elif request.source is None and request.source_id is not None:
                # a shared id must be ingestable by whichever holder
                # arrives first — an id with no features would poison the
                # entry (src_len 0) for every later sharer, so it is a
                # contract violation, rejected here rather than silently
                # decoding sourceless
                reject = ("source_id_without_source",
                          "rejected: source_id "
                          f"{request.source_id!r} without source features "
                          "(a shared entry must be ingestable by its "
                          "first holder)")
        if reject is None:
            if self._draining:
                shed = ("drain", "shed: engine is draining")
            elif (request.ttft_deadline_s is not None
                  and self.sched.overload is not None):
                est = self._predict_ttft(request)
                if est is not None and est > request.ttft_deadline_s:
                    shed = ("ttft_unattainable",
                            f"shed: predicted TTFT {est:.4f}s > deadline "
                            f"{request.ttft_deadline_s:.4f}s")
        state = self.sched.submit(request, now, reject=reject, shed=shed)
        if state.status == QUEUED:
            # admission order is FIFO over submission order, so the serial
            # is a deterministic property of the trace
            self._serials[state.rid] = self._serial_ctr
            self._serial_ctr += 1
            if (request.ttft_deadline_s is not None
                    or request.deadline_s is not None):
                self._n_deadlined += 1
        self._sync_shed_serials()
        return state

    def _sync_shed_serials(self) -> None:
        """Reclaim sampler serials of requests shed while queued (the
        bounded queue's shed-oldest policy evicts inside the scheduler, so
        the engine reconciles against the shed list's new suffix)."""
        shed = self.sched.shed
        while self._shed_seen < len(shed):
            self._serials.pop(shed[self._shed_seen].rid, None)
            self._shed_seen += 1

    def _predict_ttft(self, request: Request) -> float | None:
        """EWMA-based TTFT estimate for an arriving request: queue wait
        (queued-ahead waves times the per-request slot-hold EWMA, plus one
        wave when no slot is free) plus its own chunked prefill (chunks
        times the per-chunk-dispatch EWMA). ``None`` until the engine has
        served enough traffic to have both EWMAs — the gate never sheds on
        a cold engine."""
        if self._chunk_s == 0.0 or self._svc_s == 0.0:
            return None
        waves = len(self.sched.queue) / self.pool.n_slots
        if self.pool.n_free == 0:
            waves += 1.0
        chunks = math.ceil(len(request.prompt) / self.chunk)
        return waves * self._svc_s + chunks * self._chunk_s

    # ---- overload / lifecycle control --------------------------------------
    def cancel(self, rid) -> None:
        """Client cancellation: applied at the next step boundary — a
        queued request sheds (``cancelled``), an in-flight one retires with
        its partial tokens (``finish_reason`` / ``code`` ``cancelled``) and
        its slot + source reference reclaimed. Unknown or already-finished
        rids are dropped silently (cancellation races completion)."""
        self._cancels.add(rid)

    def drain(self) -> None:
        """Graceful shutdown: stop admitting (later submits shed with code
        ``drain``), shed everything still queued at the next step boundary,
        and let in-flight requests finish naturally. ``run()`` then returns
        once the last in-flight request retires, flushing telemetry."""
        self._draining = True
        if self._sink is not None:
            self._sink("drain", t=time.perf_counter() - self._t0,
                       queued=len(self.sched.queue),
                       in_flight=len(self.sched.prefilling)
                       + len(self.sched.decoding))

    def _enforce_control(self, now: float) -> None:
        """Step-boundary control actions: drain sheds the queue,
        cancellations and expired deadlines shed queued requests / retire
        in-flight ones with slot + source reclaim. Only runs when one of
        the three triggers is live (``step`` guards the call), so the
        default path costs nothing."""
        if self._draining:
            for st in list(self.sched.queue):
                self.sched.shed_queued(st, "drain", now,
                                       detail="shed: engine draining")
        if self._cancels:
            live = {st.rid: st for st in list(self.sched.queue)
                    + list(self.sched.prefilling)
                    + list(self.sched.decoding.values())}
            for rid in list(self._cancels):
                st = live.get(rid)
                if st is not None:
                    if st.status == QUEUED:
                        self.sched.shed_queued(st, "cancelled", now,
                                               detail="shed: cancelled by "
                                                      "client")
                    else:
                        self._reclaim(st, "cancelled", now,
                                      detail="cancelled by client")
                self._cancels.discard(rid)
        if self._n_deadlined:
            for st in list(self.sched.queue):
                r = st.request
                missed = ((r.deadline_s is not None
                           and now - st.t_submit > r.deadline_s)
                          or (r.ttft_deadline_s is not None
                              and now - st.t_submit > r.ttft_deadline_s))
                if missed:
                    self.sched.shed_queued(
                        st, "deadline", now,
                        detail=f"shed: deadline expired after "
                               f"{now - st.t_submit:.4f}s in queue")
            for st in (list(self.sched.prefilling)
                       + list(self.sched.decoding.values())):
                r = st.request
                missed = ((r.deadline_s is not None
                           and now - st.t_submit > r.deadline_s)
                          or (st.t_first is None
                              and r.ttft_deadline_s is not None
                              and now - st.t_submit > r.ttft_deadline_s))
                if missed:
                    self._reclaim(st, "deadline", now,
                                  detail=f"deadline missed after "
                                         f"{now - st.t_submit:.4f}s")
        self._sync_shed_serials()

    def _reclaim(self, state: RequestState, code: str, now: float, *,
                 error: bool = False, detail: str | None = None,
                 device: bool = True) -> int:
        """Stop a slot-holding request before its natural end and reclaim
        everything it owns: the scheduler records the typed terminal state
        (RETIRED with partial tokens, or ERRORED when ``error``), the slot
        returns to the free list, its device rows reset, and its source-KV
        reference dropped (entry zeroed when this was the last holder) —
        the same reclaim order as normal retirement in ``_emit``.
        ``device=False`` skips the device dispatches (KeyboardInterrupt
        unwinding: the cache may hold a donated buffer mid-dispatch, so
        only host ledgers are cleaned)."""
        serial = self._serials.get(state.rid)
        was_prefilling = state.status == PREFILLING
        slot = self.sched.abort(state, code, now, error=error, detail=detail)
        if was_prefilling:
            self._serials.pop(state.rid, None)
        else:
            serial = int(self.serial[slot])
        if device:
            self.cache = self._release(self.cache, jnp.int32(slot))
            self.dispatches += 1
        if self.needs_source and state.rid in self._srcs:
            freed = self.src_pool.release(self._srcs.pop(state.rid),
                                          owner=state.rid)
            if freed is not None and device:
                self.cache = self._src_release(self.cache, jnp.int32(freed))
                self.dispatches += 1
        if self._sink is not None:
            self._sink("error_retire" if error else "abort", t=now,
                       rid=state.rid, slot=slot, serial=serial, code=code,
                       n_tokens=len(state.tokens))
            self._sink("release", t=now, rid=state.rid, slot=slot,
                       serial=serial)
        self.active[slot] = False
        self.tok[slot] = self.pad_id
        self.budget[slot] = 0
        self._note_service(state, now)
        return slot

    def _note_service(self, state: RequestState, now: float) -> None:
        # slot-hold EWMA feeding the predicted-TTFT gate; host float math
        # only, so it runs unconditionally
        if state.t_admit is None:
            return
        hold = max(0.0, now - state.t_admit)
        self._svc_s = (hold if self._svc_s == 0.0
                       else 0.5 * self._svc_s + 0.5 * hold)

    def _quarantine(self, slot: int, now: float) -> None:
        """A decode row reported the ``-2`` non-finite-logits sentinel:
        quarantine exactly that request — typed ERRORED terminal state,
        slot + source reclaimed — while every other stream proceeds
        untouched (their rows never read this slot's state)."""
        state = self.sched.decoding[slot]
        self._reclaim(state, "nonfinite_logits", now, error=True,
                      detail="errored: non-finite logits row (quarantined "
                             "by the on-device finite check)")

    def warmup(self) -> "ContinuousBatchingEngine":
        """Compile the chunk / finalize / decode / release programs with a
        throwaway request whose budget (2*decode_ticks, prioritized over
        prompt length when the pool is small) walks the adaptive horizon
        down through every power-of-two K <= decode_ticks — on a pool too
        small to ever reach the larger horizons, whatever residual K a real
        trace *can* reach still compiles on its first use. ``run`` drops
        finished-traffic stats at entry so reports cover real traffic only;
        the warmup request consumes exactly one sampler serial, so two
        warmed-up engines with the same seed still draw identical
        streams."""
        m_want = 2 * self.max_ticks     # walks K = max_ticks, ..., 2, 1
        p = max(1, min(self.chunk + 1, self.pool.capacity - m_want))
        m = max(2, min(m_want, self.pool.capacity - p))
        src = (np.zeros((self.src_max, self.model.cfg.d_model), np.float32)
               if self.needs_source else None)   # compiles ingest/assign too
        # the fault plan must not burn its faults on warmup traffic
        faults, self.faults = self.faults, None
        try:
            self.run([Request(prompt=np.zeros(p, np.int32), max_new_tokens=m,
                              rid="__warmup__", source=src)])
        finally:
            self.faults = faults
        return self

    # ---- decode program per tick horizon ----------------------------------
    def _decode_fn(self, k: int, poisoned: bool = False):
        """jit'd K-tick block. At most log2(max_ticks)+1 of these ever
        compile (the horizon is floored to a power of two). ``poisoned``
        compiles the fault-injection variant taking a [n_slots] bool mask
        whose rows get NaN logits each tick — a separate cache key, so
        fault-free runs never pay for the extra argument."""
        fn = self._decode_fns.get((k, poisoned))
        if fn is None:
            model, eos, temp = self.model, self.eos_id, self.temperature
            key = self._base_key

            if poisoned:
                def block(params, tok, cache, active, budget, serials,
                          emitted, poison):
                    toks, _, _, cache = model.decode_multi(
                        params, tok, cache, active, budget, serials,
                        emitted, k, eos_id=eos, temperature=temp,
                        rng_key=key, poison=poison)
                    return toks, cache
            else:
                def block(params, tok, cache, active, budget, serials,
                          emitted):
                    toks, _, _, cache = model.decode_multi(
                        params, tok, cache, active, budget, serials,
                        emitted, k, eos_id=eos, temperature=temp,
                        rng_key=key)
                    return toks, cache
            fn = jax.jit(block, donate_argnums=(2,))
            self._decode_fns[(k, poisoned)] = fn
        return fn

    def _tick_horizon(self, now: float | None = None,
                      deadline: float | None = None) -> int:
        """K = min(decode_ticks, min remaining budget among active rows),
        forced to 1 while prefill chunks are waiting (a mid-prefill slot
        must advance every tick and join the batch the tick its final chunk
        lands — TTFT is not sacrificed to throughput), floored to a power
        of two to bound the number of compiled programs.

        A non-empty admission queue does *not* force K=1: ``admit()`` ran
        at the top of this step, so queued requests mean every slot is
        busy, and the min-remaining-budget cap already ends the block at
        exactly the next scheduled (max-token) retirement — the freed slot
        backfills at the following step, never K ticks late.

        ``deadline``: engine-clock time of the next *timed arrival while a
        slot sits free* (run() passes it) — the horizon is additionally
        capped so the block ends by then (estimated via the per-tick EWMA),
        keeping an arriving request's TTFT flat in K instead of paying up
        to K-1 ticks of block drain before it can even submit. The one
        residual trade: an unpredictable mid-block EOS costs up to K-1
        parked ticks before its slot backfills."""
        if self.max_ticks == 1 or self.sched.prefilling:
            return 1
        rem = min(s.remaining for s in self.sched.decoding.values())
        k = max(1, min(self.max_ticks, rem))
        if (deadline is not None and now is not None and self._tick_s > 0):
            k = max(1, min(k, int((deadline - now) / self._tick_s)))
        if self._n_deadlined and now is not None and self._tick_s > 0:
            # an in-flight total deadline also caps the horizon: the block
            # should end near the deadline so enforcement (step-boundary)
            # doesn't overshoot by up to K-1 ticks of dead work
            for st in self.sched.decoding.values():
                d = st.request.deadline_s
                if d is not None:
                    left = st.t_submit + d - now
                    k = max(1, min(k, max(1, int(left / self._tick_s))))
        return 1 << (k.bit_length() - 1)

    # ---- one engine step --------------------------------------------------
    def step(self, now: float | None = None,
             deadline: float | None = None) -> bool:
        """Admit + advance every prefilling slot one chunk (one batched
        dispatch) + one K-tick decode block, each phase in its profiler
        span (``SPAN_NAMES``, in order). ``deadline``: next timed
        arrival while a slot is free (caps the horizon — see
        ``_tick_horizon``). Returns False when nothing was left to do."""
        now = (time.perf_counter() - self._t0) if now is None else now
        with span("serve.admit") as sp:
            sp.note(admitted=self._admit(now))

        if self.sched.prefilling:
            self._advance_prefills()

        if not self.active.any():
            return self.sched.pending()

        blk_idx = self.decode_dispatches
        with span("serve.decode", block=blk_idx) as dec:
            toks, k, live_slots, t_dispatch = self._dispatch_block(
                blk_idx, now, deadline)
            dec.note(k=k, rows=len(live_slots))
        with span("serve.sync", block=blk_idx) as sync:
            rows = np.asarray(toks)              # [K, n_slots]; the ONE sync
            self.host_syncs += 1
        with span("serve.retire", block=blk_idx) as sp:
            sp.note(emitted=self._replay_block(
                rows, k, live_slots, blk_idx, t_dispatch, dec.t0, sync.t1))
        return True

    def _admit(self, now: float) -> int:
        """Step-boundary control, admission, and source-KV ingest for the
        newly admitted (the ``serve.admit`` span). Returns how many were
        admitted."""
        if self._draining or self._cancels or self._n_deadlined:
            self._enforce_control(now)
        newly = self.sched.admit(now)
        if self.needs_source:
            # source ingest happens AT admission, before the request's
            # first prefill chunk — the chunk's cross reads need the
            # entry resident (whisper-style decoders cross-attend in
            # every layer from chunk 0)
            for st in newly:
                if (self.faults is not None
                        and self.faults.take_ingest(st.rid) is not None):
                    # injected ingest failure: quarantine before any device
                    # write — the slot returns to the free list this step
                    if self._sink is not None:
                        self._sink("fault", t=now, rid=st.rid,
                                   fault="ingest_fail")
                    self._reclaim(st, "source_ingest_failed", now,
                                  error=True,
                                  detail="errored: source-KV ingest failed")
                    continue
                self._acquire_source(st)
        return len(newly)

    def _dispatch_block(self, blk_idx: int, now: float,
                        deadline: float | None):
        """Choose the horizon and launch one ``decode_multi`` block (the
        ``serve.decode`` span). Returns (tokens on device, K, the live
        slots at dispatch, the dispatch's ``perf_counter`` time)."""
        k = self._tick_horizon(now, deadline)
        live_slots = np.flatnonzero(self.active)     # rows at dispatch time
        poison = None
        if self.faults is not None:
            d = self.faults.take("tick_delay", block=blk_idx)
            if d is not None:
                if self._sink is not None:
                    self._sink("fault", t=time.perf_counter() - self._t0,
                               block=blk_idx, fault="tick_delay",
                               delay_s=d.delay_s)
                time.sleep(d.delay_s)
            while True:
                try:
                    # fires BEFORE the jit call: the donated cache was
                    # never consumed, so re-dispatching is safe
                    self.faults.raise_if("dispatch_fail", block=blk_idx)
                    break
                except FaultInjected:
                    self.dispatch_retries += 1
                    if self._sink is not None:
                        self._sink("fault",
                                   t=time.perf_counter() - self._t0,
                                   block=blk_idx, fault="dispatch_fail",
                                   retry=self.dispatch_retries)
            hits = self.faults.take_poison(
                {st.rid: len(st.tokens)
                 for st in self.sched.decoding.values()}, blk_idx)
            if hits:
                mask = np.zeros((self.pool.n_slots,), bool)
                for slot, st in self.sched.decoding.items():
                    if st.rid in hits:
                        mask[slot] = True
                poison = jnp.asarray(mask)
                if self._sink is not None:
                    self._sink("fault", t=time.perf_counter() - self._t0,
                               block=blk_idx, fault="poison_nan",
                               rids=list(hits))
        t_dispatch = time.perf_counter()
        if poison is None:
            toks, self.cache = self._decode_fn(k)(
                self.params, jnp.asarray(self.tok), self.cache,
                jnp.asarray(self.active), jnp.asarray(self.budget),
                jnp.asarray(self.serial), jnp.asarray(self.emitted))
        else:
            toks, self.cache = self._decode_fn(k, poisoned=True)(
                self.params, jnp.asarray(self.tok), self.cache,
                jnp.asarray(self.active), jnp.asarray(self.budget),
                jnp.asarray(self.serial), jnp.asarray(self.emitted), poison)
        self.decode_dispatches += 1
        self.dispatches += 1
        return toks, k, live_slots, t_dispatch

    def _replay_block(self, rows: np.ndarray, k: int, live_slots, blk_idx: int,
                      t_dispatch: float, t_open: float,
                      t_synced: float) -> int:
        """Per-tick bookkeeping replayed from the synced ``[K, n_slots]``
        block: tokens, retirements and their slot releases, quarantines,
        the block's event and gauges, the auditor (the ``serve.retire``
        span). ``t_open``/``t_synced``: ``perf_counter`` times at which the
        ``serve.decode`` span opened and the ``serve.sync`` span closed.
        Returns the tokens emitted."""
        # the block's tokens all became available at this one sync; stamps
        # inside the block are attributed by even subdivision of its wall
        # span (itl_source: "subdivided" in report())
        now_blk = t_synced - self._t0
        blk_start = t_dispatch - self._t0
        per_tick = (now_blk - blk_start) / k
        self._tick_s = (per_tick if self._tick_s == 0.0
                        else 0.5 * self._tick_s + 0.5 * per_tick)
        emitted_blk = 0
        quarantined = []
        for t in range(k):
            live = rows[t] >= 0                  # -1 marks parked rows
            bad = rows[t] == -2                  # quarantine sentinel: the
            if not live.any() and not bad.any():  # row's logits went NaN/inf
                break                            # all rows retired mid-block
            stamp = blk_start + (t + 1) * per_tick   # == now_blk at t == k-1
            if live.any():
                self.decode_steps += 1
                self.active_row_steps += int(live.sum())
                emitted_blk += int(live.sum())
                for slot in np.flatnonzero(live):
                    state = self.sched.decoding[int(slot)]
                    self.pool.advance(int(slot))
                    self._emit(state, int(rows[t, slot]), stamp)
            for slot in np.flatnonzero(bad):
                quarantined.append(int(slot))
                self._quarantine(int(slot), stamp)
        issued = k * len(live_slots)
        self.issued_ticks += issued
        self.parked_ticks += issued - emitted_blk
        if self._sink is not None:
            extra = {"quarantined": quarantined} if quarantined else {}
            self._sink(
                "decode_block", t=now_blk, block=blk_idx, k=k,
                dur=round(t_synced - t_open, 6), emitted=emitted_blk,
                parked=issued - emitted_blk,
                slots=[int(s) for s in live_slots],
                serials=[int(self.serial[s]) for s in live_slots],
                tokens_per_slot=[int((rows[:k, s] >= 0).sum())
                                 for s in live_slots], **extra)
            self._sample_gauges(now_blk, blk_idx, k, issued - emitted_blk)
        if self.auditor is not None:
            self.auditor.maybe_check(self)
        return emitted_blk

    def _sample_gauges(self, t: float, block: int, k: int,
                       parked: int) -> None:
        """Engine gauges, sampled at each decode block's sync: occupancy /
        queue / free-slot state, live KV bytes (rows actually holding
        committed context, not the preallocated pool), the chosen tick
        horizon, and this block's parked-tick waste. Rendered as counter
        tracks in the Perfetto export."""
        g = dict(
            active_slots=int(self.active.sum()),
            free_slots=self.pool.n_free,
            queue_depth=len(self.sched.queue),
            prefilling=len(self.sched.prefilling),
            occupancy=round(self.pool.n_used / self.pool.n_slots, 3),
            tick_k=k,
            parked_ticks_block=parked,
            parked_ticks_total=self.parked_ticks,
            kv_bytes_live=self._kv_row_bytes * sum(
                min(self.pool.length(int(s)), self._kv_rows)
                for s in np.flatnonzero(self.active)),
        )
        if self.src_pool is not None:
            g["src_entries_used"] = self.src_pool.n_used
            g["src_refs"] = sum(self.src_pool.refcount(e)
                                for e in range(self.src_pool.n_entries))
        self._sink("gauges", t=t, block=block, **g)

    def _acquire_source(self, st: RequestState) -> None:
        """Resolve a newly admitted request's source-KV pool entry: bump an
        existing entry's refcount when its source id is already resident
        (no encoder work at all — the dedup win), else take a fresh entry
        and ingest the padded source once (one dispatch: encoder for
        audio, per-layer cross K/V projections for vlm). Either way the
        slot's ``src_index`` is pointed at the entry. A request without a
        source still takes an entry; its ``src_len`` stays 0, so every
        cross read masks to an exact zero."""
        req = st.request
        sid = (req.source_id if req.source_id is not None
               else ("__rid__", st.rid))
        entry, fresh = self.src_pool.acquire(sid, owner=st.rid)
        assert entry is not None, "source pool exhausted with a free slot"
        self._srcs[st.rid] = sid
        if fresh and req.source is not None:
            cfg = self.model.cfg
            padded = np.zeros((self.src_max, cfg.d_model), np.float32)
            padded[:len(req.source)] = req.source
            self.cache = self._ingest(self.params, jnp.asarray(padded),
                                      self.cache, jnp.int32(entry),
                                      jnp.int32(len(req.source)))
            self.dispatches += 1
        # fresh + no source: the entry's rows and src_len are already zero
        # (init / release_source), which IS the empty-source state
        self.cache = self._assign(self.cache, jnp.int32(st.slot),
                                  jnp.int32(entry))
        self.dispatches += 1

    def _prefill_chunk(self, rows) -> tuple[jax.Array, np.ndarray, list]:
        """One batched prefill dispatch. ``rows``: (slot, prompt, offset)
        per advancing slot, each prefilling ``prompt[offset:offset+chunk]``.
        Returns (logits [n_slots, V] — row i meaningful on row i's final
        chunk —, the offsets, and each row's real token count); the cache
        is updated in place."""
        n = self.pool.n_slots
        toks = np.full((n, self.chunk), self.pad_id, np.int32)
        slots = np.zeros((n,), np.int32)
        offs = np.zeros((n,), np.int32)
        lasts = np.zeros((n,), np.int32)
        valid = np.zeros((n,), bool)
        sizes = [0] * n
        for i, (slot, prompt, off) in enumerate(rows):
            part = prompt[off:off + self.chunk]
            toks[i, :part.size] = part
            slots[i], offs[i] = slot, off
            lasts[i] = min(self.chunk - 1, max(0, len(prompt) - 1 - off))
            valid[i] = True
            sizes[i] = int(part.size)
        logits, self.cache = self._prefill_batched(
            self.params, jnp.asarray(toks), self.cache, jnp.asarray(slots),
            jnp.asarray(offs), jnp.asarray(lasts), jnp.asarray(valid))
        return logits, offs, sizes

    def prefill_logits(self, prompt) -> np.ndarray:
        """Last-token logits [V] (f32) of ``prompt`` through the served
        chunked-prefill program — the same compiled dispatch ``run`` makes —
        on slot 0 of an idle engine, which is released afterwards. For
        checking served numerics against a reference between runs."""
        if self.pool.n_used or self.needs_source:
            raise RuntimeError("prefill_logits needs an idle engine and a "
                               "config without a source")
        prompt = np.asarray(prompt, np.int32)
        for off in range(0, len(prompt), self.chunk):
            logits, _, _ = self._prefill_chunk([(0, prompt, off)])
        self.cache = self._release(self.cache, jnp.int32(0))
        return np.asarray(logits[0])

    def _advance_prefills(self) -> None:
        """One batched dispatch advancing *all* mid-prefill slots one chunk
        (``prefill_chunks_batched``); finalized requests sample their first
        token from their chunk-logits row (a scalar int32 transfer, never
        the [V] logits)."""
        states = list(self.sched.prefilling)
        blk_idx = self.prefill_dispatches
        with span("serve.prefill", block=blk_idx, rows=len(states)) as sp:
            logits, offs, sizes = self._prefill_chunk(
                [(st.slot, st.request.prompt, st.prefilled)
                 for st in states])
            self.prefill_dispatches += 1
            self.dispatches += 1
            self.prefill_chunks += len(states)
        if self._sink is not None:
            # one slice per advanced slot, sharing the batched dispatch's
            # host-side span (the program itself retires asynchronously —
            # its device time is hidden inside the next blocking sync)
            dur = round(sp.t1 - sp.t0, 6)
            t_ev = sp.t1 - self._t0
            for i, st in enumerate(states):
                self._sink("prefill_chunk", t=t_ev, rid=st.rid,
                           slot=st.slot, serial=self._serials.get(st.rid),
                           block=blk_idx, offset=int(offs[i]),
                           n_tokens=sizes[i], dur=dur)
        for i, st in enumerate(states):
            prompt = st.request.prompt
            st.prefilled = min(st.prefilled + self.chunk, len(prompt))
            if st.prefilled < len(prompt):
                continue   # non-final chunk: logits row never leaves device
            serial = self._serials.pop(st.rid)
            with span("serve.first_token", slot=st.slot, serial=serial):
                self._first_token(st, serial, logits[i])

    def _first_token(self, st: RequestState, serial: int,
                     logits_row: jax.Array) -> None:
        """A prompt's final chunk landed: commit the slot, sample its first
        token on device, and emit it (the ``serve.first_token`` span)."""
        prompt = st.request.prompt
        self.cache = self._finalize(self.cache, jnp.int32(st.slot),
                                    len(prompt))
        self.dispatches += 1
        self.sched.start_decoding(st)
        self.serial[st.slot] = serial
        self.budget[st.slot] = st.request.max_new_tokens
        tok0 = int(self._prefill_pick(logits_row, jnp.int32(serial)))
        self.dispatches += 1
        self.host_syncs += 1
        t_tok0 = time.perf_counter() - self._t0
        # admit -> first-token wall per chunk (includes the decode
        # blocks interleaved between chunks — the realistic under-load
        # cost the predicted-TTFT gate needs); host float math only
        per_chunk = (max(0.0, t_tok0 - st.t_admit)
                     / max(1, math.ceil(len(prompt) / self.chunk)))
        self._chunk_s = (per_chunk if self._chunk_s == 0.0
                         else 0.5 * self._chunk_s + 0.5 * per_chunk)
        if self._sink is not None:
            self._sink("first_token", t=t_tok0, rid=st.rid,
                       slot=st.slot, serial=serial, token=tok0)
        self._emit(st, tok0, t_tok0)

    def _emit(self, state: RequestState, token: int, now: float) -> None:
        # ``now``: the token's attributed timestamp — exact for prefill
        # first tokens (stamped at their sync) and single-tick blocks,
        # evenly subdivided across a multi-tick block's wall span otherwise
        if state.token_times:
            self.hist_itl.add(max(0.0, now - state.token_times[-1]))
        state.tokens.append(token)
        state.token_times.append(now)
        if state.t_first is None:
            state.t_first = now
            self.hist_ttft.add(max(0.0, now - state.t_submit))
        done = (self.eos_id is not None and token == self.eos_id)
        if done or len(state.tokens) >= state.request.max_new_tokens:
            # mirrors decode_multi's on-device retirement exactly: the
            # device flipped this row's active bit at the same tick
            reason = "eos" if done else "max_tokens"
            if self._sink is not None:
                self._sink("eos" if done else "budget_retire", t=now,
                           rid=state.rid, slot=state.slot,
                           serial=int(self.serial[state.slot]),
                           n_tokens=len(state.tokens))
            slot = self.sched.retire(state, reason, now)
            self.cache = self._release(self.cache, jnp.int32(slot))
            self.dispatches += 1
            if self.needs_source:
                # drop the source reference; zero the entry only when this
                # was the last holder (other slots may still be decoding
                # against the same source id)
                freed = self.src_pool.release(self._srcs.pop(state.rid),
                                              owner=state.rid)
                if freed is not None:
                    self.cache = self._src_release(self.cache,
                                                   jnp.int32(freed))
                    self.dispatches += 1
            if self._sink is not None:
                self._sink("release", t=now, rid=state.rid, slot=slot,
                           serial=int(self.serial[slot]))
            self.active[slot] = False
            self.tok[slot] = self.pad_id
            self.budget[slot] = 0
            self._note_service(state, now)
        else:
            self.active[state.slot] = True
            self.tok[state.slot] = token
            self.emitted[state.slot] = len(state.tokens)

    # ---- drive a whole trace ----------------------------------------------
    def run(self, requests: list[Request] | None = None) -> dict:
        """Drive until every request retires. Each request is submitted once
        the wall clock passes its ``Request.arrival`` offset (0.0 on every
        request = a fully backlogged throughput run); when the engine is
        idle it sleeps until the next arrival, so TTFT measures from the
        request's actual submission.

        ``drain()`` (from a signal handler or another coroutine) makes the
        run finish early but cleanly: queued and not-yet-due requests shed
        with code ``drain``, in-flight ones finish naturally. A
        ``KeyboardInterrupt`` is the abrupt form: the in-flight block that
        already dispatched completes (the interrupt is caught at the loop
        boundary), queued + waiting requests shed, slot-holding requests
        retire with their partial tokens (code ``interrupt``) via
        host-only reclaim (the device cache may hold a donated buffer
        mid-dispatch), telemetry flushes, and the report is returned with
        ``interrupted: true`` instead of the exception unwinding through a
        half-consistent engine."""
        # per-run stats: an engine is reusable (warmup, successive traces),
        # so drop finished-traffic history before timing starts
        self.sched.reset_stats()
        self.pool.reset_stats()
        if self.src_pool is not None:
            self.src_pool.reset_stats()
        self._zero_counters()
        self.hist_ttft.reset()
        self.hist_itl.reset()
        self._shed_seen = 0
        self._draining = False
        self._interrupted = False
        self._cancels.clear()
        self.dispatch_retries = 0
        if self.auditor is not None:
            self.auditor.reset()
        if self.tel is not None:
            self.tel.reset()    # the stream covers this run's traffic only
        waiting = sorted(requests or [], key=lambda r: r.arrival)
        self._t0 = t0 = time.perf_counter()
        try:
            while True:
                now = time.perf_counter() - t0
                if self._draining:
                    # graceful shutdown: not-yet-due arrivals submit now and
                    # shed (typed terminal state, nothing silently dropped)
                    for r in waiting:
                        self.submit(r, now=now)
                    waiting = []
                while waiting and waiting[0].arrival <= now:
                    self.submit(waiting.pop(0), now=now)
                # a not-yet-due arrival with a free slot waiting for it caps
                # the tick horizon (an arrival into a busy pool queues
                # regardless, so it imposes no deadline)
                deadline = (waiting[0].arrival
                            if waiting and self.pool.n_free else None)
                worked = self.step(now, deadline)
                if not worked and not waiting:
                    break
                if not worked and waiting:
                    time.sleep(max(0.0, waiting[0].arrival
                                   - (time.perf_counter() - t0)))
        except KeyboardInterrupt:
            now = time.perf_counter() - t0
            self._interrupted = True
            self._draining = True
            for r in waiting:           # typed shed, not silent loss
                self.submit(r, now=now)
            waiting = []
            for st in list(self.sched.queue):
                self.sched.shed_queued(st, "interrupt", now,
                                       detail="shed: run interrupted")
            for st in (list(self.sched.prefilling)
                       + list(self.sched.decoding.values())):
                # host-only reclaim: the cache may be a donated buffer if
                # the interrupt landed mid-dispatch
                self._reclaim(st, "interrupt", now, device=False,
                              detail="interrupted with partial tokens")
            self._sync_shed_serials()
        wall = time.perf_counter() - t0
        self.sched.assert_conservation()
        if self.src_pool is not None:
            self.src_pool.assert_consistent()
            assert self.src_pool.n_used <= self.pool.n_used, \
                "source entries outlive their holders"
        if self.tel is not None:
            self.tel.flush()    # no lost JSONL tail on drain / interrupt
        return self.report(wall)

    def report(self, wall_s: float) -> dict:
        done = self.sched.retired
        gen = sum(len(s.tokens) for s in done)

        def _h(hist, q, scale=1.0):
            # streaming log-bucket percentile (one-bucket accuracy) — the
            # fixed-size replacement for the sorted-list nearest-rank _pct
            p = hist.percentile(q)
            return None if p is None else round(scale * p, 4)
        # per-slot KV memory accounting: the O(window) win of ring caches
        # (kv_rows_per_slot == ring_len << max_len) is a reported number,
        # not an inference from shapes; recurrent-state families carry no
        # KV rows and report 0. Pooled source KV (src_k / src_v) counts
        # too — with n_entries == n_slots the per-slot share is exact.
        # An int8 (+w4a8) cache counts its f32 dequant-scale planes too —
        # kv_bytes_per_slot reports the true footprint, so the ~4x win the
        # regression baseline pins is net of scale overhead.
        kv = [self.cache[k] for k in ("k", "v", "k_scale", "v_scale",
                                      "cross_k", "cross_v",
                                      "src_k", "src_v",
                                      "src_k_scale", "src_v_scale")
              if k in self.cache]
        kv_bytes = sum(int(a.size) * a.dtype.itemsize for a in kv)
        term = (self.sched.retired + self.sched.shed + self.sched.errored)
        agg = {
            "n_requests": self.sched.n_submitted,
            "n_retired": self.sched.n_retired,
            "n_rejected": len(self.sched.rejected),
            "n_shed": len(self.sched.shed),
            "n_errored": len(self.sched.errored),
            "n_deadline_missed": sum(s.code == "deadline" for s in term),
            "n_cancelled": sum(s.code == "cancelled" for s in term),
            "generated_tokens": gen,
            "wall_s": round(wall_s, 3),
            "tokens_per_s": round(gen / wall_s, 1) if wall_s else None,
            "decode_ticks": self.max_ticks,
            "decode_steps": self.decode_steps,
            "decode_dispatches": self.decode_dispatches,
            "prefill_chunks": self.prefill_chunks,
            "prefill_dispatches": self.prefill_dispatches,
            "dispatches": self.dispatches,
            "host_syncs": self.host_syncs,
            "dispatches_per_token": (round(self.dispatches / gen, 4)
                                     if gen else None),
            "issued_ticks": self.issued_ticks,
            "parked_ticks": self.parked_ticks,
            "mean_occupancy": round(
                self.active_row_steps
                / (self.decode_steps * self.pool.n_slots), 3)
                if self.decode_steps else 0.0,
            "kv_bytes_per_slot": kv_bytes // self.pool.n_slots,
            "kv_rows_per_slot": (int(self.cache["k"].shape[2])
                                 if "k" in self.cache else 0),
            "max_len": self.pool.max_len,
            "ttft_p50_s": _h(self.hist_ttft, 0.50),
            "ttft_p95_s": _h(self.hist_ttft, 0.95),
            "itl_p50_ms": _h(self.hist_itl, 0.50, scale=1e3),
            "itl_p95_ms": _h(self.hist_itl, 0.95, scale=1e3),
            "itl_source": ("subdivided" if self.max_ticks > 1 else "exact"),
            "itl_effective_ms": (round(1e3 * wall_s / gen, 4)
                                 if gen else None),
        }
        if self.tel is not None:
            agg["telemetry_events"] = len(self.tel.events)
        if self.sched.n_degraded:
            agg["n_degraded"] = self.sched.n_degraded
        if self.faults is not None:
            agg["faults_fired"] = self.faults.n_fired
            agg["faults_pending"] = self.faults.n_pending
            agg["dispatch_retries"] = self.dispatch_retries
        if self.auditor is not None:
            agg["audit_checks"] = self.auditor.n_checks
        if self._draining:
            agg["drained"] = True
        if self._interrupted:
            agg["interrupted"] = True
        if self.src_pool is not None:
            # source-KV pool accounting: ingests ran the encoder / cross
            # projections; shares were served by refcount alone (the dedup
            # win — N requests on one image pay one ingest)
            agg["source_ingests"] = self.src_pool.total_ingests
            agg["source_shares"] = self.src_pool.total_shares
            agg["src_rows_per_entry"] = self.src_pool.src_max
        if self.max_ticks > 1:
            agg["itl_note"] = (
                "decode_ticks > 1: the host syncs once per K-tick block, so "
                "per-token timestamps inside a block are attributed by even "
                "subdivision of the block's wall span (itl_source: "
                "subdivided) — itl percentiles are per-token estimates, no "
                "longer K-quantized; itl_effective_ms = wall_s / "
                "generated_tokens remains the exact denominator")
        return {
            "requests": [{
                "rid": s.rid, "prompt_len": int(len(s.request.prompt)),
                "n_tokens": len(s.tokens), "tokens": list(s.tokens),
                "ttft_s": None if s.ttft is None else round(s.ttft, 4),
                "finish_reason": s.finish_reason,
                "status": s.status, "code": s.code,
            } for s in (done + self.sched.errored + self.sched.rejected
                        + self.sched.shed)],
            "aggregate": agg,
        }
