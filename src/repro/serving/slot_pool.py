"""KV slot pool + source-KV pool: the host-side ledgers of continuous
batching (see ``docs/serving.md`` for the full lifecycle diagram).

Continuous batching keeps the jit'd decode step at a static ``[n_slots]``
batch shape while request membership changes every step. :class:`KVSlotPool`
is the host-side ledger over the model's preallocated decode cache
(``model.init_cache(n_slots, max_len)``): slot ``s`` owns rows
``cache[k|v][:, s, :]`` plus its entries of ``cache['len']`` and the RoPE
angle state.

Layout contract with :meth:`TransformerLM.decode_step`'s ragged form:

* the **final cache row** (index ``max_len - 1``) is reserved as the parking
  position for the masked KV writes of inactive slots, so a request is only
  admissible if ``prompt_len + max_new_tokens <= capacity`` where
  ``capacity = max_len - 1``. Ring KV caches (``kv_ring`` SWA configs) have
  no parkable dead row — every ring slot is, or wraps into, a live window
  position — so their inactive slots park via a per-slot **write mask**
  (the row rewrites its old value in place; ``TransformerLM._write_token``
  ``active=``). The tail reservation still prices admission for rings:
  ``capacity`` bounds a request's *position* budget (``cache['len']`` /
  RoPE state run over absolute positions), which is ``max_len``-scaled even
  when the live KV working set is only ``ring_len`` rows;
* release resets the slot's ledger length (and the device ``len`` entry via
  :meth:`TransformerLM.release_slot`), so nothing in a freed slot's KV rows
  is ever attended again — the next occupant's chunked prefill overwrites
  the contents in place (reset-on-release). Recurrent-state families
  (ssm / hybrid) additionally zero the slot's per-row state (RWKV
  x_prev/wkv, Mamba conv/ssm) on release: unlike KV rows it feeds forward
  multiplicatively, so the next occupant must start from the empty-context
  state rather than merely ignoring stale rows.

:class:`SourceKVPool` is the second ledger, for **cross-attention stacks**
(vlm / audio): the encoder-side K/V a request's decoder cross-attends to.
Unlike self-attention KV it is written exactly once (at admission, via
``TransformerLM.ingest_source``) and *read-only* for the request's whole
lifetime, so it pools by **source id** with reference counting — N requests
decoding against the same image / audio clip share one device entry (the
encoder runs once, not N times), and ``cache['src_index']`` maps each slot
to its entry. The entry's device rows are zeroed only when its refcount
drops to zero (``TransformerLM.release_source``), so a backfilled request
can never read its predecessor's encoder state: the predecessor's entry is
either still alive (held by another sharing request, and the new occupant's
``src_index`` points elsewhere) or zeroed.
"""
from __future__ import annotations

from typing import Any, Hashable

RESERVED_TAIL = 1   # parking row for masked decode writes of inactive slots


class SlotPoolError(RuntimeError):
    """Misuse of the pool (double release, unknown slot, ...)."""


class KVSlotPool:
    def __init__(self, n_slots: int, max_len: int):
        if n_slots < 1:
            raise SlotPoolError(f"n_slots must be >= 1, got {n_slots}")
        if max_len <= RESERVED_TAIL:
            raise SlotPoolError(f"max_len must exceed {RESERVED_TAIL}")
        self.n_slots = n_slots
        self.max_len = max_len
        self.capacity = max_len - RESERVED_TAIL
        self._free = list(range(n_slots - 1, -1, -1))   # pop() -> slot 0 first
        self._owner: dict[int, Hashable] = {}
        self._length = [0] * n_slots
        self.total_allocs = 0
        self.total_releases = 0

    # ---- queries ----------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_slots - len(self._free)

    def fits(self, tokens: int) -> bool:
        """Can a request needing ``tokens`` cache rows ever be admitted?"""
        return 0 < tokens <= self.capacity

    def owner(self, slot: int) -> Hashable:
        return self._owner.get(slot)

    def length(self, slot: int) -> int:
        return self._length[slot]

    def used_slots(self) -> dict[int, Hashable]:
        """Snapshot of ``slot -> owner`` for every allocated slot (the
        auditor cross-checks this against the scheduler's view)."""
        return dict(self._owner)

    def occupancy(self) -> float:
        return self.n_used / self.n_slots

    # ---- alloc / release --------------------------------------------------
    def alloc(self, owner: Hashable) -> int | None:
        """Take a slot off the free list for ``owner``; None when exhausted."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._owner[slot] = owner
        self._length[slot] = 0
        self.total_allocs += 1
        return slot

    def release(self, slot: int) -> Hashable:
        """Return a slot to the free list (reset-on-release). The caller is
        responsible for the matching device-side reset
        (:meth:`TransformerLM.release_slot`)."""
        if slot not in self._owner:
            raise SlotPoolError(f"release of unowned slot {slot}")
        owner = self._owner.pop(slot)
        self._length[slot] = 0
        self._free.append(slot)
        self.total_releases += 1
        return owner

    def set_length(self, slot: int, length: int) -> None:
        if slot not in self._owner:
            raise SlotPoolError(f"set_length on unowned slot {slot}")
        if not 0 <= length <= self.capacity:
            raise SlotPoolError(f"length {length} outside [0, {self.capacity}]")
        self._length[slot] = length

    def advance(self, slot: int) -> int:
        """One decode step appended one KV row for this slot."""
        self.set_length(slot, self._length[slot] + 1)
        return self._length[slot]

    def reset_stats(self) -> None:
        """Zero the lifetime counters without touching allocation state
        (keeps ``total_allocs - total_releases == slots in use``)."""
        self.total_allocs = len(self._owner)
        self.total_releases = 0

    # ---- invariants -------------------------------------------------------
    def assert_consistent(self) -> None:
        assert len(self._free) + len(self._owner) == self.n_slots, \
            (self._free, self._owner)
        assert len(set(self._free)) == len(self._free), "free-list duplicates"
        assert not (set(self._free) & set(self._owner)), "slot both free+owned"
        assert self.total_allocs - self.total_releases == len(self._owner)
        for slot in self._free:
            assert self._length[slot] == 0, f"freed slot {slot} keeps length"


class SourceKVPool:
    """Refcounted pool of encoder-side (source) K/V entries, keyed by
    source id.

    Entry ``e`` owns the device rows ``cache['src_k'|'src_v'][:, e]`` and
    ``cache['src_len'][e]``. ``acquire(source_id)`` either bumps an existing
    entry's refcount (the source is already resident — N requests share one
    encoder ingest) or takes a fresh entry off the free list; ``release``
    drops a reference and hands the entry back for zeroing
    (``TransformerLM.release_source``) only when the last holder retires.

    Capacity note: with ``n_entries == n_slots`` (the continuous engine's
    default) acquisition can never fail while a slot is free — each live
    request holds at most one reference, so entries in use <= slots in use,
    and sharing only loosens that bound. A smaller pool would need an
    admission gate; a larger one is pure dedup headroom.

    ``on_event``: optional telemetry sink (``sink(kind, **data)``) the
    ledger calls at its three state changes — ``source_ingest`` (fresh
    entry acquired; the caller will run the encoder), ``source_share``
    (acquisition served by refcount on a resident entry) and
    ``source_release`` (last holder retired; the entry goes back for
    zeroing) — each carrying the source id, entry index, refcount, and the
    acquiring/releasing ``owner`` (the request id, when the caller passes
    one). This makes "which requests shared an encoder entry" a property
    of the trace itself rather than something inferred from the engine's
    aggregate ``source_ingests`` / ``source_shares`` counters.
    """

    def __init__(self, n_entries: int, src_max: int, on_event=None):
        if n_entries < 1:
            raise SlotPoolError(f"n_entries must be >= 1, got {n_entries}")
        if src_max < 1:
            raise SlotPoolError(f"src_max must be >= 1, got {src_max}")
        self.n_entries = n_entries
        self.src_max = src_max              # rows per entry (pad-to length)
        self._free = list(range(n_entries - 1, -1, -1))   # pop() -> entry 0
        self._entry: dict[Hashable, int] = {}             # source id -> entry
        self._refs: dict[int, int] = {}                   # entry -> refcount
        self._sid: dict[int, Hashable] = {}               # entry -> source id
        self._sink = on_event               # telemetry sink; None -> silent
        self.total_ingests = 0              # fresh entries (encoder ran)
        self.total_shares = 0               # acquisitions served by sharing

    # ---- queries ----------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_entries - len(self._free)

    def fits(self, source_rows: int) -> bool:
        """Can a source needing ``source_rows`` K/V rows ever be ingested?
        (Zero rows — a request with no source — always fits: it still takes
        an entry, whose ``src_len`` stays 0 so every read masks to zero.)"""
        return 0 <= source_rows <= self.src_max

    def entry_of(self, source_id: Hashable) -> int | None:
        return self._entry.get(source_id)

    def refcount(self, entry: int) -> int:
        return self._refs.get(entry, 0)

    def total_refs(self) -> int:
        """Live references across all entries — must equal the number of
        requests currently holding a source (refcount conservation; the
        auditor checks it against the engine's rid -> source-id ledger)."""
        return sum(self._refs.values())

    # ---- acquire / release ------------------------------------------------
    def acquire(self, source_id: Hashable,
                owner: Hashable = None) -> tuple[int | None, bool]:
        """Returns ``(entry, fresh)``: ``fresh=True`` means the caller must
        ingest the source's K/V into the entry's device rows; ``fresh=False``
        means the source is already resident and this request shares it.
        ``(None, False)`` when the pool is exhausted. ``owner`` (typically
        the request id) rides along on the ledger's telemetry events."""
        entry = self._entry.get(source_id)
        if entry is not None:
            self._refs[entry] += 1
            self.total_shares += 1
            if self._sink is not None:
                self._sink("source_share", rid=owner, entry=entry,
                           source_id=source_id, refcount=self._refs[entry])
            return entry, False
        if not self._free:
            return None, False
        entry = self._free.pop()
        self._entry[source_id] = entry
        self._refs[entry] = 1
        self._sid[entry] = source_id
        self.total_ingests += 1
        if self._sink is not None:
            self._sink("source_ingest", rid=owner, entry=entry,
                       source_id=source_id, refcount=1)
        return entry, True

    def release(self, source_id: Hashable,
                owner: Hashable = None) -> int | None:
        """Drop one reference. Returns the freed entry index when the last
        reference went away — the caller must then zero the entry's device
        rows (``TransformerLM.release_source``) — else None."""
        entry = self._entry.get(source_id)
        if entry is None:
            raise SlotPoolError(f"release of unknown source id {source_id!r}")
        self._refs[entry] -= 1
        if self._refs[entry] > 0:
            return None
        del self._refs[entry]
        del self._entry[source_id]
        del self._sid[entry]
        self._free.append(entry)
        if self._sink is not None:
            # zeroing event: the caller is about to reset the device rows
            self._sink("source_release", rid=owner, entry=entry,
                       source_id=source_id, refcount=0)
        return entry

    def reset_stats(self) -> None:
        self.total_ingests = len(self._entry)
        self.total_shares = 0

    # ---- invariants -------------------------------------------------------
    def assert_consistent(self) -> None:
        assert len(self._free) + len(self._entry) == self.n_entries, \
            (self._free, self._entry)
        assert len(set(self._free)) == len(self._free), "free-list duplicates"
        assert set(self._entry.values()) == set(self._refs), "ledger skew"
        assert not (set(self._free) & set(self._refs)), "entry both free+held"
        assert all(r > 0 for r in self._refs.values()), "zero-ref entry held"
