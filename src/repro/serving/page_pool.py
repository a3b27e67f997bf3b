"""Paged KV-cache manager: a global pool of fixed-size FP8 pages.

Layout (per attention layer, stacked over blocks like the dense cache):

  k_pages / v_pages : [num_pages, page_size, KV, hd]   uint8 FP8 codes
                      (or the model's param dtype for unquantized caches)
  k_scale / v_scale : [num_pages]                      f32 per-page scales

``PagePool`` is the host-side allocator: it owns the free list, per-page
**refcounts**, the per-slot block tables (page ids in logical order) and
the **prefix-cache index** (token-chunk hash -> page id, with LRU eviction
of unreferenced cached pages).  Page 0 is reserved as the null page —
unowned block-table entries point at it so the attention kernel's gather
always hits a valid index, and masked write lanes are redirected into it
(see :func:`write_token_page`).  All layers share one allocation (the same
block table indexes every layer's page arrays), exactly the vLLM layout.

Ownership model (the prefix-cache PR changed this from exclusive to
shared):

  * every non-null page is in exactly one of five states — on the **free
    list**, **referenced** by one or more slots (``ref[pid]`` block-table
    references), parked in the **prefix-cache LRU** (registered content,
    ``ref == 0``, evictable), **pinned** by a preemption spill record
    (see :meth:`spill_slot`), or transiently **seized** by the chaos
    harness (:meth:`seize`, a simulated external memory squeeze);
  * a page is only ever *written* by a slot that owns it exclusively
    (``ref == 1`` and not registered).  Full prompt pages get registered
    in the prefix index and may then be mapped read-only into other slots
    (``ref > 1``); writes into shared pages go through :meth:`cow_page`.

``assert_invariants`` checks the whole partition and is exercised by the
pool tests.

Per-page scales are **powers of two** chosen from the page's first write
(absmax mapped onto the format's max_normal).  A power-of-two scale means
applying it to FP8 codes is an exponent-field add — exact in the paper's
LNS view — so a page computed once for a shared prompt prefix is
bit-for-bit valid for every request that reuses it, which is what makes
prefix caching sound at the code level.  Every f32 -> code KV write uses
the paper's **stochastic-rounding carry-ins**
(``core.carry_ins.stochastic_carry_in``: a uniform bit selects between the
Table-2 RD and RU expressions), so rounding bias cannot accumulate over
thousands of decode steps; the engine keys those writes by the *write
position*, not the engine step, so the codes stay a pure function of page
content (``launch.serve.Engine``).

Device-side helpers here are pure jnp and jit/Pallas-safe; the allocator is
plain numpy/python (it runs on the host between decode steps).
"""
from __future__ import annotations

import os
from collections import Counter, OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.carry_ins import supports_stochastic
from ..core.formats import FORMATS
from ..core.lns import lns_op
from ..core.quant import QTensor, encode
from ..kernels.common import code_to_f32

__all__ = [
    "PagePool",
    "invariant_checks_enabled",
    "page_qtensor",
    "pow2_page_scale",
    "encode_kv",
    "rescale_codes",
    "write_token_page",
    "write_prefill_pages",
]


def invariant_checks_enabled() -> bool:
    """True when ``REPRO_CHECK_INVARIANTS=1`` is set in the environment:
    both schedulers then run :meth:`PagePool.assert_invariants` after every
    step, so any pool-state corruption (a bug, or an injected chaos fault)
    is caught at the step that introduced it rather than steps later as a
    wrong token.  Wired on in the CI serve-smoke and chaos-smoke jobs."""
    return os.environ.get("REPRO_CHECK_INVARIANTS") == "1"


# --------------------------------------------------------------------------- #
# Host-side allocator
# --------------------------------------------------------------------------- #
class PagePool:
    """Free-list page allocator + refcounts + block tables + prefix index.

    The pool size is independent of the slot count — cache memory is
    ``num_pages * page_size`` tokens, however many slots share it.
    Admission control is the caller's job via :meth:`can_alloc`;
    ``free_pages`` counts pages allocatable *right now*, i.e. the free
    list plus the evictable prefix-cache LRU.
    """

    def __init__(self, num_pages: int, page_size: int, slots: int,
                 max_pages_per_slot: int):
        assert num_pages >= 2, "need at least the null page + one real page"
        self.num_pages = num_pages
        self.page_size = page_size
        self.slots = slots
        self.max_pages_per_slot = max_pages_per_slot
        # page 0 is the reserved null page; hand out high ids first so tests
        # catch any code path that assumes page ids are contiguous from 1.
        self._free: List[int] = list(range(1, num_pages))
        self.ref = np.zeros((num_pages,), np.int32)  # block-table references
        self.block_tables = np.zeros((slots, max_pages_per_slot), np.int32)
        self.pages_of: List[List[int]] = [[] for _ in range(slots)]
        # bumped on every block-table mutation; the engine compares it
        # against the version of its cached device copy so an unchanged
        # table costs zero host->device transfers (``host_transfers_total``)
        self.version = 0
        # prefix cache: chunk hash -> page id, LRU over unreferenced entries
        self._index: Dict[str, int] = {}
        self._page_key: Dict[int, str] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._pinned: Dict[int, int] = {}  # page id -> spill-record pins
        self._seized: set = set()  # chaos-harness transient seizures
        # watermark / churn accounting (read by the scheduler and benches)
        self.peak_used_pages = 0
        self.used_page_steps = 0  # sum over observe_step() of used_pages
        self.observed_steps = 0
        self.spills = 0
        self.restores = 0
        # prefix-cache accounting
        self.prefix_lookups = 0  # full-page chunks looked up at admission
        self.prefix_hits = 0  # ... of which were index hits
        self.evictions = 0
        self.cow_copies = 0

    # ------------------------------------------------------------------ #
    @property
    def free_pages(self) -> int:
        """Pages allocatable right now (free list + evictable LRU)."""
        return len(self._free) + len(self._lru)

    @property
    def used_pages(self) -> int:
        """Pages referenced by a slot or pinned by a spill record."""
        return (self.num_pages - 1) - self.free_pages

    @property
    def cached_pages(self) -> int:
        """Pages registered in the prefix index (referenced or parked)."""
        return len(self._index)

    def observe_step(self) -> None:
        """Record one scheduler step for the occupancy watermark stats."""
        self.used_page_steps += self.used_pages
        self.observed_steps += 1

    def mean_utilization(self) -> float:
        """Mean fraction of (non-null) pages in use over observed steps."""
        if not self.observed_steps or self.num_pages <= 1:
            return 0.0
        return self.used_page_steps / (self.observed_steps * (self.num_pages - 1))

    def prefix_stats(self) -> Dict[str, float]:
        return dict(
            lookups=self.prefix_lookups, hits=self.prefix_hits,
            hit_rate=self.prefix_hits / max(self.prefix_lookups, 1),
            cached_pages=self.cached_pages, evictions=self.evictions,
            cow_copies=self.cow_copies,
        )

    def publish_telemetry(self, tel) -> None:
        """Publish pool occupancy gauges and mirror the cumulative event
        counters into a :class:`~repro.serving.telemetry.Telemetry`
        registry (the scheduler calls this once per step)."""
        usable = max(self.num_pages - 1, 1)
        tel.gauge("pool_pages").set(self.num_pages - 1)
        tel.gauge("pool_free_pages").set(len(self._free))
        tel.gauge("pool_used_pages").set(self.used_pages)
        tel.gauge("pool_cached_pages").set(len(self._lru))
        tel.gauge("pool_seized_pages").set(len(self._seized))
        tel.gauge("pool_utilization").set(self.used_pages / usable)
        # counters live on the pool (they already snapshot/restore through
        # state_dict); the telemetry series mirrors their absolute values
        for name, v in (("pool_prefix_lookups_total", self.prefix_lookups),
                        ("pool_prefix_hits_total", self.prefix_hits),
                        ("pool_evictions_total", self.evictions),
                        ("pool_cow_copies_total", self.cow_copies),
                        ("pool_spills_total", self.spills),
                        ("pool_restores_total", self.restores)):
            tel.counter(name).value = float(v)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def can_alloc(self, n: int) -> bool:
        return n <= self.free_pages

    # ------------------------------------------------------------------ #
    def _unregister(self, pid: int) -> None:
        key = self._page_key.pop(pid)
        del self._index[key]

    def _take_free(self, n: int) -> List[int]:
        """Pop ``n`` page ids, evicting LRU prefix-cache entries on demand.

        Eviction only ever touches the LRU — pages with ``ref > 0`` or a
        spill-record pin are structurally not in it, so a referenced cached
        page can never be evicted out from under its readers."""
        if n > self.free_pages:
            raise RuntimeError(
                f"page pool exhausted: want {n}, have {self.free_pages}"
            )
        while len(self._free) < n:
            pid, _ = self._lru.popitem(last=False)  # least recently parked
            self._unregister(pid)
            self.evictions += 1
            self._free.append(pid)
        return [self._free.pop() for _ in range(n)]

    def alloc(self, slot: int, n: int = 1) -> List[int]:
        """Allocate ``n`` exclusive pages to ``slot`` (appended in logical
        order); evicts unreferenced cached pages if the free list is dry."""
        owned = self.pages_of[slot]
        if len(owned) + n > self.max_pages_per_slot:
            raise RuntimeError(
                f"slot {slot} exceeds max_pages_per_slot="
                f"{self.max_pages_per_slot}"
            )
        ids = self._take_free(n)
        for pid in ids:
            self.ref[pid] = 1
        start = len(owned)
        owned.extend(ids)
        self.block_tables[slot, start:start + len(ids)] = ids
        self.version += 1
        self.peak_used_pages = max(self.peak_used_pages, self.used_pages)
        return ids

    def share(self, slot: int, ids: Sequence[int]) -> None:
        """Map cached pages read-only into ``slot`` (appended in logical
        order), bumping their refcounts and reviving any parked in the
        LRU.  The caller must never write into a shared page — grow an
        exclusive copy with :meth:`cow_page` instead."""
        owned = self.pages_of[slot]
        if len(owned) + len(ids) > self.max_pages_per_slot:
            raise RuntimeError(
                f"slot {slot} exceeds max_pages_per_slot="
                f"{self.max_pages_per_slot}"
            )
        for pid in ids:
            if self.ref[pid] == 0 and self._pinned.get(pid, 0) == 0:
                del self._lru[pid]  # revive from the evictable set
            self.ref[pid] += 1
        start = len(owned)
        owned.extend(ids)
        self.block_tables[slot, start:start + len(ids)] = ids
        self.version += 1
        self.peak_used_pages = max(self.peak_used_pages, self.used_pages)

    def _release_page(self, pid: int) -> None:
        self.ref[pid] -= 1
        assert self.ref[pid] >= 0, f"refcount underflow on page {pid}"
        if self.ref[pid] == 0 and self._pinned.get(pid, 0) == 0:
            if pid in self._page_key:  # cached: park, newest at the back
                self._lru[pid] = None
            else:
                self._free.append(pid)

    def free_slot(self, slot: int) -> None:
        """Drop ``slot``'s reference on every page it maps.  Exclusive
        uncached pages return to the free list; registered pages whose
        last reference this was park in the LRU (evictable, still
        servable as prefix hits)."""
        for pid in self.pages_of[slot]:
            self._release_page(pid)
        self.pages_of[slot] = []
        self.block_tables[slot] = 0
        self.version += 1

    def cow_page(self, slot: int, logical: int) -> Tuple[int, int]:
        """Copy-on-write: replace the shared page at logical index
        ``logical`` of ``slot`` with a fresh exclusive page.  Returns
        ``(old_id, new_id)``; the caller copies the page *contents*
        old -> new on device before writing into it
        (``Engine._copy_page``)."""
        old = self.pages_of[slot][logical]
        new = self._take_free(1)[0]
        self.ref[new] = 1
        self.pages_of[slot][logical] = new
        self.block_tables[slot, logical] = new
        self.version += 1
        self._release_page(old)
        self.cow_copies += 1
        self.peak_used_pages = max(self.peak_used_pages, self.used_pages)
        return old, new

    # ------------------------------------------------------------------ #
    # Prefix index
    # ------------------------------------------------------------------ #
    def match_prefix(self, keys: Sequence[str], *,
                     peek: bool = False) -> List[int]:
        """Longest cached prefix of ``keys`` (chained full-page hashes):
        page ids in logical order, stopping at the first index miss.
        ``peek`` skips the hit/lookup accounting (planning passes);
        otherwise ``prefix_lookups`` counts only the probes actually
        performed (hits plus the one terminating miss), so
        ``prefix_stats()['hit_rate']`` is a true probe hit rate."""
        ids: List[int] = []
        for k in keys:
            pid = self._index.get(k)
            if pid is None:
                break
            ids.append(pid)
        if not peek:
            self.prefix_lookups += len(ids) + (1 if len(ids) < len(keys) else 0)
            self.prefix_hits += len(ids)
        return ids

    def register_prefix(self, key: str, pid: int) -> bool:
        """Publish ``pid`` (a fully written prompt page) under ``key``.
        First writer wins: an already-registered key, or a page already
        serving as some other key's entry, is left alone."""
        if key in self._index or pid in self._page_key:
            return False
        self._index[key] = pid
        self._page_key[pid] = key
        return True

    # ------------------------------------------------------------------ #
    # Preemption
    # ------------------------------------------------------------------ #
    def spill_plan(self, slot: int) -> Tuple[List[int], List[Tuple[int, int]]]:
        """What :meth:`spill_slot` will do: ``(spilled, pinned)`` where
        ``spilled`` is the exclusive page ids (logical order) whose
        contents the caller must copy out, and ``pinned`` is
        ``(logical_idx, page_id)`` pairs of registered prefix pages that
        stay resident in the pool (never copied, never freed)."""
        spilled, pinned = [], []
        for i, pid in enumerate(self.pages_of[slot]):
            if pid in self._page_key:
                pinned.append((i, pid))
            else:
                spilled.append(pid)
        return spilled, pinned

    def spill_slot(self, slot: int) -> Tuple[List[int], List[Tuple[int, int]]]:
        """Preemption: release ``slot``'s pages, returning
        ``(spilled, pinned)`` as in :meth:`spill_plan`.

        Exclusive pages are freed after the caller copied their contents
        out (``Engine.preempt_slot``); registered prefix pages are NOT
        copied or freed — they take a pin that keeps them resident (and
        un-evictable) until :meth:`restore_slot` re-references them, so a
        shared system prompt survives its readers being preempted.

        The freed ids are prepended to the free list — :meth:`_take_free`
        pops from the END — so an immediate re-allocation by another slot
        prefers other pages; a restore-after-spill round trip through the
        same physical pages would mask block-table bugs in tests."""
        spilled, pinned = self.spill_plan(slot)
        for _, pid in pinned:
            self._pinned[pid] = self._pinned.get(pid, 0) + 1
            self.ref[pid] -= 1  # the slot's reference becomes the pin
        for pid in spilled:
            self.ref[pid] -= 1
            assert self.ref[pid] == 0, f"spilled page {pid} still shared"
        self.pages_of[slot] = []
        self.block_tables[slot] = 0
        self.version += 1
        spilled_set = set(spilled)  # hoisted: O(free + spilled), built once
        self._free = spilled + [i for i in self._free if i not in spilled_set]
        self.spills += 1
        return spilled, pinned

    def restore_slot(self, slot: int, n: int,
                     pinned: Sequence[Tuple[int, int]] = ()) -> List[int]:
        """Re-admit a preempted request into ``slot``: allocate ``n``
        fresh pages for the spilled exclusive contents (ids may differ
        from the spilled ones — the caller scatters the saved bytes back)
        and re-reference the pinned prefix pages at their recorded
        logical indices.  Returns the fresh ids in the logical order of
        the exclusive positions."""
        assert not self.pages_of[slot], "restore target slot must be empty"
        total = n + len(pinned)
        if total > self.max_pages_per_slot:
            raise RuntimeError(
                f"slot {slot} exceeds max_pages_per_slot="
                f"{self.max_pages_per_slot}"
            )
        fresh = self._take_free(n)
        for pid in fresh:
            self.ref[pid] = 1
        table: List[Optional[int]] = [None] * total
        for i, pid in pinned:
            table[i] = pid
            self.ref[pid] += 1  # pin ownership returns to the slot
            self._pinned[pid] -= 1
            if self._pinned[pid] == 0:
                del self._pinned[pid]
        it = iter(fresh)
        for i in range(total):
            if table[i] is None:
                table[i] = next(it)
        self.pages_of[slot] = list(table)
        self.block_tables[slot, :total] = table
        self.version += 1
        self.peak_used_pages = max(self.peak_used_pages, self.used_pages)
        self.restores += 1
        return fresh

    def unpin(self, pinned: Sequence[Tuple[int, int]]) -> None:
        """Drop a discarded spill record's pins — the preempted request
        reached a terminal state and will never restore.  A page whose last
        pin drops with ``ref == 0`` parks in the LRU: it is registered
        prefix content, still servable as a hit and evictable on demand."""
        for _, pid in pinned:
            pins = self._pinned.get(pid, 0)
            assert pins > 0, f"unpin of unpinned page {pid}"
            if pins > 1:
                self._pinned[pid] = pins - 1
                continue
            del self._pinned[pid]
            if self.ref[pid] == 0:
                self._lru[pid] = None

    # ------------------------------------------------------------------ #
    # Chaos hooks + snapshot state
    # ------------------------------------------------------------------ #
    def seize(self, n: int) -> List[int]:
        """Chaos hook: take up to ``n`` pages off the free list (never out
        of the prefix-cache LRU — a simulated external memory squeeze must
        not silently evict cached content), making them unallocatable until
        :meth:`release_seized`.  Returns the seized ids."""
        ids = [self._free.pop() for _ in range(min(n, len(self._free)))]
        self._seized.update(ids)
        return ids

    def release_seized(self, ids: Sequence[int]) -> None:
        """Return chaos-seized pages to the free list."""
        for pid in ids:
            assert pid in self._seized, f"page {pid} was not seized"
            self._seized.discard(pid)
            self._free.append(pid)

    def state_dict(self) -> dict:
        """JSON-serializable allocator state for crash snapshots.

        Chaos seizures are transient *faults*, not engine state: seized
        pages are recorded as free, so a restored engine starts with the
        seizure released."""
        return {
            "geometry": [self.num_pages, self.page_size, self.slots,
                         self.max_pages_per_slot],
            "free": [int(p) for p in self._free] + sorted(
                int(p) for p in self._seized),
            "ref": [int(r) for r in self.ref],
            "pages_of": [[int(p) for p in lst] for lst in self.pages_of],
            "index": dict(self._index),
            "lru": [int(p) for p in self._lru],
            "pinned": {str(pid): int(pins)
                       for pid, pins in self._pinned.items()},
            "counters": {
                "peak_used_pages": self.peak_used_pages,
                "used_page_steps": self.used_page_steps,
                "observed_steps": self.observed_steps,
                "spills": self.spills,
                "restores": self.restores,
                "prefix_lookups": self.prefix_lookups,
                "prefix_hits": self.prefix_hits,
                "evictions": self.evictions,
                "cow_copies": self.cow_copies,
            },
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore allocator state saved by :meth:`state_dict` into a pool
        of identical geometry; verifies the full invariant set after."""
        geo = [self.num_pages, self.page_size, self.slots,
               self.max_pages_per_slot]
        if list(state["geometry"]) != geo:
            raise ValueError(
                f"pool geometry mismatch: snapshot {state['geometry']} "
                f"vs engine {geo}"
            )
        self._free = [int(p) for p in state["free"]]
        self.ref = np.asarray(state["ref"], np.int32)
        self.pages_of = [[int(p) for p in lst] for lst in state["pages_of"]]
        self.block_tables = np.zeros(
            (self.slots, self.max_pages_per_slot), np.int32)
        for slot, owned in enumerate(self.pages_of):
            self.block_tables[slot, :len(owned)] = owned
        self._index = dict(state["index"])
        self._page_key = {pid: key for key, pid in self._index.items()}
        self._lru = OrderedDict((int(p), None) for p in state["lru"])
        self._pinned = {int(pid): int(pins)
                        for pid, pins in state["pinned"].items()}
        self._seized = set()
        self.version += 1
        for name, val in state["counters"].items():
            setattr(self, name, val)
        self.assert_invariants()

    def ensure_capacity(self, slot: int, n_tokens: int) -> None:
        """Allocate pages so ``slot`` can hold ``n_tokens`` tokens."""
        need = self.pages_needed(n_tokens) - len(self.pages_of[slot])
        if need > 0:
            self.alloc(slot, need)

    def ensure_capacity_batch(self, n_tokens) -> None:
        """Grow every slot to hold ``n_tokens[slot]`` tokens in one
        bookkeeping pass (entry 0 or negative leaves a slot alone).

        The per-step replacement for calling :meth:`ensure_capacity` in a
        per-slot loop: one array pass computes every slot's page deficit,
        one :meth:`_take_free` covers the whole step (one exhaustion check,
        one eviction sweep), and the version counter bumps once, so the
        engine re-uploads the block tables at most once per step."""
        n_tokens = np.asarray(n_tokens, np.int64)
        assert n_tokens.shape == (self.slots,), (
            f"expected one token count per slot, got {n_tokens.shape}"
        )
        owned = np.fromiter((len(p) for p in self.pages_of), np.int64,
                            count=self.slots)
        need = -(-n_tokens // self.page_size) - owned
        need = np.where(n_tokens > 0, np.maximum(need, 0), 0)
        total = int(need.sum())
        if total == 0:
            return
        over = np.nonzero(owned + need > self.max_pages_per_slot)[0]
        if over.size:
            raise RuntimeError(
                f"slot {int(over[0])} exceeds max_pages_per_slot="
                f"{self.max_pages_per_slot}"
            )
        ids = self._take_free(total)
        self.ref[ids] = 1
        off = 0
        for slot in np.nonzero(need)[0]:
            n = int(need[slot])
            chunk = ids[off:off + n]
            start = int(owned[slot])
            self.pages_of[slot].extend(chunk)
            self.block_tables[slot, start:start + n] = chunk
            off += n
        self.version += 1
        self.peak_used_pages = max(self.peak_used_pages, self.used_pages)

    def writable(self, pid: int) -> bool:
        """True iff a slot may scribble into ``pid``: exclusively owned
        (one reference, no pins) and not published in the prefix index."""
        return (pid != 0 and self.ref[pid] == 1
                and self._pinned.get(pid, 0) == 0
                and pid not in self._page_key)

    def writable_mask(self) -> np.ndarray:
        """Vectorized :meth:`writable`: boolean ``[num_pages]`` mask, so
        per-step write-safety checks are one fancy-index instead of a
        python loop over every active slot's pages."""
        mask = self.ref == 1
        mask[0] = False
        for pid, pins in self._pinned.items():
            if pins:
                mask[pid] = False
        if self._page_key:
            mask[np.fromiter(self._page_key.keys(), np.int64,
                             count=len(self._page_key))] = False
        return mask

    # ------------------------------------------------------------------ #
    def assert_invariants(self) -> None:
        """Every non-null page id is in exactly one of: the free list,
        referenced by ≥1 slot, the prefix-cache LRU, pinned by a spill
        record, or chaos-seized — and all the cross-maps agree.
        Test/debug helper; gated into every scheduler step by
        ``REPRO_CHECK_INVARIANTS=1`` (:func:`invariant_checks_enabled`)."""
        free_set = set(self._free)
        assert len(free_set) == len(self._free), "duplicate ids in free list"
        owners = Counter()
        for lst in self.pages_of:
            owners.update(lst)
        assert 0 not in free_set and 0 not in owners and 0 not in self._lru
        for pid in range(1, self.num_pages):
            states = (
                pid in free_set,
                self.ref[pid] > 0 or self._pinned.get(pid, 0) > 0,
                pid in self._lru,
                pid in self._seized,
            )
            assert sum(states) == 1, (
                f"page {pid}: free={states[0]} held={states[1]} "
                f"lru={states[2]} seized={states[3]} (ref={self.ref[pid]}, "
                f"pins={self._pinned.get(pid, 0)})"
            )
            assert self.ref[pid] == owners[pid], (
                f"page {pid}: ref={self.ref[pid]} but "
                f"{owners[pid]} block-table references"
            )
        for key, pid in self._index.items():
            assert self._page_key.get(pid) == key, f"index desync on {pid}"
        assert len(self._index) == len(self._page_key)
        assert set(self._lru) <= set(self._page_key), "LRU holds uncached page"
        for pid, pins in self._pinned.items():
            assert pins > 0 and pid in self._page_key
        for slot, owned in enumerate(self.pages_of):
            n = len(owned)
            assert self.block_tables[slot, :n].tolist() == owned
            assert not self.block_tables[slot, n:].any()


# --------------------------------------------------------------------------- #
# Device-side helpers (pure jnp)
# --------------------------------------------------------------------------- #
def page_qtensor(pages, scales, fmt) -> QTensor:
    """:class:`QTensor` view of a page array (zero-copy metadata wrap).

    pages: [P, page, KV, hd] uint8 codes; scales: [P] f32 per-page scales.
    The scale is reshaped to broadcast per page, so ``view.dequantize()``
    is the float content of the whole pool — serving code, tests and
    offline tools share the training stack's one decode path instead of
    hand-multiplying codes and scales.
    """
    if isinstance(fmt, str):
        fmt = FORMATS[fmt]
    scale = jnp.asarray(scales, jnp.float32).reshape(
        (-1,) + (1,) * (pages.ndim - 1)
    )
    return QTensor(codes=pages, scale=scale, fmt=fmt.name)


def pow2_page_scale(amax, fmt):
    """Power-of-two scale mapping ``amax`` just inside the format's range.

    Pure integer bit manipulation (jnp.exp2/log2 are polynomial
    approximations under jit and would produce not-quite-pow2 scales):
    ``scale = 2^(ceil(log2(amax)) - e_max)`` so ``amax / scale <= 2^e_max
    <= max_normal``.  Clamped so both the scale and its reciprocal are
    normal FP8 values — the reciprocal is the LNS rescale operand for code
    splices.
    """
    if isinstance(fmt, str):
        fmt = FORMATS[fmt]
    a = jnp.maximum(jnp.asarray(amax, jnp.float32), 1e-12)
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    e_amax = ((bits >> 23) & 0xFF).astype(jnp.int32) - 127
    e_amax = e_amax + ((bits & 0x7FFFFF) != 0).astype(jnp.int32)  # ceil
    e = jnp.clip(e_amax - fmt.e_max, -(fmt.bias - 1), fmt.bias - 1)
    return jax.lax.bitcast_convert_type(
        ((e + 127).astype(jnp.uint32)) << 23, jnp.float32
    )


def _rbits(key, shape):
    return jax.random.randint(key, shape, 0, 2, dtype=jnp.int32)


def _is_key_batch(key, n: int) -> bool:
    """True when ``key`` is an [n]-batch of PRNG keys (one per slot)."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            return key.ndim == 1 and key.shape[0] == n
    except (AttributeError, TypeError):
        pass
    return key.ndim == 2 and key.shape[0] == n


def encode_kv(x, scale, fmt: str, mode: str = "stochastic", key=None):
    """float K/V -> FP8 codes at ``scale`` (value ~= decode(code) * scale).

    ``mode="stochastic"`` uses the f32 encoder's stochastic rounding (needs
    ``key`` — a single PRNG key, or a per-row batch of keys matching
    ``x.shape[0]``, the position-addressed serving write path); any
    Table-2/3 mode string falls through to the deterministic encoder.
    """
    xs = jnp.asarray(x, jnp.float32) / scale
    if mode == "stochastic":
        if key is None:
            raise ValueError("stochastic KV encode needs a PRNG key")
        if _is_key_batch(key, xs.shape[0]):
            return jax.vmap(
                lambda xb, kb: encode(xb, fmt, "stochastic", key=kb)
            )(xs, key)
        return encode(xs, fmt, "stochastic", key=key)
    return encode(xs, fmt, mode)


def rescale_codes(codes, inv_scale, fmt: str, mode: str = "stochastic",
                  key=None):
    """Rescale FP8 codes by ``inv_scale`` entirely in the code domain.

    ``code' = lns_mul(code, encode(inv_scale))`` — the paper's integer-add
    multiply.  For power-of-two ratios (the page-scale contract) the
    mantissa of the ratio code is zero, every Table-2 carry-in evaluates to
    0, and the rescale is exact; for general ratios ``mode="stochastic"``
    selects per element between the RD and RU carry-ins
    (``carry_ins.stochastic_carry_in``) so the rescale is unbiased.
    """
    ratio = encode(jnp.asarray(inv_scale, jnp.float32), fmt, "rne")
    ratio = jnp.broadcast_to(ratio, codes.shape)
    if mode == "stochastic" and supports_stochastic(fmt, "mul"):
        if key is None:
            raise ValueError("stochastic rescale needs a PRNG key")
        return lns_op(fmt, "mul", "stochastic", codes, ratio,
                      rbits=_rbits(key, codes.shape))
    if mode == "stochastic":  # format without RD/RU mul expressions (e4m3)
        mode = "rne"
    return lns_op(fmt, "mul", mode, codes, ratio)


def token_row_codes(scales, new, page_ids, rows, *,
                    fmt: Optional[str], mode: str = "stochastic", key=None,
                    write_mask=None, store_dtype=None):
    """The per-row half of ``write_token_page``: everything except the
    scatter.  Returns ``(masked_page_ids, row_codes [B, KV, hd], page_scale
    [B])`` — the write-mask redirect to the null page, the row-0 pow2 scale
    claim, and the (stochastic) encode, in exactly ``write_token_page``'s
    op order.  The fused decode kernel consumes the row codes directly
    (``kernels.paged_attention.fused_decode_write_attend``) so the
    attention launch never reads the scattered page arrays.
    """
    page_ids = jnp.asarray(page_ids, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    if write_mask is not None:
        write_mask = jnp.asarray(write_mask, bool)
        page_ids = jnp.where(write_mask, page_ids, 0)
    if fmt is None:
        codes = new if store_dtype is None else new.astype(store_dtype)
        return page_ids, codes, jnp.asarray(scales, jnp.float32)[page_ids]
    amax = jnp.max(jnp.abs(jnp.asarray(new, jnp.float32)), axis=(1, 2))
    fresh = rows == 0
    if write_mask is not None:
        fresh = fresh & write_mask  # masked lanes never claim a scale
    s = jnp.where(fresh, pow2_page_scale(amax, fmt), scales[page_ids])
    codes = encode_kv(new, s[:, None, None], fmt, mode, key)
    return page_ids, codes, s


def write_token_page(pages, scales, new, page_ids, rows, *,
                     fmt: Optional[str], mode: str = "stochastic", key=None,
                     write_mask=None):
    """Scatter one decode token's K or V into its page, per slot.

    pages: [P, page, KV, hd]; scales: [P] f32; new: [B, KV, hd] float;
    page_ids/rows: [B] int32 (physical page and row of each slot's write);
    ``key``: a PRNG key or a [B] batch of per-slot keys (the
    position-addressed serving streams).  ``write_mask``: optional [B]
    bool — the **explicit write mask** of the mixed prefill+decode step:
    lanes with a False mask are redirected into the reserved null page 0
    and never claim a page scale, so a masked sub-step can never scribble
    into a real (possibly shared, prefix-cached) page.

    A write to row 0 claims the page and sets its scale from the token's
    absmax; later rows reuse the page's existing scale.  Returns
    (pages, scales).
    """
    page_ids, codes, s = token_row_codes(
        scales, new, page_ids, rows, fmt=fmt, mode=mode, key=key,
        write_mask=write_mask, store_dtype=pages.dtype,
    )
    rows = jnp.asarray(rows, jnp.int32)
    # [P, page, d] pages (MLA) take the [B, 1, d] row without its head axis
    codes = codes.reshape(codes.shape[:1] + pages.shape[2:])
    pages = pages.at[page_ids, rows].set(codes)
    if fmt is not None:
        scales = scales.at[page_ids].set(s)
    return pages, scales


def write_prefill_pages(pages, scales, src, page_ids, *,
                        fmt: Optional[str], mode: str = "stochastic",
                        key=None):
    """Splice a prefill cache row into freshly allocated pages.

    pages: [P, page, KV, hd]; scales: [P]; src: [S, KV, hd] — scale-1 FP8
    codes (the dense prefill cache representation) or float ([P, page, d]
    pages, MLA's, take src [S, d]); page_ids:
    [n_pages] int32 with n_pages * page_size >= S.  Per-page scales come
    from the page content's absmax; the code -> code rescale is the LNS
    multiply with stochastic carry-ins (exact here because page scales are
    powers of two).  Returns (pages, scales).
    """
    page_ids = jnp.asarray(page_ids, jnp.int32)
    n_pages = page_ids.shape[0]
    page = pages.shape[1]
    S = src.shape[0]
    pad = n_pages * page - S
    srcp = jnp.pad(src, ((0, pad),) + ((0, 0),) * (src.ndim - 1)) if pad else src
    srcp = srcp.reshape(n_pages, page, *src.shape[1:])
    if fmt is None:
        pages = pages.at[page_ids].set(srcp.astype(pages.dtype))
        return pages, scales
    vals = code_to_f32(srcp, fmt)  # scale-1 decode of the dense cache codes
    amax = jnp.max(jnp.abs(vals), axis=tuple(range(1, srcp.ndim)))
    s = pow2_page_scale(amax, fmt)
    inv = (1.0 / s).reshape((-1,) + (1,) * (srcp.ndim - 1))
    codes = rescale_codes(srcp, inv, fmt, mode=mode, key=key)
    pages = pages.at[page_ids].set(codes)
    scales = scales.at[page_ids].set(s)
    return pages, scales
