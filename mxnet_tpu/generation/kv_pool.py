"""Paged KV-cache pool — fixed-size pages, per-sequence page tables,
refcounted copy-on-write sharing, and a cross-request prefix cache.

The dense alternative (one ``(max_len, heads, head_dim)`` buffer per
sequence slot) reserves ``max_len x batch`` tokens of HBM whether or not
they are ever written; mixed-length autoregressive traffic wastes most
of it.  Here KV storage is a shared pool of fixed-size pages (the vLLM
PagedAttention layout): a sequence owns ``ceil(len / page_size)`` pages,
listed in order in its page table, so live memory tracks live tokens and
the pool admits as many sequences as actually fit.

The K/V planes live on the pool's device and never visit the host: the
decode step's programs take them as carried arguments and hand them back
updated (``Executor.set_carried``), a prefill's K/V is scattered into
them by one program (:meth:`PagedKVPool.write_prefill`), a copy-on-write
split is one page copy over all layers.  Each of these donates the
planes it is given (the step's does not where its executable may be
serialized, executor.py), so the pool is their single owner: it holds
them as NDArrays that every executor binds, and a finished program
rebinds those NDArrays to its outputs before anything else can read
them.  The host keeps the bookkeeping: free list, page tables,
refcounts, prefix index.

A pool may carry a second kind of plane beside the paged ones: *slot*
planes, ``(num_slots,) + shape`` each, that hold one fixed-size state a
sequence and layer (a state-space layer's recurrent state and convolution
tail, or a sliding-window layer's ring of its last ``window`` tokens' K and
V, models/hybrid_lm.py) where a paged plane holds K/V a token.  One
manager owns both: a sequence takes its slot with its first pages
(:meth:`alloc_prefix`) and gives it back with them (:meth:`free`), so the
rule that keeps a page from a new owner until the last step that names it
has been dispatched (generation/engine.py) keeps a slot the same way.  Slot
0 is scratch as page 0 is.

Page 0 is reserved as scratch: inactive decode lanes point their
page-table rows at it so their masked-out writes land harmlessly
(ops/paged.py), and so do a prefill's padding rows.  Allocation is O(1)
off a free list; exhaustion raises
:class:`KVPoolExhaustedError` — the engine's admission backpressure and
preemption signal, never a deadlock.

Prefix caching (cross-request): every COMPLETE page a sequence fills is
content-addressed by a page-granular rolling hash over the token ids it
holds (each page's digest chains over every preceding token, so two
sequences share page ``j`` only when their first ``(j+1)*page_size``
tokens are identical).  Pages carry refcounts: :meth:`alloc_prefix`
resolves the longest indexed prefix of a new prompt and takes references
on the hit pages instead of recomputing them; :meth:`free` decrements,
and a page whose refcount reaches 0 while still indexed is RETAINED as
reusable cache rather than returned to the free list — a bounded LRU
(``MXNET_GEN_PREFIX_CACHE_PAGES``) that evicts only refcount-0 pages,
either on demand (allocation pressure) or to stay under the bound.  A
lane about to write into a shared page copies it first
(:meth:`ensure_writable` — copy-on-write), so a diverging stream can
never mutate history another stream (or the cache) still reads.

Watermark accounting (live/peak pages, occupancy over the allocatable
``num_pages - 1``, shared/cached page counts) exports through
``mxnet_tpu.telemetry`` gauges; every allocation passes the
``generation.kv.alloc`` fault point and every prefix lookup passes
``generation.prefix.lookup`` so chaos runs can starve or blind the pool
deterministically (a failed lookup degrades to a cache miss, never a
failed stream).
"""
from __future__ import annotations

import functools
import hashlib
import threading
from collections import OrderedDict, namedtuple
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import faults
from .. import profiler as _prof
from .. import telemetry as _telemetry
from ..base import MXNetError

__all__ = ["PagedKVPool", "KVPoolExhaustedError", "PlaneSpec"]

# One carried plane: the lane program's argument ``name``; ``kind`` "paged"
# (``shape`` a token: the plane is ``(num_pages, page_size) + shape``) or
# "slot" (``shape`` a sequence: ``(num_slots,) + shape``); its dtype.
PlaneSpec = namedtuple("PlaneSpec", "name kind shape dtype")


class KVPoolExhaustedError(MXNetError):
    """No free pages — backpressure: callers queue, shed, or preempt."""


def _write_program(length: int, kinds):
    """The scatter of one prefill length bucket: every paged plane takes
    its slab's rows at ``slots`` (indices into the plane's flattened
    ``num_pages * page_size`` token axis), every slot plane its slab's
    batch rows at ``state_slots`` (given only where the pool has such
    planes).  Donates the planes."""
    import jax

    def pool_write(planes, slabs, slots, *state_slots):
        out = []
        for plane, slab, kind in zip(planes, slabs, kinds):
            if kind == "slot":
                out.append(plane.at[state_slots[0]].set(
                    slab.astype(plane.dtype)))
                continue
            flat = plane.reshape((-1,) + plane.shape[2:])
            rows = slab.reshape((-1,) + plane.shape[2:]).astype(flat.dtype)
            out.append(flat.at[slots.reshape(-1)].set(rows)
                       .reshape(plane.shape))
        return out

    pool_write.__name__ = "pool_write_L%d" % length
    return jax.jit(pool_write, donate_argnums=(0,))


def _copy_page_program():
    """Page ``src`` onto page ``dst`` in every plane.  Donates the planes."""
    import jax

    def pool_copy_page(planes, src, dst):
        return [p.at[dst].set(p[src]) for p in planes]

    return jax.jit(pool_copy_page, donate_argnums=(0,))


def _page_digest(prev: bytes, chunk) -> bytes:
    """Rolling content hash for one page worth of token ids: chains the
    previous page's digest so a digest identifies the ENTIRE prefix up
    to and including this page, not just its own tokens."""
    h = hashlib.sha1(prev)
    h.update(np.asarray(chunk, np.int64).tobytes())
    return h.digest()


class PagedKVPool:
    """Paged K/V storage for ``num_layers`` attention layers, and with
    ``planes`` whatever else a model carries from step to step: the planes
    on the device, the bookkeeping on the host.

    Parameters
    ----------
    num_pages : int
        Total pool pages INCLUDING the reserved scratch page 0, so
        ``num_pages - 1`` are allocatable.
    page_size : int
        Tokens per page.
    num_layers, num_heads, head_dim : int
        K/V geometry; each layer holds one ``(num_pages, page_size,
        num_heads, head_dim)`` K plane and one V plane (``k_pools`` /
        ``v_pools``: NDArrays on ``ctx``).
    prefix_cache_pages : int, optional
        Upper bound on refcount-0 pages the prefix index retains after
        their last owner frees them (0, the default, disables prefix
        caching entirely — legacy alloc/free semantics).
    ctx : Context, optional
        Where the planes live (default: the current context).
    planes : sequence of (name, kind, shape, dtype), optional
        The planes, in the order the programs take and return them, in
        place of ``num_layers`` K/V pairs (:data:`PlaneSpec`).  A paged
        plane is one plane: a layer's K and V are two (``*_k_pool`` /
        ``*_v_pool``), a layer that caches one row a token (a latent) has
        one; write, copy and copy-on-write go plane by plane.
    num_slots : int, optional
        Slots of every slot plane INCLUDING the reserved scratch slot 0.
    """

    def __init__(self, num_pages, page_size, num_layers=None, num_heads=None,
                 head_dim=None, dtype=np.float32, prefix_cache_pages: int = 0,
                 ctx=None, planes=None, num_slots: int = 0):
        from .. import ndarray as nd
        from ..context import current_context

        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved scratch)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.prefix_cache_pages = max(0, int(prefix_cache_pages))
        self._dtype = np.dtype(dtype)
        if planes is None:
            planes = [("layer%d_%s_pool" % (i, kv), "paged",
                       (int(num_heads), int(head_dim)), self._dtype)
                      for i in range(int(num_layers)) for kv in "kv"]
        self.specs = [PlaneSpec(n, k, tuple(int(d) for d in shp),
                                np.dtype(dt)) for n, k, shp, dt in planes]
        has_slots = any(s.kind == "slot" for s in self.specs)
        if has_slots and num_slots < 2:
            raise ValueError("need >= 2 slots (slot 0 is reserved scratch)")
        self.num_slots = int(num_slots) if has_slots else 0
        ctx = ctx or current_context()
        with _prof.Frame("start:pool", "startup") as span:
            self._planes = [
                nd.zeros(((self.num_pages, self.page_size)
                          if s.kind == "paged" else (self.num_slots,))
                         + s.shape, ctx, dtype=s.dtype)
                for s in self.specs]
            span.set(bytes=self.device_bytes(), pages=self.num_pages,
                     slots=self.num_slots)
            # a sliding-window layer's rings (``*_ring``: the last
            # ``window`` tokens a lane, models/hybrid_lm.py), every slot's
            rings = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                        for s in self.specs
                        if s.kind == "slot" and s.name.endswith("_ring"))
            if rings:
                span.set(ring_bytes=self.num_slots * rings)
        self._paged = [p for p, s in zip(self._planes, self.specs)
                       if s.kind == "paged"]
        # the K/V pairs among them, by the names this pool gives its own
        # (``*_k_pool`` / ``*_v_pool``), for the readers that want a layer's
        # K and V (tests, ``read_page``); ``num_layers`` counts those pairs
        self.k_pools, self.v_pools = (
            [p for p, s in zip(self._planes, self.specs)
             if s.kind == "paged" and s.name.endswith(end)]
            for end in ("_k_pool", "_v_pool"))
        self.num_layers = len(self.k_pools)
        # bytes of one sequence's slots over every slot plane
        self.slot_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                              for s in self.specs if s.kind == "slot")
        self._free_slots: List[int] = list(range(self.num_slots - 1, 0, -1))
        self._slots: Dict[object, int] = {}
        self.peak_slots = 0
        self._writers: Dict[tuple, object] = {}  # slots' shape -> program
        self._copy_page = _prof.first_call(
            _copy_page_program(), "pool",
            functools.partial(setattr, self, "_copy_page"))
        self._lock = threading.Lock()
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._tables: Dict[object, List[int]] = {}
        self._lengths: Dict[object, int] = {}
        # -- sharing / prefix-cache state ---------------------------------
        self._ref: Dict[int, int] = {}          # page -> refcount (live)
        self._index: "OrderedDict[bytes, int]" = OrderedDict()  # LRU->MRU
        self._page_key: Dict[int, bytes] = {}   # indexed page -> digest
        self._cached = 0                        # indexed pages at ref 0
        self._chain: Dict[object, Tuple[int, bytes]] = {}  # seq -> (pages
        #                                     registered, digest so far)
        self.peak_pages = 0
        reg = self._registry = _telemetry.Registry()
        self._g_live = reg.gauge("mxtpu_gen_kv_pages_live")
        self._g_peak = reg.gauge("mxtpu_gen_kv_pages_peak")
        self._g_occ = reg.gauge("mxtpu_gen_kv_pool_occupancy_pct")
        # ratio gauge over the ALLOCATABLE pages (num_pages - 1): hits
        # exactly 1.0 at a full pool, unlike pre-fix math that could
        # never reach it when derived from the raw num_pages
        self._g_occ_ratio = reg.gauge("mxtpu_gen_kv_occupancy")
        self._g_shared = reg.gauge("mxtpu_gen_pages_shared")
        self._g_cached = reg.gauge("mxtpu_gen_prefix_cached_pages")
        self._c_allocs = reg.counter("mxtpu_gen_kv_page_allocs_total")
        self._c_frees = reg.counter("mxtpu_gen_kv_page_frees_total")
        self._c_hits = reg.counter("mxtpu_gen_prefix_hits_total")
        self._c_misses = reg.counter("mxtpu_gen_prefix_misses_total")
        self._c_evict = reg.counter("mxtpu_gen_prefix_evictions_total")
        self._c_cow = reg.counter("mxtpu_gen_kv_cow_copies_total")
        self._c_hit_tokens = reg.counter("mxtpu_gen_prefix_hit_tokens_total")
        if self.num_slots:
            self._g_slots = reg.gauge("mxtpu_gen_state_slots_live")
            self._g_slots_peak = reg.gauge("mxtpu_gen_state_slots_peak")
            self._g_state_bytes = reg.gauge("mxtpu_gen_state_bytes")
        _telemetry.register_collector(self)

    # -- accounting -------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable pages (scratch page excluded)."""
        return self.num_pages - 1

    def live_pages(self) -> int:
        with self._lock:
            return self._live_locked()

    def _live_locked(self) -> int:
        """Pages owned by at least one live sequence — excludes scratch
        page 0, the free list, AND retained (refcount-0) cache pages."""
        return self.capacity - len(self._free) - self._cached

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def reclaimable_pages(self) -> int:
        """Pages an allocation can obtain: the free list plus retained
        refcount-0 cache pages (evicted on demand)."""
        with self._lock:
            return len(self._free) + self._cached

    def cached_pages(self) -> int:
        with self._lock:
            return self._cached

    def shared_pages(self) -> int:
        """Pages referenced by more than one live sequence."""
        with self._lock:
            return sum(1 for r in self._ref.values() if r > 1)

    def total_refcount(self) -> int:
        """Sum of live refcounts — 0 after every sequence closed means
        no leaked shared pages (the chaos-run invariant)."""
        with self._lock:
            return sum(self._ref.values())

    def occupancy(self) -> float:
        return self.live_pages() / float(self.capacity)

    def pages_for(self, num_tokens: int) -> int:
        return -(-int(num_tokens) // self.page_size)  # ceil div

    def seq_length(self, seq_id) -> int:
        with self._lock:
            return self._lengths[seq_id]

    def live_sequences(self) -> int:
        with self._lock:
            return len(self._tables)

    def free_slots(self) -> Optional[int]:
        """State slots an admission can take; None where the pool has no
        slot planes (nothing to run out of)."""
        if not self.num_slots:
            return None
        with self._lock:
            return len(self._free_slots)

    def state_slot(self, seq_id) -> int:
        """The slot of every slot plane that holds this sequence's state."""
        with self._lock:
            return self._slots[seq_id]

    def _refresh_gauges_locked(self):
        if self.num_slots:
            self.peak_slots = max(self.peak_slots, len(self._slots))
            self._g_slots.set(len(self._slots))
            self._g_slots_peak.set(self.peak_slots)
            self._g_state_bytes.set(len(self._slots) * self.slot_bytes)
        live = self._live_locked()
        if live > self.peak_pages:
            self.peak_pages = live
        self._g_live.set(live)
        self._g_peak.set(self.peak_pages)
        self._g_occ.set(int(round(100.0 * live / self.capacity)))
        self._g_occ_ratio.set(round(live / float(self.capacity), 4))
        self._g_shared.set(sum(1 for r in self._ref.values() if r > 1))
        self._g_cached.set(self._cached)

    # -- prefix-index internals (all called with the lock held) ----------
    def _evict_one_locked(self) -> bool:
        """Drop the least-recently-used refcount-0 indexed page back to
        the free list.  Returns False when nothing is evictable."""
        for key, page in self._index.items():
            if self._ref.get(page, 0) == 0:
                del self._index[key]
                del self._page_key[page]
                self._cached -= 1
                self._free.append(page)
                self._c_evict.inc()
                return True
        return False

    def _reserve_locked(self, need: int):
        """Ensure ``need`` pages are on the free list, evicting retained
        cache pages LRU-first; raises when the pool genuinely cannot."""
        while len(self._free) < need:
            if not self._evict_one_locked():
                raise KVPoolExhaustedError(
                    "KV pool exhausted: need %d pages, %d free (capacity "
                    "%d); retry, shed, or preempt" %
                    (need, len(self._free), self.capacity))

    def _enforce_cache_bound_locked(self):
        while self._cached > self.prefix_cache_pages:
            if not self._evict_one_locked():
                break

    def _release_page_locked(self, page: int):
        """Drop one reference; a refcount-0 page is retained when still
        indexed (and retention is enabled), else returned to the free
        list."""
        r = self._ref.get(page, 0) - 1
        if r > 0:
            self._ref[page] = r
            return
        self._ref.pop(page, None)
        key = self._page_key.get(page)
        if key is not None and self.prefix_cache_pages > 0:
            self._cached += 1
        else:
            if key is not None:
                del self._index[key]
                del self._page_key[page]
            self._free.append(page)

    def _match_prefix_locked(self, tokens) -> Tuple[List[int], List[bytes]]:
        """Longest run of indexed pages covering ``tokens``' complete
        page chunks; returns (pages, their chained digests)."""
        ps = self.page_size
        pages: List[int] = []
        digests: List[bytes] = []
        key = b""
        for start in range(0, (len(tokens) // ps) * ps, ps):
            key = _page_digest(key, tokens[start:start + ps])
            page = self._index.get(key)
            if page is None:
                break
            pages.append(page)
            digests.append(key)
        return pages, digests

    # -- alloc / extend / free -------------------------------------------
    def can_fit(self, num_tokens: int) -> bool:
        with self._lock:
            return (self.pages_for(num_tokens)
                    <= len(self._free) + self._cached)

    def alloc(self, seq_id, num_tokens: int) -> List[int]:
        """Claim pages for a new sequence of ``num_tokens`` tokens;
        returns its page list.  Raises :class:`KVPoolExhaustedError`
        without allocating anything when the pool cannot fit it."""
        pages, _ = self.alloc_prefix(seq_id, num_tokens, tokens=None)
        return pages

    def alloc_prefix(self, seq_id, num_tokens: int,
                     tokens=None) -> Tuple[List[int], int]:
        """Claim pages for a new sequence, resolving ``tokens`` (the
        prompt) against the prefix index first.  Returns ``(pages,
        cached_tokens)`` where the first ``cached_tokens`` positions'
        K/V are already materialized in shared pages — the caller skips
        prefill for them and feeds only the remainder.

        The hit policy is conservative: a match is only taken when the
        cached run covers at least as many tokens as the leftover
        suffix, so a near-miss never trades one big prefill for a long
        dribble of per-token catch-up steps.  ``cached_tokens`` is
        capped at ``num_tokens - 1`` — the final prompt position must
        always be (re)fed so its logits exist to produce the first
        generated token; when the cache covers it too, the write lands
        in a shared page and copy-on-write splits it.

        A fault injected at ``generation.prefix.lookup`` degrades the
        lookup to a miss (full prefill) instead of failing the stream.
        """
        faults.fire("generation.kv.alloc")
        lookup_ok = True
        if tokens is not None and self.prefix_cache_pages > 0:
            try:
                faults.fire("generation.prefix.lookup")
            except Exception:
                lookup_ok = False
        need_total = max(1, self.pages_for(num_tokens))
        with self._lock:
            if seq_id in self._tables:
                raise MXNetError("sequence %r already allocated" % (seq_id,))
            taken: List[int] = []
            digests: List[bytes] = []
            cached_tokens = 0
            if tokens is not None and self.prefix_cache_pages > 0 \
                    and lookup_ok:
                hit_pages, hit_digests = self._match_prefix_locked(tokens)
                usable = min(len(hit_pages) * self.page_size,
                             int(num_tokens) - 1)
                if usable >= 1 and (int(num_tokens) - usable) <= usable:
                    cached_tokens = usable
                    n_pages = self.pages_for(usable)
                    taken = hit_pages[:n_pages]
                    digests = hit_digests[:n_pages]
            if tokens is not None and self.prefix_cache_pages > 0:
                if cached_tokens:
                    self._c_hits.inc()
                    self._c_hit_tokens.inc(cached_tokens)
                else:
                    self._c_misses.inc()
            fresh_need = need_total - len(taken)
            if self.num_slots and not self._free_slots:
                raise KVPoolExhaustedError(
                    "state slots exhausted: all %d hold a live sequence; "
                    "retry, shed, or preempt" % (self.num_slots - 1))
            self._reserve_locked(fresh_need)
            if self.num_slots:
                self._slots[seq_id] = self._free_slots.pop()
            for page, key in zip(taken, digests):
                r = self._ref.get(page, 0)
                if r == 0:
                    self._cached -= 1
                self._ref[page] = r + 1
                self._index.move_to_end(key)
            fresh = [self._free.pop() for _ in range(fresh_need)]
            for page in fresh:
                self._ref[page] = 1
            pages = taken + fresh
            self._tables[seq_id] = pages
            self._lengths[seq_id] = int(num_tokens)
            self._chain[seq_id] = (len(taken),
                                   digests[-1] if digests else b"")
            self._c_allocs.inc(fresh_need)
            self._refresh_gauges_locked()
            return list(pages), cached_tokens

    def extend(self, seq_id, new_length: int) -> List[int]:
        """Grow a sequence to ``new_length`` tokens, claiming new pages
        when it crosses a page boundary.  Raises
        :class:`KVPoolExhaustedError` (state unchanged) when the pool is
        out — the engine preempts a sequence to make room."""
        with self._lock:
            pages = self._tables.get(seq_id)
            if pages is None:
                raise MXNetError("unknown sequence %r" % (seq_id,))
            need = self.pages_for(new_length) - len(pages)
            if need > 0:
                self._reserve_locked(need)
            for _ in range(max(0, need)):
                page = self._free.pop()
                self._ref[page] = 1
                pages.append(page)
            self._lengths[seq_id] = max(self._lengths[seq_id],
                                        int(new_length))
            if need > 0:
                # the gauges count pages: a step that claims none (15 of
                # 16 at pages of 16 tokens) moves none of them, and their
                # sums run over every referenced page of the pool
                self._c_allocs.inc(need)
                self._refresh_gauges_locked()
            return list(pages)

    def free(self, seq_id):
        """Release a sequence's references (idempotent).  Unshared pages
        return to the free list; pages other sequences still reference
        merely decrement; refcount-0 pages the prefix index still names
        are retained as cache, LRU-bounded by ``prefix_cache_pages``."""
        with self._lock:
            pages = self._tables.pop(seq_id, None)
            self._lengths.pop(seq_id, None)
            self._chain.pop(seq_id, None)
            slot = self._slots.pop(seq_id, None)
            if slot is not None:
                self._free_slots.append(slot)
            if pages:
                # reversed keeps the legacy free-list LIFO order: a
                # follow-up alloc reuses the pages lowest-id-first
                for page in reversed(pages):
                    self._release_page_locked(page)
                self._c_frees.inc(len(pages))
                self._enforce_cache_bound_locked()
                self._refresh_gauges_locked()

    # -- copy-on-write ----------------------------------------------------
    def is_shared(self, seq_id, position: int) -> bool:
        """True when the page holding ``position`` must not be written
        by this sequence (another reference or the index still reads
        it)."""
        with self._lock:
            pages = self._tables.get(seq_id)
            if pages is None:
                raise MXNetError("unknown sequence %r" % (seq_id,))
            idx = int(position) // self.page_size
            if idx >= len(pages):
                return False
            page = pages[idx]
            return self._ref.get(page, 0) > 1 or page in self._page_key

    def ensure_writable(self, seq_id, position: int) -> bool:
        """Copy-on-write: when the page holding ``position`` is shared
        (refcount > 1) or still prefix-indexed, copy its K/V into a
        fresh private page and repoint this sequence's table entry, so
        the upcoming write can never mutate data another stream or the
        cache reads.  Returns True when a copy happened.  Raises
        :class:`KVPoolExhaustedError` when no page can be claimed."""
        with self._lock:
            pages = self._tables.get(seq_id)
            if pages is None:
                raise MXNetError("unknown sequence %r" % (seq_id,))
            idx = int(position) // self.page_size
            if idx >= len(pages):
                return False  # beyond allocation: write hits scratch
            page = pages[idx]
            if self._ref.get(page, 0) <= 1 and page not in self._page_key:
                return False
            self._reserve_locked(1)
            fresh = self._free.pop()
            self._ref[fresh] = 1
            self.copy_page(page, fresh)
            pages[idx] = fresh
            self._release_page_locked(page)
            # the chain state survives a COW: digests are content-based
            # (over token ids), and the index keeps naming the ORIGINAL
            # page, whose bytes this sequence can no longer touch
            self._c_cow.inc()
            self._c_allocs.inc()
            self._enforce_cache_bound_locked()
            self._refresh_gauges_locked()
            return True

    # -- prefix registration ----------------------------------------------
    def register_prefix(self, seq_id, tokens) -> int:
        """Publish this sequence's newly COMPLETE pages (every position
        written and final) into the prefix index under their rolling
        content digests.  ``tokens`` must cover exactly the positions
        whose K/V is materialized and valid.  Incremental and
        idempotent; returns the number of pages newly indexed."""
        if self.prefix_cache_pages <= 0:
            return 0
        ps = self.page_size
        with self._lock:
            pages = self._tables.get(seq_id)
            if pages is None:
                return 0
            n_reg, key = self._chain.get(seq_id, (0, b""))
            complete = min(len(tokens) // ps, len(pages))
            added = 0
            for j in range(n_reg, complete):
                key = _page_digest(key, tokens[j * ps:(j + 1) * ps])
                page = pages[j]
                if key not in self._index and page not in self._page_key:
                    self._index[key] = page
                    self._page_key[page] = key
                    added += 1
            self._chain[seq_id] = (complete, key)
            if added:
                self._refresh_gauges_locked()
            return added

    # -- page-table / data plumbing for the decode step ------------------
    def page_table_row(self, seq_id, max_pages: int) -> np.ndarray:
        """The sequence's page list padded to ``max_pages`` with the
        scratch page 0 (the decode step's per-lane page-table row)."""
        with self._lock:
            pages = self._tables.get(seq_id)
            if pages is None:
                raise MXNetError("unknown sequence %r" % (seq_id,))
            if len(pages) > max_pages:
                raise MXNetError(
                    "sequence %r spans %d pages > max_pages %d"
                    % (seq_id, len(pages), max_pages))
            row = np.zeros((max_pages,), np.float32)
            row[:len(pages)] = pages
            return row

    # -- the planes (device side) ----------------------------------------
    def planes(self) -> list:
        """The planes in the order the programs take and return them
        (``specs``' order): ``[k0, v0, k1, v1, ...]`` where K/V is all."""
        return list(self._planes)

    def plane_names(self) -> List[str]:
        return [s.name for s in self.specs]

    def paged_planes(self) -> list:
        """The paged planes alone, in ``specs``' order."""
        return list(self._paged)

    def device_bytes(self) -> int:
        return sum(int(np.prod(p.shape)) * p.dtype.itemsize
                   for p in self.planes())

    def devices(self) -> List[str]:
        return sorted({str(d) for p in self.planes()
                       for d in p._data.devices()})

    def _run(self, program, planes, *args):
        """One donating program over ``planes``: the old buffers are dead
        when it returns and the planes hold its outputs."""
        for plane, new in zip(planes,
                              program([p._data for p in planes], *args)):
            plane._set(new)

    def write_slots(self, slabs, slots, state_slots=None):
        """Scatter ``slabs`` — one a plane, in the planes' order, on the
        device or the host — into the planes on the device.  A paged
        plane's slab is ``(batch, length, heads, head_dim)``: row ``[b,
        t]`` goes to token slot ``slots[b, t]`` (``page * page_size +
        offset``).  A slot plane's slab is ``(batch,) + shape``: row ``b``
        goes to slot ``state_slots[b]`` (default: scratch).  One program a
        length bucket (``jit_pool_write_L<length>``)."""
        shape = tuple(slots.shape)
        program = self._writers.get(shape)
        if program is None:
            program = self._writers[shape] = _prof.first_call(
                _write_program(shape[1], [s.kind for s in self.specs]),
                "pool", functools.partial(self._writers.__setitem__, shape))
        more = ()
        if self.num_slots:
            more = (np.zeros(slots.shape[:1], np.int32)
                    if state_slots is None
                    else np.asarray(state_slots, np.int32),)
        self._run(program, self._planes, list(slabs),
                  np.asarray(slots, np.int32), *more)

    def write_prefill(self, seq_ids, slabs, lengths):
        """Scatter a prefill pass's K/V into its sequences' pages, and its
        final states into their slots: batch row ``b`` of the slabs (see
        :meth:`write_slots`) belongs to ``seq_ids[b]``, of which only the
        first ``lengths[b]`` rows are real.  Padding — the rest of a row,
        and batch rows beyond ``seq_ids`` — lands in scratch page 0 and
        scratch slot 0."""
        ps = self.page_size
        paged = next(slab for slab, s in zip(slabs, self.specs)
                     if s.kind == "paged")  # any: they share (batch, length)
        slots = np.zeros(paged.shape[:2], np.int32)
        state_slots = np.zeros(paged.shape[:1], np.int32)
        with self._lock:
            for b, (seq_id, n) in enumerate(zip(seq_ids, lengths)):
                pos = np.arange(int(n))
                pages = np.asarray(self._tables[seq_id], np.int32)
                slots[b, :int(n)] = pages[pos // ps] * ps + pos % ps
                state_slots[b] = self._slots.get(seq_id, 0)
        self.write_slots(slabs, slots, state_slots)

    def copy_page(self, src: int, dst: int):
        """Page ``src`` onto page ``dst`` in every paged plane, one
        program."""
        self._run(self._copy_page, self._paged, np.int32(src),
                  np.int32(dst))

    def read_page(self, layer: int, page: int):
        """``(k, v)`` of one page of one K/V layer (``k_pools`` /
        ``v_pools``' order), read to the host: for tests and debugging,
        nothing on the serving path reads a plane."""
        return (self.k_pools[layer][int(page)].asnumpy(),
                self.v_pools[layer][int(page)].asnumpy())

    def snapshot(self) -> dict:
        with self._lock:
            live = self._live_locked()
            slots = {} if not self.num_slots else {"state_slots": {
                "capacity": self.num_slots - 1, "live": len(self._slots),
                "peak": self.peak_slots, "slot_bytes": self.slot_bytes,
                "bytes": len(self._slots) * self.slot_bytes}}
            return {**slots,
                    "capacity": self.capacity, "live_pages": live,
                    "peak_pages": self.peak_pages,
                    "sequences": len(self._tables),
                    "occupancy": live / float(self.capacity),
                    "shared_pages": sum(1 for r in self._ref.values()
                                        if r > 1),
                    "cached_pages": self._cached,
                    "prefix_index_size": len(self._index),
                    "prefix_hits": self._c_hits.value,
                    "prefix_misses": self._c_misses.value,
                    "prefix_evictions": self._c_evict.value,
                    "cow_copies": self._c_cow.value,
                    "total_refcount": sum(self._ref.values()),
                    "device_bytes": self.device_bytes()}

    def render_prometheus(self):
        """Collector hook for ``telemetry.render_prometheus()``."""
        return self._registry.render_prometheus()
