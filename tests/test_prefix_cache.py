"""Pool-level contract of the cross-request prefix cache: refcounted
copy-on-write sharing, the bounded LRU index, content-addressed
matching, and the fault hook that degrades lookups to misses.  Tests of
the host-side bookkeeping on a tiny pool, so they run in milliseconds.
Engine-level behavior (zero prefill steps, TTFT, speculative parity)
lives in test_generation.py."""
import numpy as np
import pytest

from mxnet_tpu import faults
from mxnet_tpu.generation import KVPoolExhaustedError, PagedKVPool


def _pool(num_pages=16, page_size=4, cache=8):
    return PagedKVPool(num_pages=num_pages, page_size=page_size,
                       num_layers=1, num_heads=2, head_dim=4,
                       prefix_cache_pages=cache)


def _publish(pool, sid, tokens, seed=0):
    """Alloc + write + register + free one transcript: its full pages
    stay behind in the index as refcount-0 cache."""
    rng = np.random.RandomState(seed)
    n = len(tokens)
    pool.alloc_prefix(sid, n, tokens=tokens)
    k = rng.randn(n, 2, 4).astype(np.float32)
    v = rng.randn(n, 2, 4).astype(np.float32)
    pool.write_prefill([sid], [k[None], v[None]], [n])
    pool.register_prefix(sid, tokens)
    pool.free(sid)


def test_hit_maps_shared_pages_and_refcounts_drain():
    pool = _pool()
    t = list(range(8))  # two full pages
    _publish(pool, "a", t)
    assert pool.cached_pages() == 2
    assert pool.live_pages() == 0  # cache pages are not "live"

    pages_b, cached_b = pool.alloc_prefix("b", 8, tokens=t)
    pages_c, cached_c = pool.alloc_prefix("c", 8, tokens=t)
    # both map the SAME physical pages, K/V already materialized
    assert cached_b == cached_c == 7  # final position always re-fed
    assert pages_b == pages_c
    assert pool.shared_pages() == 2
    assert pool.total_refcount() > 0
    pool.free("b")
    pool.free("c")
    assert pool.total_refcount() == 0
    assert pool.cached_pages() == 2  # retained for the NEXT request


def test_match_is_content_addressed_not_positional():
    pool = _pool()
    t = list(range(8))
    _publish(pool, "a", t)
    # same first page, different second page: one-page partial hit
    t2 = t[:4] + [99, 98, 97, 96]
    _, cached = pool.alloc_prefix("b", 8, tokens=t2)
    assert cached == 4
    # completely different content: clean miss
    _, cached = pool.alloc_prefix("c", 8, tokens=[50 + i for i in range(8)])
    assert cached == 0
    snap = pool.snapshot()
    assert snap["prefix_hits"] == 1 and snap["prefix_misses"] >= 1


def test_lru_index_is_bounded_and_counts_evictions():
    pool = _pool(num_pages=32, cache=3)
    for i in range(3):
        _publish(pool, "s%d" % i, [16 * i + j for j in range(8)], seed=i)
    # 3 transcripts x 2 full pages = 6 published, bound is 3
    assert pool.cached_pages() == 3
    snap = pool.snapshot()
    assert snap["prefix_evictions"] == 3
    assert snap["prefix_index_size"] == 3
    # the OLDEST transcript was evicted, the newest survives
    _, cached_old = pool.alloc_prefix("old", 8, tokens=[j for j in range(8)])
    assert cached_old == 0
    _, cached_new = pool.alloc_prefix("new", 8,
                                      tokens=[32 + j for j in range(8)])
    assert cached_new > 0


def test_allocation_pressure_reclaims_cache_but_never_shared_pages():
    pool = _pool(num_pages=8, cache=8)  # capacity 7
    t = list(range(8))
    _publish(pool, "a", t)  # 2 cached pages
    _, cached = pool.alloc_prefix("b", 8, tokens=t)  # maps both, refcount 1
    assert cached == 7
    # 5 pages left (7 - 2 shared); a 20-token alloc (5 pages) must evict
    # nothing shared — it fits exactly in the free remainder
    pool.alloc("fill", 20)
    assert pool.total_refcount() > 0  # b's shared mapping survived
    # now NOTHING is reclaimable: shared pages are pinned
    with pytest.raises(KVPoolExhaustedError):
        pool.alloc("overflow", 4)
    pool.free("b")
    pool.free("fill")


def test_cache_disabled_pool_never_retains():
    pool = _pool(cache=0)
    t = list(range(8))
    pool.alloc_prefix("a", 8, tokens=t)
    pool.register_prefix("a", t)
    pool.free("a")
    assert pool.cached_pages() == 0
    assert pool.free_pages() == pool.capacity
    _, cached = pool.alloc_prefix("b", 8, tokens=t)
    assert cached == 0


def test_occupancy_ratio_reaches_exactly_one():
    """Satellite regression: capacity excludes the reserved scratch
    page, so a full pool reads occupancy 1.0 — not the asymptote the
    raw num_pages denominator produced."""
    pool = _pool(num_pages=8, cache=0)
    assert pool.capacity == 7
    pool.alloc("a", 7 * 4)  # every allocatable page
    assert pool.occupancy() == 1.0
    assert pool.snapshot()["occupancy"] == 1.0


def test_lookup_fault_degrades_to_miss_not_failure():
    pool = _pool()
    t = list(range(8))
    _publish(pool, "a", t)
    with faults.inject("generation.prefix.lookup:ioerr=1", seed=0):
        pages, cached = pool.alloc_prefix("b", 8, tokens=t)
    assert cached == 0  # blinded lookup: full prefill, stream unharmed
    assert len(pages) == 2
    pool.free("b")
    # with the plan gone the same prompt hits again
    _, cached = pool.alloc_prefix("c", 8, tokens=t)
    assert cached == 7


def test_cow_split_preserves_digest_chain_for_future_hits():
    """After a COW split the writer owns a private copy; the original
    page keeps serving hits because digests are content-based."""
    pool = _pool()
    t = list(range(8))
    _publish(pool, "a", t)
    pages_b, _ = pool.alloc_prefix("b", 8, tokens=t)
    assert pool.is_shared("b", 7)
    assert pool.ensure_writable("b", 7)
    assert not pool.is_shared("b", 7)
    assert pool.snapshot()["cow_copies"] >= 1
    # a second ensure_writable is a no-op (already private)
    assert not pool.ensure_writable("b", 7)
    pages_c, cached = pool.alloc_prefix("c", 8, tokens=t)
    assert cached == 7
    assert pages_c[1] == pages_b[1]  # c maps the pre-split original
    pool.free("b")
    pool.free("c")
    assert pool.total_refcount() == 0
