"""Generative serving tests — AOT bundles, speculative decoding and the
cross-request prefix cache.

The engine's executables round-trip through AOT bundles with their own
cache kinds, speculative greedy decode is bit-identical to plain greedy for
every K, and cached prefixes skip prefill without ever mutating a shared
page.

All CPU-only, on the tiny transformer LM of ``generation_lm.py``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import serving, telemetry
from mxnet_tpu.generation import DecodeEngine, PagedKVPool

from generation_lm import (HEADS, HID, LAYERS, S, SPEC, V, _NET, _PARAMS,
                           _prompts, _sequential_reference)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# compile cache + AOT bundles
# ---------------------------------------------------------------------------

def _cc_reset():
    telemetry._reset_for_tests()
    cc.reset_stats()


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "cc")
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", d)
    _cc_reset()
    yield d
    _cc_reset()


def test_aot_bundle_roundtrips_decode_executables(cache_dir, tmp_path,
                                                  monkeypatch):
    """The generator's prefill/decode executables ride in the AOT bundle
    with their own cache kinds; from_checkpoint restores the generator
    from the warmup manifest and warms it deserialize-only."""
    spec = dict(SPEC, lane_buckets=(1, 2), prefill_len_buckets=(8,),
                prefill_batch_buckets=(1, 2))
    prefix = str(tmp_path / "gen")
    mx.model.save_checkpoint(prefix, 1, _NET, dict(_PARAMS), {})
    srv = serving.InferenceServer(
        _NET, dict(_PARAMS), {"data": (2, S), "softmax_label": (2, S)},
        generator_spec=spec)
    try:
        ref = srv.submit_generate([6, 3, 9], 5).result(timeout=60)
        kinds = {getattr(e, "_kind", None) for e in srv.compiled_entries()}
        assert "gen-step" in kinds and "gen-prefill" in kinds, kinds
        bundle = srv.save_aot_bundle(prefix, 1)
    finally:
        srv.stop()
    manifest = cc.read_manifest(bundle)
    assert manifest["warmup"]["generator"]["lane_buckets"] == [1, 2]

    # the admin CLI labels decode entries by kind
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "compile_cache_admin.py"),
         "ls", "--dir", cache_dir, "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    ls_kinds = {e.get("kind")
                for e in json.loads(out.stdout.strip().splitlines()[-1])}
    assert "gen-step" in ls_kinds and "gen-prefill" in ls_kinds, ls_kinds

    _cc_reset()
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "")
    srv2 = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (2, S), "softmax_label": (2, S)})
    try:
        s = cc.stats()
        assert s["hits"] >= 1 and s["misses"] == 0, \
            "bundle-attached generator warmup still compiled: %s" % s
        assert srv2._generator is not None  # restored from the manifest
        assert srv2.submit_generate([6, 3, 9], 5).result(timeout=60) == ref
        assert srv2.cold_bucket_runs() == 0
    finally:
        srv2.stop()


# ---------------------------------------------------------------------------
# cross-request prefix caching + speculative decoding
# ---------------------------------------------------------------------------

def test_spec_greedy_bit_identical_across_k():
    """Speculative decoding with a draft model — here the target itself,
    but acceptance is argmax-vs-argmax so ANY draft works — must emit
    exactly the plain greedy transcript for every K: the verify graph is
    K+1 chained copies of the decode block, so accepted tokens are the
    target's own argmaxes by construction."""
    rng = np.random.RandomState(11)
    workload = [(p, int(rng.randint(3, 9))) for p in _prompts(rng, 5)]
    ref = _sequential_reference(_PARAMS, workload)
    for k in (1, 2, 3):
        eng = DecodeEngine(_PARAMS, draft={"params": dict(_PARAMS),
                                           "num_layers": LAYERS,
                                           "num_heads": HEADS,
                                           "hidden": HID, "k": k},
                           **SPEC)
        try:
            streams = [eng.submit(p, n) for p, n in workload]
            got = [s.result(timeout=120) for s in streams]
            proposed = sum(s.draft_proposed for s in streams)
            accepted = sum(s.draft_accepted for s in streams)
        finally:
            eng.stop()
        assert got == ref, "spec decode diverged at k=%d" % k
        assert eng.spec()["draft"]["k"] == k
        assert proposed > 0 and 0 < accepted <= proposed
    rendered = telemetry.render_prometheus()
    assert "mxtpu_gen_draft_proposed_total" in rendered
    assert "mxtpu_gen_draft_accepted_total" in rendered


def test_cached_prefix_admission_skips_prefill():
    """A request whose prompt the index fully covers admits with ZERO
    prefill steps and first token after ONE engine iteration — and the
    transcript still matches the uncached engine bit for bit."""
    rng = np.random.RandomState(13)
    shared = [int(t) for t in rng.randint(0, V, size=16)]
    spec = dict(SPEC, prefix_cache_pages=SPEC["num_pages"])
    ref = _sequential_reference(_PARAMS, [(shared, 6)])
    eng = DecodeEngine(_PARAMS, **spec)
    try:
        eng.generate(shared, 2, timeout=120)  # publishes the prefix
        st = eng.submit(shared, 6)
        got = st.result(timeout=120)
        assert got == ref[0]
        assert st.prefill_tokens == 0, \
            "cached admission still prefilled %d tokens" % st.prefill_tokens
        assert st.cached_prefix_tokens == len(shared) - 1
        assert st.ttft_iters == 1, st.ttft_iters
        snap = eng.pool.snapshot()
        assert snap["prefix_hits"] >= 1
    finally:
        eng.stop()
    rendered = telemetry.render_prometheus()
    assert "mxtpu_gen_prefix_hits_total" in rendered
    assert "mxtpu_gen_pages_shared" in rendered


def test_partial_prefix_hit_catches_up_in_one_iteration():
    """A 90%%-shared prompt (unique tail) admits against the index's
    page-granular match and batch-walks the remainder at admission:
    still zero prefill steps, still TTFT == 1 iteration, still
    bit-identical."""
    rng = np.random.RandomState(17)
    shared = [int(t) for t in rng.randint(0, V, size=18)]
    tail = [int(t) for t in rng.randint(0, V, size=3)]
    spec = dict(SPEC, prefix_cache_pages=SPEC["num_pages"])
    ref = _sequential_reference(_PARAMS, [(shared + tail, 5)])
    eng = DecodeEngine(_PARAMS, **spec)
    try:
        eng.generate(shared + [1], 2, timeout=120)
        st = eng.submit(shared + tail, 5)
        assert st.result(timeout=120) == ref[0]
        assert st.prefill_tokens == 0
        assert st.cached_prefix_tokens > 0
        assert st.ttft_iters == 1, st.ttft_iters
    finally:
        eng.stop()


def test_cow_isolation_never_mutates_shared_page():
    """Copy-on-write at the pool layer: a sequence diverging inside a
    shared page splits it first; the cached original — and any reader
    that mapped it — keeps its bytes."""
    rng = np.random.RandomState(19)
    pool = PagedKVPool(num_pages=16, page_size=4, num_layers=1,
                       num_heads=2, head_dim=4, prefix_cache_pages=8)
    t = [int(x) for x in rng.randint(0, V, size=8)]
    pages_a, cached = pool.alloc_prefix("a", 8, tokens=t)
    assert cached == 0  # cold index
    k = rng.randn(8, 2, 4).astype(np.float32)
    v = rng.randn(8, 2, 4).astype(np.float32)
    pool.write_prefill(["a"], [k[None], v[None]], [8])
    assert pool.register_prefix("a", t) == 2  # both full pages published
    pool.free("a")  # refcount-0 pages retained as cache

    pages_b, cached_b = pool.alloc_prefix("b", 8, tokens=t)
    assert cached_b == 7  # capped at num_tokens - 1
    last = pages_b[1]
    assert pool.is_shared("b", 7)
    before, _ = pool.read_page(0, last)
    assert np.array_equal(before, k[4:8])  # what a's prefill put there

    assert pool.ensure_writable("b", 7)  # COW split
    row = pool.page_table_row("b", 4)
    assert int(row[1]) != last, "diverging seq still maps the shared page"
    own = int(row[1])
    assert np.array_equal(pool.read_page(0, own)[0], before)  # the copy
    pool.k_pools[0][own, 3] = 99.0  # b writes its own copy
    assert np.all(pool.read_page(0, own)[0][3] == 99.0)
    assert np.array_equal(pool.read_page(0, last)[0], before), \
        "COW leaked a write into the shared page"
    assert pool.snapshot()["cow_copies"] >= 1

    # a third request still hits the ORIGINAL bytes
    pages_c, cached_c = pool.alloc_prefix("c", 8, tokens=t)
    assert cached_c == 7 and pages_c[1] == last
    assert np.array_equal(pool.read_page(0, last)[0], before)
    pool.free("b")
    pool.free("c")
    assert pool.total_refcount() == 0


def test_preempted_lane_readmits_through_prefix_index():
    """Satellite regression: a preempted lane's re-admission consults
    the prefix index — prompt + generated-so-far re-enter as a cache
    hit, so the lane's prefill token count never grows past the
    original prompt."""
    rng = np.random.RandomState(23)
    prompt = [int(t) for t in rng.randint(0, V, size=9)]
    ref = _sequential_reference(_PARAMS, [(prompt, 6)])
    spec = dict(SPEC, prefix_cache_pages=SPEC["num_pages"])
    eng = DecodeEngine(_PARAMS, warmup=True, start=False, **spec)
    try:
        st = eng.submit(prompt, 6)
        eng._admit()
        assert st.prefill_tokens == len(prompt)
        eng._decode_step()  # a couple of tokens land before the preempt
        eng._decode_step()
        assert len(st.tokens) >= 2
        assert eng._preempt_one()
        eng._admit()  # re-admission: prefix HIT, not a second prefill
        assert st.prefill_tokens == len(prompt), \
            "re-admission re-prefilled the transcript"
        assert st.cached_prefix_tokens > 0
        assert eng.metrics.preempted.value == 1
        for _ in range(32):
            if st.done:
                break
            eng._decode_step()
        assert st.done and list(st.tokens) == ref[0]
    finally:
        eng.stop()


def test_aot_bundle_carries_draft_and_resolved_k(cache_dir, tmp_path,
                                                 monkeypatch):
    """The AOT bundle manifest carries the draft checkpoint (spilled to
    a sidecar .draft.params file) and the RESOLVED speculative K; a
    replica restored from the bundle speculates immediately with zero
    compiles and zero re-tuning."""
    spec = dict(SPEC, lane_buckets=(1, 2), prefill_len_buckets=(16,),
                prefill_batch_buckets=(1, 2),
                draft={"params": dict(_PARAMS), "num_layers": LAYERS,
                       "num_heads": HEADS, "hidden": HID, "k": 2})
    prefix = str(tmp_path / "gen")
    mx.model.save_checkpoint(prefix, 1, _NET, dict(_PARAMS), {})
    srv = serving.InferenceServer(
        _NET, dict(_PARAMS), {"data": (2, S), "softmax_label": (2, S)},
        generator_spec=spec)
    try:
        ref = srv.submit_generate([6, 3, 9], 5).result(timeout=120)
        assert srv._generator.spec()["draft"]["k"] == 2
        bundle = srv.save_aot_bundle(prefix, 1)
    finally:
        srv.stop()
    manifest = cc.read_manifest(bundle)
    gen_spec = manifest["warmup"]["generator"]
    assert gen_spec["draft"]["k"] == 2
    assert isinstance(gen_spec["draft"]["params"], str)
    assert gen_spec["draft"]["params"].endswith(".draft.params")
    assert os.path.exists(gen_spec["draft"]["params"])

    _cc_reset()
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "")
    srv2 = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (2, S), "softmax_label": (2, S)})
    try:
        s = cc.stats()
        assert s["misses"] == 0, \
            "bundle-attached speculative rig still compiled: %s" % s
        eng2 = srv2._generator
        assert eng2 is not None and eng2.spec()["draft"]["k"] == 2
        st = srv2.submit_generate([6, 3, 9], 5)
        assert st.result(timeout=120) == ref
        assert st.draft_proposed > 0  # it actually speculated
        assert eng2.cold_decode_runs() == 0
    finally:
        srv2.stop()
