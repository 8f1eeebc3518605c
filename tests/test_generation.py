"""Generative serving tests — continuous batching + paged KV-cache.

The PR-12 acceptance criteria as assertions: continuous-batched greedy
decode is bit-identical to sequential decode, paged attention matches
the dense full-prefix recompute, admit/retire churns correctly under
length skew, pool exhaustion backpressures (and preempts) without
deadlocking, the decode loop never recompiles after warmup, the engine's
executables round-trip through AOT bundles with their own cache kinds,
and — chaos-marked — a replica killed mid-stream resumes on a survivor
with zero duplicated or dropped tokens.

All CPU-only: the model is a tiny transformer LM (vocab 64, 2 layers)
with deterministic random weights, so greedy argmax transcripts are
stable references.
"""
import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import faults, generation, serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.generation import (DecodeEngine, KVPoolExhaustedError,
                                  PagedKVPool)
from mxnet_tpu.serving import QueueFullError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V, LAYERS, HEADS, HID, S = 64, 2, 2, 32, 32

SPEC = dict(vocab_size=V, num_layers=LAYERS, num_heads=HEADS, hidden=HID,
            max_seq_len=S, lane_buckets=(1, 2, 4), page_size=4,
            num_pages=48, prefill_len_buckets=(8, 16, 32))


def _lm_params(seed=0):
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=LAYERS,
                                       num_heads=HEADS, hidden=HID,
                                       seq_len=S)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(seed)
    params = {
        name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
        for name, shp in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "softmax_label")}
    return net, params


_NET, _PARAMS = _lm_params()


def _prompts(rng, n, lo=2, hi=12):
    return [[int(t) for t in rng.randint(0, V, size=rng.randint(lo, hi))]
            for _ in range(n)]


def _sequential_reference(params, workload, **spec_overrides):
    """One request at a time through a fresh engine: the ground truth
    continuous batching must reproduce bit-identically."""
    spec = dict(SPEC, **spec_overrides)
    eng = DecodeEngine(params, **spec)
    try:
        return [eng.generate(p, n) for p, n in workload]
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# paged KV pool
# ---------------------------------------------------------------------------

def test_kv_pool_alloc_extend_free():
    pool = PagedKVPool(num_pages=8, page_size=4, num_layers=1,
                       num_heads=2, head_dim=4)
    assert pool.capacity == 7  # page 0 is reserved scratch
    assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    pool.alloc(0, 6)           # 2 pages
    pool.alloc(1, 4)           # 1 page
    assert pool.free_pages() == 4
    pool.extend(1, 5)          # crosses a page boundary: +1 page
    assert pool.free_pages() == 3
    assert pool.peak_pages == 4
    pool.free(0)
    assert pool.free_pages() == 5
    pool.free(1)
    assert pool.free_pages() == 7
    assert pool.peak_pages == 4  # high-water mark survives frees


def test_kv_pool_exhaustion_raises():
    pool = PagedKVPool(num_pages=4, page_size=4, num_layers=1,
                       num_heads=2, head_dim=4)
    pool.alloc(0, 12)  # 3 pages = full capacity
    with pytest.raises(KVPoolExhaustedError):
        pool.alloc(1, 1)
    pool.free(0)
    pool.alloc(1, 1)  # freed pages are reusable


# ---------------------------------------------------------------------------
# decode parity: the acceptance bit-identity checks
# ---------------------------------------------------------------------------

def test_continuous_batching_matches_sequential():
    """N concurrent mixed-length requests through one engine produce
    exactly the transcripts of one-at-a-time decoding."""
    rng = np.random.RandomState(7)
    workload = [(p, int(rng.randint(3, 10)))
                for p in _prompts(rng, 8)]
    ref = _sequential_reference(_PARAMS, workload)
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        streams = [eng.submit(p, n) for p, n in workload]
        got = [s.result(timeout=120) for s in streams]
    finally:
        eng.stop()
    assert got == ref


def test_paged_attention_matches_dense_full_prefix():
    """The paged decode path agrees with the dense recompute: re-running
    the whole prefix through the full-length prefill executable and
    taking argmax at the last position yields the same greedy tokens."""
    from mxnet_tpu.models.transformer import get_transformer_lm_prefill

    sym = get_transformer_lm_prefill(V, LAYERS, HEADS, HID, seq_len=S,
                                     max_seq_len=S)
    pred = mx.Predictor(sym, dict(_PARAMS), {"data": (1, S)})
    buf = np.zeros((1, S), np.float32)

    def dense_decode(prompt, max_new):
        toks = list(prompt)
        gen = []
        for _ in range(max_new):
            buf[:] = 0
            buf[0, :len(toks)] = toks
            logits = pred.forward(data=buf)[0].asnumpy()
            nxt = int(np.argmax(logits[0, len(toks) - 1]))
            toks.append(nxt)
            gen.append(nxt)
        return gen

    rng = np.random.RandomState(11)
    workload = [(p, 6) for p in _prompts(rng, 4)]
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        got = [eng.generate(p, n) for p, n in workload]
    finally:
        eng.stop()
    assert got == [dense_decode(p, n) for p, n in workload]


# ---------------------------------------------------------------------------
# admit/retire churn, backpressure, preemption
# ---------------------------------------------------------------------------

def test_admit_retire_under_length_skew():
    """More requests than lanes with skewed budgets (1..12 tokens):
    short sequences retire and free lanes that later arrivals fill, all
    transcripts stay bit-identical, and the engine drains clean."""
    rng = np.random.RandomState(3)
    workload = [(p, 1 + (i * 5) % 12)
                for i, p in enumerate(_prompts(rng, 12))]
    ref = _sequential_reference(_PARAMS, workload)
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        streams = [eng.submit(p, n) for p, n in workload]
        got = [s.result(timeout=120) for s in streams]
        assert got == ref
        assert [len(g) for g in got] == [n for _, n in workload]
        assert eng.active_lanes() == 0 and eng.pending_depth() == 0
        assert eng.metrics.admitted.value >= len(workload)
        assert eng.metrics.retired.value == len(workload)
        assert eng.metrics.tokens.value == sum(n for _, n in workload)
    finally:
        eng.stop()


def test_submit_rejects_impossible_and_queue_full():
    eng = DecodeEngine(_PARAMS, **dict(SPEC, num_pages=8, max_pending=2,
                                       lane_buckets=(1,)))
    try:
        # 8 pages -> capacity 7 -> 28 tokens max; this can never fit
        with pytest.raises(MXNetError, match="never be admitted"):
            eng.submit(list(range(20)), 12)
        with pytest.raises(MXNetError, match="max_seq_len"):
            eng.submit([1, 2], S)
        # single lane + bounded queue: flood until QueueFullError
        accepted = [eng.submit([1, 2, 3], 8)]
        with pytest.raises(QueueFullError):
            for _ in range(8):
                accepted.append(eng.submit([1, 2, 3], 8))
        assert eng.metrics.rejected.value >= 1
        # backpressure, not deadlock: everything accepted still finishes
        for s in accepted:
            assert len(s.result(timeout=120)) == 8
    finally:
        eng.stop()


def test_pool_exhaustion_preempts_and_stays_bit_identical():
    """A pool too small for both long sequences at full length forces a
    mid-decode preemption (re-queue + re-prefill); greedy determinism
    makes the preempted stream's transcript identical anyway."""
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, 2, lo=6, hi=7)
    workload = [(p, 14) for p in prompts]
    ref = _sequential_reference(_PARAMS, workload)
    # each seq peaks at 5 pages; capacity 7 cannot hold 2x5
    eng = DecodeEngine(_PARAMS, **dict(SPEC, num_pages=8,
                                       lane_buckets=(1, 2)))
    try:
        streams = [eng.submit(p, n) for p, n in workload]
        got = [s.result(timeout=120) for s in streams]
        assert got == ref
        assert eng.metrics.preempted.value >= 1
        assert eng.pool.free_pages() == eng.pool.capacity  # all freed
    finally:
        eng.stop()


def test_engine_contains_injected_step_fault():
    """A fault fired inside the decode loop fails the in-flight streams
    with the injected error but never wedges the engine: the next
    submit decodes normally."""
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        ref = eng.generate([4, 8, 15], 5)
        with faults.inject("generation.engine.step:ioerr=1@#1"):
            stream = eng.submit([4, 8, 15], 5)
            with pytest.raises(IOError):
                stream.result(timeout=60)
        assert eng.generate([4, 8, 15], 5) == ref
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# one step in flight: the transcript is the sequential one, whatever happens
# between a step's dispatch and its read
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def greedy():
    """Sequential greedy decode by dense recompute of the whole prefix: no
    engine, no pool, nothing in flight.  ``greedy(prompt, n, eos=None)``."""
    from mxnet_tpu.models.transformer import get_transformer_lm_prefill

    sym = get_transformer_lm_prefill(V, LAYERS, HEADS, HID, seq_len=S,
                                     max_seq_len=S)
    pred = mx.Predictor(sym, dict(_PARAMS), {"data": (1, S)})
    buf = np.zeros((1, S), np.float32)

    def decode(prompt, max_new, eos=None):
        toks, gen = list(prompt), []
        while len(gen) < max_new and (not gen or gen[-1] != eos):
            buf[:] = 0
            buf[0, :len(toks)] = toks
            logits = pred.forward(data=buf)[0].asnumpy()
            gen.append(int(np.argmax(logits[0, len(toks) - 1])))
            toks.append(gen[-1])
        return gen

    return decode


def _flight_budget(greedy):
    """Lanes retire by budget on different steps: the lanes behind a
    retired one move down, and take their ids from where they were."""
    rng = np.random.RandomState(31)
    work = [(p, n) for p, n in zip(_prompts(rng, 4), (3, 9, 5, 7))]
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        streams = [eng.submit(p, n) for p, n in work]
        got = [s.result(timeout=120) for s in streams]
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert got == [greedy(p, n) for p, n in work]
    assert snap["tokens_total"] == sum(n for _, n in work)
    assert 0 < snap["steps_overlapped"] <= snap["steps"]
    assert snap["tokens_dropped"] == 0  # a budget is known a step early


def _flight_eos(greedy):
    """The EOS is seen when the step behind it is already dispatched: the
    lane rides that step, its token is dropped, and the pages it wrote to
    go to the request that waited for them only afterwards."""
    rng = np.random.RandomState(37)
    first, second = _prompts(rng, 2, lo=9, hi=10)
    free_run = greedy(first, 12)
    # an id whose first occurrence is past the first decode step
    at = next(i for i in range(2, 11) if free_run[i] not in free_run[:i])
    eos = free_run[at]
    # 5 pages: the first request alone fills them (9 + 12 tokens reserve
    # 6 > 5 would never fit; 9 + 10 -> 5), the second waits for its pages
    eng = DecodeEngine(_PARAMS, start=False, **dict(
        SPEC, num_pages=6, eos_id=eos, lane_buckets=(1,)))
    try:
        one = eng.submit(first, 10)
        two = eng.submit(second, 6)
        eng._admit()
        assert eng.active_lanes() == 1 and eng.pending_depth() == 1
        while not one.done:
            eng._decode_step()
        # the step dispatched before the EOS was read is still in flight
        assert eng._inflight is not None and eng.active_lanes() == 0
        assert eng.pool.free_pages() == eng.pool.capacity
        eng._admit()  # the second request takes the first one's pages
        while not two.done:
            eng._decode_step()
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert one.result() == free_run[:at + 1] == greedy(first, 10, eos)
    assert two.result() == greedy(second, 6, eos)
    assert snap["tokens_dropped"] == 1
    assert snap["tokens_total"] == at + 1 + len(two.result())


def _flight_preempt(greedy):
    """The pool runs out while a step is in flight: the victim's token in
    flight is dropped and computed again after its re-admission."""
    rng = np.random.RandomState(5)
    work = [(p, 14) for p in _prompts(rng, 2, lo=6, hi=7)]
    eng = DecodeEngine(_PARAMS, **dict(SPEC, num_pages=8,
                                       lane_buckets=(1, 2)))
    try:
        streams = [eng.submit(p, n) for p, n in work]
        got = [s.result(timeout=120) for s in streams]
        assert eng.metrics.preempted.value >= 1
        assert eng.pool.free_pages() == eng.pool.capacity
    finally:
        eng.stop()
    assert got == [greedy(p, n) for p, n in work]


def _flight_prefix(greedy):
    """A partial prefix hit joins running lanes: its lane is fed from the
    host (the walked suffix's last token) while its neighbours take their
    ids on the device."""
    rng = np.random.RandomState(41)
    shared = [int(t) for t in rng.randint(0, V, size=12)]
    tail = [int(t) for t in rng.randint(0, V, size=3)]
    runners = [(p, 14) for p in _prompts(rng, 2)]
    eng = DecodeEngine(_PARAMS, start=False, **dict(
        SPEC, prefix_cache_pages=SPEC["num_pages"], lane_buckets=(4,)))
    fed = []
    dispatch = eng._dispatch_lanes

    def recording(pred, data, positions, table, source=None):
        fed.append(None if source is None else list(source))
        return dispatch(pred, data, positions, table, source)

    eng._dispatch_lanes = recording
    try:
        seeded = eng.submit(shared + [1], 2)  # publishes the shared pages
        eng._admit()
        while not seeded.done or eng._inflight is not None:
            eng._decode_step()
        streams = [eng.submit(p, n) for p, n in runners]
        eng._admit()
        eng._decode_step()
        eng._decode_step()
        hit = eng.submit(shared + tail, 5)
        del fed[:]
        eng._admit()  # with a step in flight
        assert hit.cached_prefix_tokens > 0 and hit.prefill_tokens == 0
        eng._decode_step()
        # the two runners from their lanes of the step before, the hit
        # from the host
        assert fed[-1][:3] == [0.0, 1.0, -1.0], fed
        while not all(s.done for s in streams + [hit]):
            eng._decode_step()
    finally:
        eng.stop()
    assert [s.result() for s in streams] == [greedy(p, n)
                                             for p, n in runners]
    assert hit.result() == greedy(shared + tail, 5)


def _flight_admission(greedy):
    """A request is admitted (its prefill runs) while a step is in flight,
    into a larger lane count than the step in flight has."""
    rng = np.random.RandomState(43)
    (long, late) = _prompts(rng, 2)
    eng = DecodeEngine(_PARAMS, start=False, **SPEC)
    try:
        a = eng.submit(long, 12)
        eng._admit()
        eng._decode_step()
        eng._decode_step()
        assert eng._inflight is not None
        b = eng.submit(late, 6)
        eng._admit()
        assert eng.active_lanes() == 2 and eng._inflight is not None
        while not (a.done and b.done):
            eng._decode_step()
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert a.result() == greedy(long, 12) and b.result() == greedy(late, 6)
    assert snap["tokens_dropped"] == 0


def _flight_drain(greedy):
    """``stop(drain=True)`` with steps in flight: every stream finishes
    whole."""
    rng = np.random.RandomState(47)
    work = [(p, 8) for p in _prompts(rng, 5)]
    eng = DecodeEngine(_PARAMS, **SPEC)
    streams = [eng.submit(p, n) for p, n in work]
    streams[0]._q.get(timeout=60)  # decoding has begun
    eng.stop(drain=True)
    assert all(s.done for s in streams)
    assert [s.result() for s in streams] == [greedy(p, n) for p, n in work]
    assert eng._inflight is None


def _flight_fault(greedy):
    """The step fault fires with a step in flight: its streams fail, none
    hangs, the step in flight is forgotten and the engine serves on."""
    rng = np.random.RandomState(53)
    work = [(p, 8) for p in _prompts(rng, 3)]
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        with faults.inject("generation.engine.step:ioerr=1@#3"):
            streams = [eng.submit(p, n) for p, n in work]
            for s in streams:
                with pytest.raises(IOError):
                    s.result(timeout=60)
        assert eng.active_lanes() == 0
        assert [eng.generate(p, n) for p, n in work] == \
            [greedy(p, n) for p, n in work]
        assert eng.pool.free_pages() == eng.pool.capacity
    finally:
        eng.stop()


@pytest.mark.parametrize("case", [
    _flight_budget, _flight_eos, _flight_preempt, _flight_prefix,
    _flight_admission, _flight_drain, _flight_fault],
    ids=lambda f: f.__name__[len("_flight_"):])
def test_step_in_flight_keeps_the_sequential_transcript(case, greedy):
    case(greedy)


# ---------------------------------------------------------------------------
# recompile detector
# ---------------------------------------------------------------------------

def test_zero_recompiles_after_warmup():
    """Steady state never recompiles: a full mixed-length churn after
    warmup hits only warmed lane buckets and prefill buckets."""
    rng = np.random.RandomState(9)
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        streams = [eng.submit(p, int(rng.randint(2, 9)))
                   for p in _prompts(rng, 10)]
        for s in streams:
            s.result(timeout=120)
        assert eng.cold_decode_runs() == 0
        assert set(SPEC["lane_buckets"]) <= eng.warmed_lane_buckets
        assert eng.metrics.cold_steps.value == 0
    finally:
        eng.stop()


def test_cold_decode_detector_fires_without_warmup():
    """The detector actually detects: with warmup skipped, the first
    decode steps hit never-warmed buckets and are counted."""
    eng = DecodeEngine(_PARAMS, warmup=False, **SPEC)
    try:
        eng.generate([1, 2, 3], 3)
        assert eng.cold_decode_runs() >= 1
    finally:
        eng.stop()


def test_telemetry_counters_render():
    eng = DecodeEngine(_PARAMS, **SPEC)
    try:
        eng.generate([2, 4, 6], 4)
        text = telemetry.render_prometheus()
        for name in ("mxtpu_gen_tokens_total",
                     "mxtpu_gen_sequences_admitted_total",
                     "mxtpu_gen_kv_pages_live", "mxtpu_gen_kv_pages_peak",
                     "mxtpu_gen_ttft_ms", "mxtpu_gen_itl_ms"):
            assert name in text, name
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# serving integration: server + HTTP streaming + router
# ---------------------------------------------------------------------------

def _server(**kw):
    return serving.InferenceServer(
        _NET, dict(_PARAMS), {"data": (2, S), "softmax_label": (2, S)},
        generator_spec=dict(SPEC), **kw)


def test_server_http_generate_streams_ndjson():
    srv = _server()
    try:
        prompt = [3, 11, 7]
        ref = srv.submit_generate(prompt, 8).result(timeout=60)
        host, port = srv.serve_http()
        req = urllib.request.Request(
            "http://%s:%d/generate" % (host, port),
            data=json.dumps({"prompt": prompt,
                             "max_new_tokens": 8}).encode(),
            headers={"Content-Type": "application/json"})
        toks, done = [], None
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"] == "application/x-ndjson"
            for line in resp:
                obj = json.loads(line)
                if obj.get("done"):
                    done = obj
                    break
                toks.append(obj["token"])
        assert toks == ref
        assert done["n"] == len(ref) and done["ttft_ms"] > 0
    finally:
        srv.stop()


def test_http_generate_404_without_generator():
    srv = serving.InferenceServer(
        _NET, dict(_PARAMS), {"data": (2, S), "softmax_label": (2, S)})
    try:
        host, port = srv.serve_http()
        req = urllib.request.Request(
            "http://%s:%d/generate" % (host, port),
            data=json.dumps({"prompt": [1], "max_new_tokens": 2}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_router_generate_stream_parity():
    rng = np.random.RandomState(13)
    srvs = [_server() for _ in range(2)]
    router = serving.Router(srvs, seed=2)
    try:
        for p in _prompts(rng, 3):
            ref = _sequential_reference(_PARAMS, [(p, 7)])[0]
            assert list(router.generate(p, 7)) == ref
        snap = router.metrics.snapshot()
        assert snap["streams"].get("generate") == 3
    finally:
        router.close()
        for s in srvs:
            s.stop()


@pytest.mark.chaos
def test_router_resumes_stream_after_replica_kill():
    """Kill the replica actively decoding mid-stream: the Router
    re-submits prompt + tokens-so-far on a survivor and the client sees
    one uninterrupted, bit-identical token stream."""
    prompt = [5, 9, 2]
    ref = _sequential_reference(_PARAMS, [(prompt, 12)])[0]
    srvs = [_server() for _ in range(2)]
    router = serving.Router(srvs, seed=3)
    try:
        out, killed = [], False
        for tok in router.generate(prompt, 12):
            out.append(tok)
            if len(out) == 4 and not killed:
                killed = True
                victim = next(s for s in srvs
                              if s._generator.active_lanes() > 0)
                threading.Thread(target=victim.stop,
                                 kwargs={"drain": False}).start()
        assert out == ref
        assert router.metrics.snapshot()["stream_resumes"] >= 1
    finally:
        router.close()
        for s in srvs:
            s.stop()


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
