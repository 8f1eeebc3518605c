"""Generative serving tests — continuous batching + paged KV-cache.

The PR-12 acceptance criteria as assertions: continuous-batched greedy
decode is bit-identical to sequential decode, paged attention matches
the dense full-prefix recompute, admit/retire churns correctly under
length skew, pool exhaustion backpressures (and preempts) without
deadlocking, and the decode loop never recompiles after warmup.  The
server, HTTP and router tests are in ``test_generation_serving.py``; AOT
bundles, speculative decoding and the prefix cache in
``test_generation_spec.py``.

All CPU-only, on the tiny transformer LM of ``generation_lm.py``.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.generation import (DecodeEngine, KVPoolExhaustedError,
                                  PagedKVPool)
from mxnet_tpu.serving import QueueFullError

from generation_lm import (HEADS, HID, LAYERS, S, SPEC, V, _PARAMS,
                           _prompts, _sequential_reference)

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
# the engine's four defaults: the argument, else MXNET_GEN_*, else a constant
# ---------------------------------------------------------------------------

_DRAFT = {"params": _PARAMS, "num_layers": LAYERS, "num_heads": HEADS,
          "hidden": HID}
# what spec() says -> (the variable, its value and what that gives, the
# keyword and the argument that pins it, the pinned value, the constant)
DEFAULTS = {
    "page_size": ("MXNET_GEN_PAGE_SIZE", "4", 4, "page_size", 8, 8, 16),
    "num_pages": ("MXNET_GEN_NUM_PAGES", "64", 64, "num_pages", 32, 32, 128),
    "lane_buckets": ("MXNET_GEN_MAX_LANES", "4", [1, 2, 4],
                     "lane_buckets", (1, 2), [1, 2], [1, 2, 4, 8]),
    # a caller's dict that still carries the hint no one reads is accepted
    "draft_k": ("MXNET_GEN_DRAFT_K", "3", 3,
                "draft", dict(_DRAFT, k=2, acceptance_hint=0.8), 2, 4),
}


@pytest.mark.parametrize("source", ["argument", "variable", "constant"])
@pytest.mark.parametrize("what", sorted(DEFAULTS))
def test_engine_defaults(what, source, monkeypatch):
    variable, raw, from_variable, keyword, argument, pinned, constant = \
        DEFAULTS[what]
    kw = dict(vocab_size=V, num_layers=LAYERS, num_heads=HEADS, hidden=HID,
              max_seq_len=S, prefill_len_buckets=(8,), draft=dict(_DRAFT),
              warmup=False, start=False)
    monkeypatch.delenv(variable, raising=False)
    if source != "constant":
        monkeypatch.setenv(variable, raw)  # an argument beats it
    if source == "argument":
        kw[keyword] = argument
    eng = DecodeEngine(_PARAMS, **kw)
    try:
        spec = eng.spec()
        assert sorted(spec["draft"]) == [
            "hidden", "k", "num_heads", "num_layers", "params"]
        got = spec["draft"]["k"] if what == "draft_k" else spec[what]
        assert got == {"argument": pinned, "variable": from_variable,
                       "constant": constant}[source]
    finally:
        eng.stop()
