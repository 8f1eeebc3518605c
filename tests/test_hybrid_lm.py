"""The hybrid state-space / attention family (models/hybrid_lm.py) through
the generation engine, against the benchmark's plain reference
(perfbench/models/hybrid_lm.py: float32, the recurrence token by token):
a 5-layer pattern ``m m a m m`` at toy widths, with float32 weights and with
bfloat16 ones.

Tolerances.  float32: the program's chunked scan, gathers and fused norms
against the reference's plain order of the same float32 sums: 2e-4 on logits
of order 0.1-1.  bfloat16: the program rounds every activation to bfloat16
(8 bits of mantissa, about 0.4 % a rounding) through 5 layers where the
reference keeps float32: 0.06 on the same logits, ten times what the runs
read.  Transcripts (float32 only) are compared exactly against the
reference's greedy decoding where its top-two margin exceeds the float32
tolerance, which on these seeds it always does.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.generation import (DecodeEngine, KVPoolExhaustedError,
                                  PagedKVPool, StateNotRebuildableError)
from perfbench.builders import hybrid_lm as builder
from perfbench.models import hybrid_lm as ref

V, S = 96, 48
CFG = dict(vocab_size=V, hidden_size=32,
           layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
           num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
           shared_intermediate_size=64, mamba_n_heads=4, mamba_d_head=16,
           mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
           mamba_chunk_size=4, mamba_conv_bias=True, rms_norm_eps=1e-5,
           # at hidden 32 a N(0, 0.02) matrix passes almost nothing on:
           # with the published multipliers (12 / 0.22) every layer is a
           # rounding error beside the embedding and the model repeats its
           # last token.  These make the layers carry the logits.
           embedding_multiplier=1.0, residual_multiplier=5.0,
           attention_multiplier=0.125, logits_scaling=0.25,
           position_embedding_type="nope", tie_word_embeddings=True)
TOL = {"float32": 2e-4, "bfloat16": 0.06}
ENGINE = dict(max_seq_len=S, lane_buckets=(2, 4), page_size=4, num_pages=60,
              prefill_len_buckets=(8, 16, 32), prefill_batch_buckets=(1,))


def _cfg(dtype="float32"):
    return dict(CFG, weights_dtype=dtype)


def _weights(dtype="float32", seed=5):
    cfg = _cfg(dtype)
    w = ref.make_weights(cfg, seed)
    return cfg, w, {k: mx.nd.NDArray(v, mx.cpu()) for k, v in w.items()}


def _engine(cfg, params, **kw):
    spec = dict(ENGINE, family=builder.family_spec(cfg), ctx=mx.cpu())
    spec.update(kw)
    return DecodeEngine(params, **spec)


_SCORERS = {}


def _ref_logits(cfg, w, seq):
    """The reference's logits (len(seq), V) of one sequence."""
    key = cfg["weights_dtype"]
    if key not in _SCORERS:
        _SCORERS[key] = ref.make_scorer(cfg, len(cfg["layer_types"]), S)
    ids = np.zeros((1, S), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(_SCORERS[key](w, ids))[:len(seq)]


def _greedy(cfg, w, prompt, max_new, eos=None):
    """The reference's greedy transcript: a whole forward pass a token."""
    seq = list(prompt)
    for _ in range(max_new):
        row = _ref_logits(cfg, w, seq)[-1]
        top2 = np.sort(row)[-2:]
        assert top2[1] - top2[0] > TOL["float32"], "a tie: pick other seeds"
        seq.append(int(row.argmax()))
        if eos is not None and seq[-1] == eos:
            break
    return seq[len(prompt):]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, V, size=n)] for n in lengths]


# ---------------------------------------------------------------------------
# the graphs against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_full_sequence_graph_is_the_reference(dtype):
    cfg, w, params = _weights(dtype)
    net = builder.scoring_symbol(mx, cfg, {"max_seq_len": 16})
    pred = mx.Predictor(net, params, {"data": (2, 16),
                                      "softmax_label": (2, 16)}, ctx=mx.cpu())
    seqs = _prompts([16, 16], seed=1)
    pred.set_input("data", np.asarray(seqs, np.float32))
    pred._exec.forward(is_train=False)
    prob = pred.get_outputs()[0].asnumpy().reshape(2, 16, V)
    for b, seq in enumerate(seqs):
        lg = _ref_logits(cfg, w, seq)
        want = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
        np.testing.assert_allclose(np.log(prob[b]), want, atol=TOL[dtype],
                                   rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_logits_are_the_reference(dtype):
    """The engine driven by hand, one iteration at a time: the logits of
    every prefill (at the prompt's last token) and of every decode step it
    dispatches (every lane, every position) against the reference's whole
    forward pass over the finished transcript.  Prompts of 3 to 17 tokens:
    shorter than the convolution, across chunk and bucket edges."""
    cfg, w, params = _weights(dtype)
    eng = _engine(cfg, params, start=False)
    got = {}  # (sid, position) -> logits row
    streams = []
    for prompt in _prompts([3, 8, 17, 5]):
        st = eng.submit(prompt, 9)
        streams.append(st)
        eng._admit()  # one prompt a prefill (batch bucket 1)
        L = eng._prefill_bucket_for(len(prompt))
        out = eng._prefill[L]._preds[1].get_outputs()[0].asnumpy()
        got[(st.sid, len(prompt) - 1)] = out[0, len(prompt) - 1]
    while eng._active or eng._inflight is not None:
        eng._decode_step()
        flight = eng._inflight
        if flight is not None:
            logits = flight.pred.get_outputs()[0].asnumpy()
            for i, (seq, pos) in enumerate(flight.lanes):
                got[(seq.sid, pos)] = logits[i]
    snap = eng.snapshot()
    eng.stop()
    assert snap["state_slots"]["live"] == 0 and \
        snap["state_slots"]["peak"] == 4
    # on the host platform the state step is the pass over the whole plane
    # (an engine without state planes has no such key: test_paged_kernel.py)
    assert snap["ssm_step"] == "xla" and snap["paged_attention"] == "xla"
    assert snap["ssm_scan"] == "xla"  # likewise the prefill's scan
    for st in streams:
        assert st.done and st.exception() is None and len(st.tokens) == 9
        seq = st.prompt + st.tokens
        want = _ref_logits(cfg, w, seq)
        rows = [p for (sid, p) in got if sid == st.sid]
        # every position from the prompt's last to the last one fed
        assert sorted(rows) == list(range(len(st.prompt) - 1, len(seq) - 1))
        for p in rows:
            np.testing.assert_allclose(got[(st.sid, p)], want[p],
                                       atol=TOL[dtype], rtol=0)
        if dtype == "float32":
            assert st.tokens == _greedy(cfg, w, st.prompt, 9)


def test_transcripts_vary():
    """The toy model is no constant: the cases below can tell a sequence's
    state from its neighbour's."""
    cfg, w, _ = _weights()
    outs = [_greedy(cfg, w, p, 9) for p in _prompts([3, 8, 17, 5])]
    assert len({tuple(o) for o in outs}) == 4
    assert all(len(set(o)) > 2 for o in outs)


# ---------------------------------------------------------------------------
# slots: reuse, a neighbour's late retirement, what is refused
# ---------------------------------------------------------------------------

def test_a_reused_state_slot_gives_a_fresh_engines_logits():
    """Two lanes, five requests: every later request takes a slot (and
    pages) an earlier one left its state in.  Each transcript is the
    reference's, which a fresh engine gives too."""
    cfg, w, params = _weights()
    prompts = _prompts([6, 11, 4, 9, 13], seed=3)
    with _engine(cfg, params, lane_buckets=(2,)) as eng:
        outs = [s.result(120) for s in [eng.submit(p, 8) for p in prompts]]
        assert eng.snapshot()["state_slots"]["peak"] == 2
    with _engine(cfg, params, lane_buckets=(2,)) as fresh:
        alone = fresh.generate(prompts[-1], 8)
    assert outs[-1] == alone
    assert outs == [_greedy(cfg, w, p, 8) for p in prompts]


def test_a_lane_retiring_by_eos_one_step_late_leaves_its_neighbours_alone():
    """An EOS is seen a step late (one step is in flight): the lane rides
    one step more, which writes its slot once more after the slot may have
    been given away.  Neighbours and successors keep the reference's
    transcripts."""
    cfg, w, params = _weights()
    prompts = _prompts([7, 12, 5, 10, 6, 9], seed=4)
    eos = _greedy(cfg, w, prompts[0], 10)[3]
    want = [_greedy(cfg, w, p, 10, eos=eos) for p in prompts]
    assert any(len(o) < 10 for o in want)  # some lane does stop early
    with _engine(cfg, params, lane_buckets=(2,), eos_id=eos) as eng:
        outs = [s.result(120) for s in [eng.submit(p, 10) for p in prompts]]
        snap = eng.snapshot()
    assert outs == want
    assert snap["tokens_dropped"] >= 1  # the late step did run


@pytest.mark.parametrize("what,kw", [
    ("draft=", dict(draft={"params": {}, "num_layers": 1, "num_heads": 2,
                           "hidden": 32, "k": 2})),
    ("prefix_cache_pages=4", dict(prefix_cache_pages=4))])
def test_what_needs_a_windowed_graph_is_refused_by_name(what, kw):
    cfg, _, params = _weights()
    with pytest.raises(MXNetError, match="no windowed") as err:
        _engine(cfg, params, start=False, warmup=False, **kw)
    assert what in str(err.value) and "hybrid_lm" in str(err.value)


def test_a_preempted_sequence_is_prefilled_again_or_fails_by_name():
    """A pool too small for both lanes' growth preempts the younger one.
    Its transcript fits a prefill bucket: it is re-admitted by a prefill over
    prompt + generated and ends with the reference's transcript.  With
    buckets too short for that it fails with StateNotRebuildableError, and
    the other lane finishes."""
    cfg, w, params = _weights()
    prompts = _prompts([8, 8], seed=6)
    small = dict(lane_buckets=(2,), num_pages=8)  # 7 pages of 4 tokens
    with _engine(cfg, params, **small) as eng:
        outs = [s.result(120) for s in [eng.submit(p, 9) for p in prompts]]
        assert eng.metrics.preempted.value >= 1
    assert outs == [_greedy(cfg, w, p, 9) for p in prompts]
    with _engine(cfg, params, prefill_len_buckets=(8,), **small) as eng:
        streams = [eng.submit(p, 9) for p in prompts]
        ends = []
        for st, prompt in zip(streams, prompts):
            try:
                ends.append(st.result(120) == _greedy(cfg, w, prompt, 9))
            except StateNotRebuildableError as exc:
                assert "prefill bucket is 8" in str(exc)
                ends.append("lost")
        # the younger lane, whichever it was
        assert sorted(ends, key=str) == [True, "lost"]
        assert eng.snapshot()["state_slots"]["live"] == 0


# ---------------------------------------------------------------------------
# the manager, the seam, the spans
# ---------------------------------------------------------------------------

def _pool(**kw):
    planes = [("l0_ssm_state", "slot", (2, 3, 4), "float32"),
              ("l0_conv_tail", "slot", (3, 5), "float32"),
              ("l1_k_pool", "paged", (2, 4), "float32"),
              ("l1_v_pool", "paged", (2, 4), "float32")]
    return PagedKVPool(9, 4, planes=planes, num_slots=3, ctx=mx.cpu(), **kw)


def test_pool_takes_and_gives_back_a_slot_with_the_pages():
    pool = _pool()
    assert [p.shape for p in pool.planes()] == [
        (3, 2, 3, 4), (3, 3, 5), (9, 4, 2, 4), (9, 4, 2, 4)]
    assert pool.slot_bytes == (24 + 15) * 4 and pool.free_slots() == 2
    pool.alloc("a", 5)
    pool.alloc("b", 3)
    assert {pool.state_slot("a"), pool.state_slot("b")} == {1, 2}
    with pytest.raises(KVPoolExhaustedError, match="state slots"):
        pool.alloc("c", 1)
    assert pool.free_pages() == 8 - 3  # the refused alloc took nothing
    snap = pool.snapshot()["state_slots"]
    assert snap == {"capacity": 2, "live": 2, "peak": 2,
                    "slot_bytes": 156, "bytes": 312}
    slot = pool.state_slot("a")
    pool.free("a")
    pool.free("a")  # idempotent
    assert pool.free_slots() == 1
    pool.alloc("c", 1)
    assert pool.state_slot("c") == slot
    text = pool.render_prometheus()
    for name in ("mxtpu_gen_state_slots_live 2", "mxtpu_gen_state_slots_peak "
                 "2", "mxtpu_gen_state_bytes 312"):
        assert name in text


def test_pool_writes_a_prefills_states_into_its_slots():
    pool = _pool()
    pool.alloc("a", 6)
    pool.alloc("b", 2)
    r = np.random.RandomState(0)
    slabs = [r.randn(2, 2, 3, 4), r.randn(2, 3, 5),
             r.randn(2, 8, 2, 4), r.randn(2, 8, 2, 4)]
    slabs = [s.astype(np.float32) for s in slabs]
    pool.write_prefill(["b", "a"], slabs, [2, 6])
    state, tail = (p.asnumpy() for p in pool.planes()[:2])
    for row, seq in enumerate(["b", "a"]):
        np.testing.assert_array_equal(state[pool.state_slot(seq)],
                                      slabs[0][row])
        np.testing.assert_array_equal(tail[pool.state_slot(seq)],
                                      slabs[1][row])
    k, _ = pool.read_page(0, pool._tables["a"][1])
    np.testing.assert_array_equal(k[:2], slabs[2][1, 4:6])
    # a copy-on-write split copies pages, never a slot plane
    pool.copy_page(pool._tables["a"][0], pool._tables["b"][0])
    np.testing.assert_array_equal(pool.planes()[0].asnumpy(), state)


def test_a_pool_without_slot_planes_has_no_slot_accounting():
    pool = PagedKVPool(5, 4, 2, 2, 4, ctx=mx.cpu())
    assert pool.num_slots == 0 and pool.free_slots() is None
    assert "state_slots" not in pool.snapshot()
    assert "mxtpu_gen_state" not in pool.render_prometheus()
    assert pool.plane_names() == ["layer0_k_pool", "layer0_v_pool",
                                  "layer1_k_pool", "layer1_v_pool"]


def test_the_seam_serves_both_families_from_a_spec():
    """``spec()`` rebuilds the engine: the default family under the width
    keywords it always had, any other under ``family``."""
    cfg, _, params = _weights()
    eng = _engine(cfg, params, start=False, warmup=False)
    spec = eng.spec()
    assert "vocab_size" not in spec and \
        spec["family"]["family"] == "hybrid_lm" and \
        spec["family"]["layer_types"] == CFG["layer_types"]
    again = DecodeEngine(params, start=False, warmup=False, ctx=mx.cpu(),
                         **spec)
    assert again.spec() == spec
    assert [s.name for s in again.pool.specs] == \
        [s.name for s in eng.pool.specs]
    from mxnet_tpu.models import lm_family

    fam = lm_family(dict(family="transformer_lm", vocab_size=64,
                         num_layers=2, num_heads=2, hidden=32))
    assert fam.catchup_symbol(32, 4) is not None and \
        [p[1] for p in fam.planes()] == ["paged"] * 4
    with pytest.raises(ValueError, match="family must be one of"):
        lm_family({"family": "nope"})
    with pytest.raises(MXNetError, match="vocab_size or family"):
        DecodeEngine({}, start=False, warmup=False)


def test_the_platform_counts_the_state_in_a_models_footprint():
    """``ModelSpec.kv_footprint`` reads the family's planes: pages and
    state slots, as the engine's pool holds them."""
    from mxnet_tpu.platform.spec import ModelSpec

    cfg, _, params = _weights("bfloat16")
    gen = dict(ENGINE, family=builder.family_spec(cfg))
    spec = ModelSpec("g4h", "/nowhere/g4h", 0, {"data": (1, 16)},
                     slo="generate", generator_spec=gen)
    eng = _engine(cfg, params, start=False, warmup=False)
    assert spec.kv_footprint() == eng.pool.device_bytes()
    assert eng.pool.device_bytes() > 5 * eng.pool.slot_bytes > 0


def test_the_default_familys_footprint_is_its_pages():
    """The same resolver and the same formula for a spec with no
    ``family``: K and V pages over the layers, nothing else."""
    from mxnet_tpu.platform.spec import ModelSpec

    gen = dict(vocab_size=64, num_layers=2, num_heads=2, hidden=32,
               num_pages=8, page_size=4, lane_buckets=(2,), dtype="bfloat16")
    spec = ModelSpec("gpt", "/nowhere/gpt", 0, {"data": (1, 16)},
                     slo="generate", generator_spec=gen)
    assert spec.kv_footprint() == 2 * 2 * 8 * 4 * 32 * 2


def test_bfloat16_weights_are_bound_as_they_come():
    """No float32 copy at bind: every executor of the engine and the
    server's scoring replica hold the very arrays they were given."""
    cfg, _, params = _weights("bfloat16")
    srv = mx.serving.InferenceServer(
        builder.scoring_symbol(mx, cfg, {"max_seq_len": 16}), params,
        {"data": (1, 16), "softmax_label": (1, 16)}, ctx=mx.cpu(),
        buckets=[1], warmup=False, start=False,
        generator_spec=dict(ENGINE, family=builder.family_spec(cfg)))
    try:
        eng = srv.generator
        execs = [p._exec for p in eng._decode.values()] + \
            [p._exec for bp in eng._prefill.values()
             for p in bp._preds.values()] + \
            [p._exec for p in srv._replicas[0]._preds.values()]
        for ex in execs:
            for name, arr in params.items():
                bound = ex.arg_dict[name]
                assert str(bound.dtype) == "bfloat16"
                assert bound._data is arr._data
        assert str(eng.pool.planes()[0].dtype) == "float32"   # the state
        assert str(eng.pool.planes()[1].dtype) == "bfloat16"  # its tail
        assert str(eng._decode[2]._exec.arg_dict["data"].dtype) == "float32"
    finally:
        srv.stop(drain=False)


def test_spans_and_counters_of_the_state(tmp_path):
    import glob
    import os

    import jax

    cfg, _, params = _weights()
    eng = _engine(cfg, params, lane_buckets=(2,), start=False)
    jax.profiler.start_trace(str(tmp_path))
    for p in _prompts([5, 9], seed=8):
        eng.submit(p, 4)
    eng._admit()
    while eng._active or eng._inflight is not None:
        eng._decode_step()
    jax.profiler.stop_trace()
    text = telemetry.render_prometheus()
    eng.stop()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    events = [e for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    steps = [dict(e.stats) for e in events if e.name == "gen:step"]
    assert steps and all(
        int(s["state_bytes"]) == int(s["lanes"]) * eng.pool.slot_bytes
        for s in steps if "lanes" in s)
    prefills = [dict(e.stats) for e in events if e.name == "gen:prefill"]
    assert sorted(str(p["state_slot"]) for p in prefills) == ["1", "2"]
    # prompts of 5 and 9 in buckets of 8 and 16, chunks of 4: what the
    # buckets hold, and the chunks with a token in them
    assert sorted((int(p["scan_chunks"]), int(p["scan_chunks_live"]))
                  for p in prefills) == [(2, 2), (4, 3)]
    for name in ("mxtpu_gen_state_slots_live", "mxtpu_gen_state_slots_peak",
                 "mxtpu_gen_state_bytes"):
        assert name in text


# ---------------------------------------------------------------------------
# the graphs of the published configuration, pinned
# ---------------------------------------------------------------------------

# sha256 of tojson() of granite-4.0-h-micro's three graphs as PR 33 emitted
# them (written against the parent of PR 34, which gave the block its other
# kinds: a convolution mixer, rotary / QK-norm, routed experts).  The JSON is
# the compile-cache fingerprint and fixes every named scope of the device
# trace; a PR that changes a graph on purpose replaces its digest here and
# says so in CHANGES.md.  (PR 44: the prefill graph returns an attention
# layer's K and V slabs with a token as ONE row of kv_heads * head_dim, two
# Reshape nodes a layer, as the planes hold it now; the lane graph takes its
# planes as unshaped Variables and did not move.)
_DIGESTS = {
    "score":
        "709b3c0a98e8c33203bc78e65d41a9b59a1ce2be1e6aaaf73892eea570ba1e1c",
    "prefill":
        "54f0e77983ea61f083ef09cc2f8dd42de543e8c11ca12335324cbe9016b8a449",
    "decode":
        "b3e417a9826d79e72bb7c6f948657bb79a0b16aafb91c6e8ef23ac60d664fc5e",
}


@pytest.mark.parametrize("which", sorted(_DIGESTS))
def test_graph_json_is_pinned(which):
    import hashlib
    import json
    import os

    from mxnet_tpu.models import HybridLM
    from mxnet_tpu.name import NameManager

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        model = HybridLM(**builder.family_spec(json.load(f)))
    with NameManager():
        net = {"score": lambda: mx.models.get_hybrid_lm(model, 1024),
               "prefill": lambda: model.prefill_symbol(512, 1024),
               "decode": lambda: model.decode_symbol(1024, 16)}[which]()
    assert hashlib.sha256(net.tojson().encode()).hexdigest() == \
        _DIGESTS[which]
