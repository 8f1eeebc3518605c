"""The short-convolution / attention family with routed experts (the
``conv`` mixer, rotary attention with QK-norm and the ``experts`` feed-forward
of models/hybrid_lm.py) through the generation engine, against the
benchmark's plain reference (perfbench/models/lfm2_moe_lm.py: float32, every
expert over every row, the convolution as shifted products, no cache): an
8-layer pattern ``c c a c | c c a c`` at toy widths, two dense layers and six
expert layers.

Tolerances.  float32 weights: the program's grouped products, gathers and
fused norms against the reference's plain order of the same float32 sums:
2e-4 on logits of order 1.  bfloat16 weights: the program rounds every
activation to bfloat16 (8 bits of mantissa, about 0.4 % a rounding) through 8
layers of width 32 where the reference keeps float32: 0.45 on the same
logits, over twice the largest the runs read on seeds 6 to 8 (0.19; the
median row reads 0.05) and under two thirds of the least the float8 control
reads on a stream (0.97).  (The bfloat16 cases take seed 7: under seed 5 one
row of sixteen is ill-conditioned, 0.52 in the program and 0.17 in the
reference itself computed in bfloat16.)  The bfloat16 case picks ALL of its
4 experts
a row, so no near-tie of the router's scores can pick another expert than
the reference (a discontinuity, not a rounding: tests/test_moe_ops.py counts
how often it happens, the benchmark's limits file what it costs).  The
float8 control (the reference with its products in float8) fails both
tolerances; that is asserted.  Transcripts (float32 only) are compared
exactly against the reference's greedy decoding where its top-two margin
exceeds the float32 tolerance, which on these seeds it always does.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.generation import DecodeEngine
from perfbench.builders import lfm2_moe_lm as builder
from perfbench.models import lfm2_moe_lm as ref

V, S = 96, 48
CFG = dict(vocab_size=V, hidden_size=32,
           layer_types=["conv", "conv", "full_attention", "conv"] * 2,
           num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=64, moe_intermediate_size=16, conv_L_cache=3,
           conv_bias=False, num_dense_layers=2, num_experts=8,
           num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=1, use_expert_bias=True, rope_theta=1000000,
           norm_eps=1e-5)
TOL = {"float32": 2e-4, "bfloat16": 0.45}
ENGINE = dict(max_seq_len=S, lane_buckets=(2, 4), page_size=4, num_pages=60,
              prefill_len_buckets=(8, 16, 32), prefill_batch_buckets=(1,))
LAYERS = len(CFG["layer_types"])
EXPERT_LAYERS = LAYERS - CFG["num_dense_layers"]


def _cfg(dtype="float32"):
    if dtype == "bfloat16":  # every expert picked: no pick can flip
        return dict(CFG, weights_dtype=dtype, num_experts=4,
                    num_experts_per_tok=4)
    return dict(CFG, weights_dtype=dtype)


def _weights(dtype="float32", seed=None):
    cfg = _cfg(dtype)
    seed = {"float32": 5, "bfloat16": 7}[dtype] if seed is None else seed
    w = ref.make_weights(cfg, seed)
    return cfg, w, {k: mx.nd.NDArray(v, mx.cpu()) for k, v in w.items()}


def _engine(cfg, params, **kw):
    spec = dict(ENGINE, family=builder.family_spec(cfg), ctx=mx.cpu())
    spec.update(kw)
    return DecodeEngine(params, **spec)


_SCORERS = {}


def _ref_logits(cfg, w, seq, prec="f32"):
    """The reference's logits (len(seq), V) of one sequence."""
    key = (cfg["weights_dtype"], prec)
    if key not in _SCORERS:
        _SCORERS[key] = ref.make_scorer(cfg, LAYERS, S, prec)
    ids = np.zeros((1, S), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(_SCORERS[key](w, ids))[:len(seq)]


def _greedy(cfg, w, prompt, max_new):
    """The reference's greedy transcript: a whole forward pass a token."""
    seq = list(prompt)
    for _ in range(max_new):
        row = _ref_logits(cfg, w, seq)[-1]
        top2 = np.sort(row)[-2:]
        assert top2[1] - top2[0] > TOL["float32"], "a tie: pick other seeds"
        seq.append(int(row.argmax()))
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
    forward pass over the finished transcript.  Prompts of 2 to 17 tokens:
    shorter than the convolution, across bucket edges; decode positions
    rotate by ``positions``, prefill positions by 0..L-1.  The float8
    control misses the same tolerance on the same transcripts."""
    cfg, w, params = _weights(dtype)
    eng = _engine(cfg, params, start=False)
    got = {}  # (sid, position) -> logits row
    streams = []
    for prompt in _prompts([2, 8, 17, 5]):
        st = eng.submit(prompt, 9)
        streams.append(st)
        eng._admit()  # one prompt a prefill (batch bucket 1)
        L = eng._prefill_bucket_for(len(prompt))
        out = eng._prefill[L]._preds[1].get_outputs()[0].asnumpy()
        got[(st.sid, len(prompt) - 1)] = out[0, len(prompt) - 1]
    loads = []
    while eng._active or eng._inflight is not None:
        eng._decode_step()
        flight = eng._inflight
        if flight is not None:
            logits = flight.pred.get_outputs()[0].asnumpy()
            loads.append((len(flight.lanes),
                          np.asarray(flight.extras["expert_load"])))
            for i, (seq, pos) in enumerate(flight.lanes):
                got[(seq.sid, pos)] = logits[i]
    snap = eng.snapshot()
    eng.stop()
    assert snap["state_slots"]["live"] == 0 and \
        snap["state_slots"]["peak"] == 4
    # six convolution layers' tails a lane, and no recurrent state plane
    assert snap["state_slots"]["slot_bytes"] == 6 * 2 * 32 * (
        4 if dtype == "float32" else 2)
    assert "ssm_step" not in snap and snap["paged_attention"] == "xla"
    assert snap["moe_experts"] == "ragged-dense"  # the host's formulation
    # picked from the operands' facts: the leaves' dtype and the two widths
    # (ops/moe.py ``experts_formulation``)
    assert eng._moe_experts == (np.dtype(dtype), 32, 16)
    # a padded lane of the bucket picks nothing: live lanes x k a layer
    k = cfg["num_experts_per_tok"]
    for lanes, load in loads:
        assert load.shape == (EXPERT_LAYERS, cfg["num_experts"])
        assert (load.sum(axis=1) == lanes * k).all()
    control_misses = 0
    for st in streams:
        assert st.done and st.exception() is None and len(st.tokens) == 9
        seq = st.prompt + st.tokens
        want = _ref_logits(cfg, w, seq)
        low = _ref_logits(cfg, w, seq, "fp8")
        rows = [p for (sid, p) in got if sid == st.sid]
        # every position from the prompt's last to the last one fed
        assert sorted(rows) == list(range(len(st.prompt) - 1, len(seq) - 1))
        for p in rows:
            np.testing.assert_allclose(got[(st.sid, p)], want[p],
                                       atol=TOL[dtype], rtol=0)
        control_misses += np.abs(low[rows] - want[rows]).max() > TOL[dtype]
        if dtype == "float32":
            assert st.tokens == _greedy(cfg, w, st.prompt, 9)
    assert control_misses == len(streams)


@pytest.mark.parametrize("fault,moves", [
    ("experts-fp8", True), ("experts-rotated", True),
    ("experts-zeroed", True), ("layer5-zeroed", True),
    ("layer5-rotated", True), ("layer1-zeroed", False)])
def test_an_expert_only_control_faults_the_expert_layers_it_names(fault,
                                                                  moves):
    """The controls the benchmark's limits are read with
    (``make_scorer(..., "bf16+experts-rotated")``): the rest of the model at
    the first precision, the fault in every expert layer or in the one
    named.  Layer 1 is dense: a fault there changes nothing."""
    cfg, w, _ = _weights()
    seq = _prompts([24], seed=3)[0]
    want = _ref_logits(cfg, w, seq)
    got = _ref_logits(cfg, w, seq, "f32+" + fault)
    if moves:
        assert np.abs(got - want).max() > 50 * TOL["float32"]
    else:
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="unknown control"):
        ref.make_scorer(cfg, LAYERS, S, "f32+experts-dropped")


def test_a_layers_experts_share_their_common_part():
    """Seeded experts of one layer are ``EXPERTS_OWN`` their own draw over
    a draw common to the layer (perfbench/models/lfm2_moe_lm.py says why):
    two experts' matrices correlate by 1 - EXPERTS_OWN^2, two layers' not at
    all, and every matrix keeps the fan-in width."""
    cfg, w, _ = _weights()
    a = np.asarray(w["layer2_experts_w13"], np.float64)
    b = np.asarray(w["layer3_experts_w13"], np.float64)
    corr = np.corrcoef(a.reshape(a.shape[0], -1))
    off = corr[~np.eye(len(corr), dtype=bool)]
    assert abs(off.mean() - (1 - ref.EXPERTS_OWN ** 2)) < 0.01
    assert abs(np.corrcoef(a[0].ravel(), b[0].ravel())[0, 1]) < 0.1
    np.testing.assert_allclose(a.std(), ref.GAIN / np.sqrt(a.shape[1]),
                               rtol=0.1)
    own = a - a.mean(0)  # what is left of an expert without the common part
    np.testing.assert_allclose(own.std() / a.std(), ref.EXPERTS_OWN,
                               rtol=0.15)


def test_transcripts_vary():
    """The toy model is no constant: the cases below can tell a sequence's
    tails and pages from its neighbour's."""
    cfg, w, _ = _weights()
    outs = [_greedy(cfg, w, p, 9) for p in _prompts([2, 8, 17, 5])]
    assert len({tuple(o) for o in outs}) == 4
    assert all(len(set(o)) > 3 for o in outs)


# ---------------------------------------------------------------------------
# tails and slots
# ---------------------------------------------------------------------------

def test_a_right_padded_prompts_tail_is_the_unpadded_prompts():
    """One prompt of 8 tokens through a prefill bucket of 8 (no padding) and
    through one of 32 (24 padded positions, none of which routes or reaches a
    tail): the same tails in the lane's slot (to float32 rounding: the two
    buckets' products are tiled differently) and the same transcript."""
    cfg, w, params = _weights()
    prompt = _prompts([8], seed=2)[0]
    tails, outs = [], []
    for buckets in ((8,), (32,)):
        eng = _engine(cfg, params, start=False, lane_buckets=(2,),
                      prefill_len_buckets=buckets)
        st = eng.submit(prompt, 6)
        eng._admit()
        slot = eng.pool.state_slot(st.sid)
        tails.append([p.asnumpy()[slot] for p, spec
                      in zip(eng.pool.planes(), eng.pool.specs)
                      if spec.kind == "slot"])
        while eng._active or eng._inflight is not None:
            eng._decode_step()
        outs.append(list(st.tokens))
        eng.stop()
    assert len(tails[0]) == 6
    for a, b in zip(*tails):
        assert np.abs(a).max() > 0.01
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    assert outs[0] == outs[1] == _greedy(cfg, w, prompt, 6)


def test_a_lane_admitted_into_a_slot_another_has_just_left():
    """Two lanes, five requests: every later request takes a tail slot (and
    pages) an earlier one left its tails in.  Each transcript is the
    reference's, which a fresh engine gives too."""
    cfg, w, params = _weights()
    prompts = _prompts([6, 11, 4, 9, 13], seed=4)
    with _engine(cfg, params, lane_buckets=(2,)) as eng:
        outs = [s.result(120) for s in [eng.submit(p, 8) for p in prompts]]
        snap = eng.snapshot()
    assert snap["state_slots"]["peak"] == 2
    with _engine(cfg, params, lane_buckets=(2,)) as fresh:
        alone = fresh.generate(prompts[-1], 8)
    assert outs[-1] == alone
    assert outs == [_greedy(cfg, w, p, 8) for p in prompts]
    # what the lanes picked, since the start
    load = np.asarray(snap["experts"]["load"])
    assert load.shape == (EXPERT_LAYERS, 8) and \
        (load.sum(axis=1) == load.sum(axis=1)[0]).all()
    assert 1 <= snap["experts"]["hit_per_step_layer"] <= 4
    assert snap["experts"]["max_over_mean"] >= 1.0


@pytest.mark.parametrize("what,kw", [
    ("draft=", dict(draft={"params": {}, "num_layers": 1, "num_heads": 2,
                           "hidden": 32, "k": 2})),
    ("prefix_cache_pages=4", dict(prefix_cache_pages=4))])
def test_what_needs_a_windowed_graph_is_refused_by_name(what, kw):
    cfg, _, params = _weights()
    with pytest.raises(MXNetError, match="no windowed") as err:
        _engine(cfg, params, start=False, warmup=False, **kw)
    assert what in str(err.value)


# ---------------------------------------------------------------------------
# the seam, the footprint, the spans
# ---------------------------------------------------------------------------

def test_the_family_says_which_planes_and_outputs_it_carries():
    from mxnet_tpu.models import HybridLM, lm_family

    spec = builder.family_spec(_cfg())
    fam = HybridLM(**spec)
    kinds = [(kind, shape) for _, kind, shape, _ in fam.planes()]
    assert kinds == ([("slot", (2, 32))] * 2 + [("paged", (2 * 8,))] * 2
                     + [("slot", (2, 32))]) * 2
    assert fam.lane_extras == ("expert_load",)
    assert fam.expert_layers == tuple(range(2, 8))
    assert fam.expert_pairs(10) == 10 * 2 * 6
    assert fam.expert_bytes() == 3 * 32 * 16 * 4
    assert lm_family(fam.spec()).spec() == fam.spec()
    outs = fam.decode_symbol(S, 4).list_outputs()
    assert outs[-2:] == ["next_ids_output", "expert_load_output"]
    # a description with no expert layer returns nothing after the ids
    dense = HybridLM(**dict(spec, num_experts=0))
    assert dense.lane_extras == () and dense.expert_layers == ()
    assert dense.decode_symbol(S, 4).list_outputs()[-1] == "next_ids_output"
    with pytest.raises(ValueError, match="experts: 9 a token of 8"):
        HybridLM(**dict(spec, experts_per_token=9))
    # a padded lane is known by its scratch state slot; a description whose
    # layers carry no slot plane knows it by its scratch page instead
    # (tests/test_latent_lm.py runs one) and takes no state_slot
    paged = HybridLM(**dict(spec, layer_types=["attention"] * 3))
    assert not paged.has_slots and fam.has_slots
    assert "state_slot" not in paged.decode_symbol(S, 4).list_arguments()
    assert "state_slot" in fam.decode_symbol(S, 4).list_arguments()


@pytest.mark.parametrize("key,value,says", [
    ("num_shared_experts", 1, "no shared expert"),
    ("scoring_func", "softmax", "scores by sigmoid"),
    ("use_expert_bias", False, "selection bias"),
    ("rope_scaling", {"type": "yarn"}, "no scaling"),
    ("conv_bias", True, "no bias"),
    ("tie_word_embeddings", False, "tied head")])
def test_the_builder_refuses_by_name_what_the_program_cannot_build(key, value,
                                                                   says):
    with pytest.raises(ValueError, match=says):
        builder.family_spec(dict(_cfg(), **{key: value}))


def test_the_platform_counts_the_tails_in_a_models_footprint():
    from mxnet_tpu.platform.spec import ModelSpec

    cfg, _, params = _weights("bfloat16")
    gen = dict(ENGINE, family=builder.family_spec(cfg))
    spec = ModelSpec("lfm2", "/nowhere/lfm2", 0, {"data": (1, 16)},
                     slo="generate", generator_spec=gen)
    eng = _engine(cfg, params, start=False, warmup=False)
    assert spec.kv_footprint() == eng.pool.device_bytes()
    # 4 lanes + scratch, six layers' tails of 2 x 32 bfloat16
    assert eng.pool.slot_bytes == 6 * 2 * 32 * 2
    assert eng.pool.device_bytes() > 5 * eng.pool.slot_bytes > 0


def test_spans_and_counters_of_the_experts(tmp_path):
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
    steps = [dict(e.stats) for e in events
             if e.name in ("gen:step", "gen:drain")]
    read = [s for s in steps if "expert_pairs" in s]
    assert read
    for s in read:  # of the step it reads: 1 or 2 live lanes
        assert int(s["expert_pairs"]) in (2 * 6, 2 * 2 * 6)
        assert 6 <= int(s["experts_hit"]) <= int(s["expert_pairs"])
        assert int(s["expert_bytes"]) == int(s["experts_hit"]) * 3 * 32 * 16 \
            * 4
    prefills = [dict(e.stats) for e in events if e.name == "gen:prefill"]
    assert sorted(int(p["expert_pairs"]) for p in prefills) == \
        [5 * 2 * 6, 9 * 2 * 6]
    for name in ("mxtpu_gen_expert_picks", "mxtpu_gen_experts_hit"):
        assert name in text
