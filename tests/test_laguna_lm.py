"""The family whose layers attend either to every earlier token or to a
sliding window (the ``window`` kind, per-kind query heads and rotations, the
scaled and partial rotation and ``attn_gate`` of models/hybrid_lm.py, beside
a shared expert and routed experts of which this chip holds a share) through
the generation engine, against the benchmark's plain reference
(perfbench/models/laguna_lm.py: float32, the whole sequence under an explicit
``(t, u)`` mask, every held expert over every row; no ring, no pages): two
periods of full, sliding, sliding, sliding at toy widths, window 8, 4 query
heads in a full layer and 6 in a sliding one over 2 K/V heads, experts 2-5 of
8 held here.

The reference has no ring and no cache: the engine's decode path
(``_contrib_PagedAttention`` over the full layers' pages and
``_contrib_WindowAttentionStep`` over the sliding layers' ring slots, a token
at ``t % 8``) is held to the reference's whole forward pass position by
position, for prompts shorter than the window, equal to it and longer, under
a bucket's padding, and through a decode that wraps the ring more than twice.

Tolerances.  float32 weights: the program's blocks, gathers and fused norms
against the reference's plain order of the same float32 sums: 3e-4 on logits
of order 1.  bfloat16 weights: the program rounds every activation to
bfloat16 through 8 layers of width 32 where the reference keeps float32: 0.2
on the same logits.  The bfloat16 case picks ALL 8 experts a row (4 of them
held), so no near-tie of the router's scores can pick another expert than
the reference (tests/test_lfm2_lm.py has the reason).  The float8 control
fails both tolerances; that is asserted.
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.generation import DecodeEngine
from mxnet_tpu.ops import paged
from perfbench.builders import laguna_lm as builder
from perfbench.models import laguna_lm as ref

V, S, WINDOW, LAYERS = 96, 48, 8, 8
FULL, SLIDING = "full_attention", "sliding_attention"
YARN = {"rope_theta": 100.0, "rope_type": "yarn", "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.2,
        "partial_rotary_factor": 0.5}
CFG = dict(model_type="laguna", vocab_size=V, hidden_size=32,
           intermediate_size=64, num_hidden_layers=LAYERS,
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           attention_bias=False, rms_norm_eps=1e-6, num_experts=4,
           num_experts_published=8, first_expert=2, num_experts_per_tok=2,
           moe_intermediate_size=16, shared_expert_intermediate_size=16,
           tie_word_embeddings=False, gating=True, sliding_window=WINDOW,
           rope_parameters={
               FULL: YARN, SLIDING: {"rope_type": "default",
                                     "rope_theta": 50.0,
                                     "partial_rotary_factor": 1}},
           layer_types=[FULL, SLIDING, SLIDING, SLIDING] * 2,
           mlp_layer_types=["dense"] + ["sparse"] * 7,
           num_attention_heads_per_layer=[4, 6, 6, 6] * 2,
           moe_apply_router_weight_on_input=False,
           moe_routed_scaling_factor=2.5)
TOL = {"float32": 3e-4, "bfloat16": 0.2}
ENGINE = dict(max_seq_len=S, lane_buckets=(2, 4), page_size=4, num_pages=60,
              prefill_len_buckets=(8, 16, 32), prefill_batch_buckets=(1,))
RING_ROW = 2 * 8        # kv_heads * head_dim
SLIDING_LAYERS = 6


def _cfg(dtype="float32"):
    if dtype == "bfloat16":  # every expert picked: no pick can flip
        return dict(CFG, weights_dtype=dtype, num_experts_per_tok=8)
    return dict(CFG, weights_dtype=dtype)


def _weights(dtype="float32", seed=None, **more):
    cfg = dict(_cfg(dtype), **more)
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
    key = (cfg["weights_dtype"], cfg["num_experts_per_tok"],
           cfg["gating"], prec)
    if key not in _SCORERS:
        _SCORERS[key] = ref.make_scorer(cfg, LAYERS, S, prec)
    ids = np.zeros((1, S), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(_SCORERS[key](w, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, V, size=n)] for n in lengths]


# ---------------------------------------------------------------------------
# the graphs against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,gate", [("float32", True), ("bfloat16", True),
                                        ("float32", False)])
def test_full_sequence_graph_is_the_reference(dtype, gate, monkeypatch):
    """The scoring graph (the full layers' attention in query blocks, the
    sliding layers' banded) against the reference, gate on and off."""
    monkeypatch.setattr(paged, "_QUERY_BLOCK", 8)
    cfg, w, params = _weights(dtype, gating=gate)
    if not gate:
        w = {k: v for k, v in w.items() if "gate_weight" not in k}
        params = {k: v for k, v in params.items() if "gate_weight" not in k}
    net = builder.scoring_symbol(mx, cfg, {"max_seq_len": 32})
    assert sorted(set(net.list_arguments()) - set(params)) == \
        ["data", "softmax_label"]
    assert set(params) <= set(net.list_arguments())
    pred = mx.Predictor(net, params, {"data": (2, 32),
                                      "softmax_label": (2, 32)}, ctx=mx.cpu())
    seqs = _prompts([32, 32], seed=1)
    pred.set_input("data", np.asarray(seqs, np.float32))
    pred._exec.forward(is_train=False)
    prob = pred.get_outputs()[0].asnumpy().reshape(2, 32, V)
    prec = "f32" if gate else "f32+attn-nogate"
    full = dict(w, **{k: v for k, v in _weights(dtype)[1].items()
                      if "gate_weight" in k})
    for b, seq in enumerate(seqs):
        lg = _ref_logits(cfg, full, seq, prec)
        want = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
        np.testing.assert_allclose(np.log(prob[b]), want, atol=TOL[dtype],
                                   rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_logits_are_the_reference(dtype, monkeypatch):
    """The engine driven by hand: the logits of every prefill (at the
    prompt's last token) and of every decode step (every lane, every
    position) against the reference's whole forward pass over the finished
    transcript.  Prompts shorter than the window (3), equal to it (8), longer
    (17, in a bucket of 32: 15 rows of padding past its last real token) and
    one a bucket's whole width (16); 20 new tokens each wrap the ring of 8
    more than twice.  The float8 control misses the same tolerance on the
    same transcripts."""
    monkeypatch.setattr(paged, "_QUERY_BLOCK", 8)
    cfg, w, params = _weights(dtype)
    eng = _engine(cfg, params, start=False)
    got = {}  # (sid, position) -> logits row
    streams = []
    for prompt in _prompts([3, 8, 17, 16]):
        st = eng.submit(prompt, 20)
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
    # pages for the 2 full layers, a ring slot a lane for the 6 sliding ones
    names = eng.pool.plane_names()
    assert names == [
        "layer%d_%s_%s" % (i, kv, "pool" if i % 4 == 0 else "ring")
        for i in range(LAYERS) for kv in "kv"]
    item = 4 if dtype == "float32" else 2
    assert eng._ring_bytes == SLIDING_LAYERS * 2 * WINDOW * RING_ROW * item
    assert eng.pool.slot_bytes == eng._ring_bytes
    assert eng.pool.num_slots == 5 and snap["state_slots"]["capacity"] == 4
    assert snap["window_attention"] == "xla"
    assert snap["sequence_attention"] == "xla"   # the host platform
    assert snap["paged_attention"] == "xla"
    assert snap["moe_experts"] == "ragged-dense"  # the host's formulation
    control_misses = 0
    for st in streams:
        assert st.done and st.exception() is None and len(st.tokens) == 20
        seq = st.prompt + st.tokens
        want = _ref_logits(cfg, w, seq)
        low = _ref_logits(cfg, w, seq, "fp8")
        rows = [p for (sid, p) in got if sid == st.sid]
        assert sorted(rows) == list(range(len(st.prompt) - 1, len(seq) - 1))
        for p in rows:
            np.testing.assert_allclose(got[(st.sid, p)], want[p],
                                       atol=TOL[dtype], rtol=0)
        control_misses += np.abs(low[rows] - want[rows]).max() > TOL[dtype]
        if dtype == "float32":  # greedy: the reference's own picks
            assert st.tokens == [int(r.argmax()) for r in
                                 want[len(st.prompt) - 1:-1]]
    assert control_misses == len(streams)


@pytest.mark.parametrize("control,moves", [
    ("f32+attn-nowindow", True), ("f32+attn-plainangles", True),
    ("f32+attn-nogate", True), ("f32+shared-zeroed", True),
    ("f32+experts-zeroed", True), ("f32+layer4-zeroed", True),
    ("f32+experts-rotated", True), ("f32+experts-fp8", True),
    ("fp8", True), ("f32", False)])
def test_a_control_faults_what_it_names(control, moves):
    cfg, w, _ = _weights()
    seq = _prompts([40], seed=3)[0]
    want = _ref_logits(cfg, w, seq)
    got = _ref_logits(cfg, w, seq, control)
    assert bool(np.abs(got - want).max() > 1e-3) is moves
    if control == "f32+attn-nowindow":  # the window's first 8 rows see all
        np.testing.assert_allclose(got[:WINDOW], want[:WINDOW], atol=1e-5)
    with pytest.raises(ValueError, match="unknown control"):
        ref.control("bf16+attn-zeroed")


def test_the_heads48_control_zeroes_a_sliding_layers_last_heads():
    """At the row's head counts (64 in a sliding layer): the control takes
    the last 16 query heads' output away, in the sliding layers alone."""
    cfg = dict(_cfg(), num_attention_heads_per_layer=[4, 64, 64, 64] * 2,
               num_hidden_layers=2, head_dim=2, num_key_value_heads=2,
               rope_parameters={FULL: dict(YARN), SLIDING: CFG[
                   "rope_parameters"][SLIDING]})
    w = ref.make_weights(cfg, 3, 2)
    ids = np.asarray([_prompts([S], seed=4)[0]], np.int32)
    want = np.asarray(ref.make_scorer(cfg, 2, S)(w, ids))
    got = np.asarray(ref.make_scorer(cfg, 2, S, "f32+attn-heads48")(w, ids))
    assert np.abs(got - want).max() > 1e-3
    # the same as zeroing those heads' columns of W_o in the sliding layer
    cut = dict(w)
    cut["layer1_o_weight"] = np.asarray(w["layer1_o_weight"]).copy()
    cut["layer1_o_weight"][:, 48 * 2:] = 0
    np.testing.assert_allclose(
        got, np.asarray(ref.make_scorer(cfg, 2, S)(cut, ids)), atol=1e-5)


# ---------------------------------------------------------------------------
# the rotation's table
# ---------------------------------------------------------------------------

ROW = {"factor": 64.0, "original": 4096, "beta_fast": 64.0, "beta_slow": 1.0,
       "attention_factor": 1.4158883083359672}


def test_the_scaled_table_is_the_written_formula_at_the_rows_numbers():
    """Laguna-XS.2's full layers: 64 rotated features, theta 500000, factor
    64 over 4096 positions, beta 64 / 1: the ramp runs from m = 5 to m = 16;
    f_0 is the plain frequency, f_31 the plain one over 64.  The program's
    table (ops/moe.py) and the reference's are made by separate code."""
    from mxnet_tpu.ops.moe import rotary_table

    f, lo, hi = ref.yarn_table(500000.0, 64, ROW)
    assert (lo, hi) == (5, 16)
    e = 500000.0 ** (-2.0 * np.arange(32) / 64)
    assert f[0] == 1.0 and f[5] == e[5]
    np.testing.assert_allclose(f[31], e[31] / 64, rtol=1e-12)
    np.testing.assert_allclose(f[31], 500000.0 ** (-62 / 64) / 64, rtol=1e-12)
    np.testing.assert_allclose(f[16:], e[16:] / 64, rtol=1e-12)
    m = 10  # on the ramp: 5 / 11 of the way
    np.testing.assert_allclose(
        f[m], e[m] / 64 * (5 / 11) + e[m] * (6 / 11), rtol=1e-12)
    c = 64 * math.log(4096 / (2 * math.pi * 64)) / (2 * math.log(500000.0))
    assert math.floor(c) == 5
    table, factor = rotary_table(
        500000.0, 64, dict(factor=64.0, original_max=4096, beta_fast=64.0,
                           beta_slow=1.0,
                           attention_factor=1.4158883083359672))
    np.testing.assert_allclose(table, f.astype(np.float32), rtol=1e-6)
    assert factor == 1.4158883083359672
    plain, one = rotary_table(500000.0, 64)
    np.testing.assert_allclose(plain, e.astype(np.float32), rtol=1e-6)
    assert one == 1.0


def test_the_rotary_op_turns_part_of_a_head_by_the_scaled_table():
    """The op against the reference's rotation: half of a head by YaRN's
    table with the factor on cosine and sine, the other half passes."""
    x = np.random.default_rng(0).standard_normal((1, 40, 3, 16)).astype("f")
    pos = np.arange(40, dtype=np.float32)
    got = mx.nd._contrib_Rotary(
        mx.nd.array(x), mx.nd.array(pos), theta=100.0, rotary_dim=8,
        factor=4.0, original_max=16, beta_fast=4.0, beta_slow=1.0,
        attention_factor=1.2).asnumpy()
    yarn = dict(factor=4.0, original=16, beta_fast=4.0, beta_slow=1.0)
    f, lo, hi = ref.yarn_table(100.0, 8, yarn)
    assert (lo, hi) == (0, 1) and f[1] == 100.0 ** (-2 / 8) / 4
    want = np.asarray(ref.rotate(x[0], f, 1.2))
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # a rotated q . k carries the factor's square
    plain = mx.nd._contrib_Rotary(mx.nd.array(x), mx.nd.array(pos),
                                  theta=100.0, rotary_dim=8, factor=4.0,
                                  original_max=16, beta_fast=4.0,
                                  beta_slow=1.0,
                                  attention_factor=1.0).asnumpy()
    with pytest.raises(ValueError, match="every one of"):
        mx.nd._contrib_Rotary(mx.nd.array(x), mx.nd.array(pos), theta=100.0,
                              factor=4.0)
    np.testing.assert_allclose(got[..., :8], 1.2 * plain[..., :8], atol=1e-5)


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that all 8 shares of a
    layer's experts give (here 4 shares of 2 of 8), plus the shared expert
    counted once, are the uncut layer of the reference; and the program's
    ops told a share give that share's part."""
    from mxnet_tpu.ops import moe

    whole = dict(_cfg(), num_experts=8, first_expert=0)
    w = ref.make_weights(whole, 11)
    z = ref.sizes(whole)
    g = np.random.default_rng(2).standard_normal((24, 32)).astype(np.float32)
    p = {k[len("layer2_"):]: v for k, v in w.items()
         if k.startswith("layer2_")}
    want = ref.shared(g, p, z, "f32") + ref.routed(g, p, z, "f32")
    total = np.asarray(ref.shared(g, p, z, "f32"))
    for first in (0, 2, 4, 6):
        cut = dict(whole, num_experts=2, first_expert=first)
        ws = ref.make_weights(cut, 11)
        # a share's leaves are the whole layer's slice
        for leaf in ("experts_w13", "experts_w2"):
            np.testing.assert_array_equal(
                np.asarray(ws["layer2_" + leaf]),
                np.asarray(w["layer2_" + leaf])[first:first + 2])
        ps = {k[len("layer2_"):]: v for k, v in ws.items()
              if k.startswith("layer2_")}
        part = np.asarray(ref.routed(g, ps, ref.sizes(cut), "f32"))
        assert np.abs(part).max() > 0
        total = total + part
        ids, weights, _ = moe.route(g, ps["router_weight"], None, top_k=2,
                                    scale=2.5)
        mine = moe.routed_experts(g, ids, weights, ps["experts_w13"],
                                  ps["experts_w2"], first_expert=first)
        np.testing.assert_allclose(np.asarray(mine), part, atol=2e-5, rtol=0)
    np.testing.assert_allclose(total, np.asarray(want), atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# the seam, the refusals, the spans
# ---------------------------------------------------------------------------

def test_the_family_says_which_planes_and_outputs_it_carries():
    from mxnet_tpu.models import HybridLM, lm_family

    spec = builder.family_spec(_cfg())
    fam = HybridLM(**spec)
    assert spec["layer_types"] == ["attention", "window", "window",
                                   "window"] * 2
    assert (spec["num_heads"], spec["window_heads"]) == (4, 6)
    assert spec["rotary_dim"] == 4 and spec["window_rotary_theta"] == 50.0
    assert spec["rotary_scaling"] == dict(
        factor=4.0, original_max=16, beta_fast=4.0, beta_slow=1.0,
        attention_factor=1.2)
    want = []
    for i in range(LAYERS):
        if i % 4 == 0:
            want += [("layer%d_%s_pool" % (i, kv), "paged", (RING_ROW,),
                      "float32") for kv in "kv"]
        else:
            want += [("layer%d_%s_ring" % (i, kv), "slot",
                      (WINDOW, RING_ROW), "float32") for kv in "kv"]
    assert fam.planes() == want
    assert fam.has_slots and fam.lane_extras == ("expert_load",)
    assert fam.expert_layers == tuple(range(1, LAYERS))
    assert fam.ring_bytes() == SLIDING_LAYERS * 2 * WINDOW * RING_ROW * 4
    assert lm_family(fam.spec()).spec() == fam.spec()
    lane = fam.decode_symbol(S, 4)
    args = lane.list_arguments()
    assert "state_slot" in args and "lm_head_weight" in args
    assert sum(a.endswith("_gate_weight") for a in args) == LAYERS
    assert not any(a.endswith("router_bias") for a in args)
    # 4 heads' worth of q and of gates in a full layer, 6 in a sliding one
    # (the engine test binds the graphs to leaves of these shapes)
    shapes = ref.param_shapes(_cfg())
    assert shapes["layer0_q_weight"] == (4 * 8, 32)
    assert shapes["layer1_q_weight"] == (6 * 8, 32)
    assert shapes["layer0_gate_weight"] == (4, 32)
    assert shapes["layer1_gate_weight"] == (6, 32)
    with pytest.raises(ValueError, match="window: a window of 0"):
        HybridLM(**dict(spec, window=0))
    with pytest.raises(ValueError, match="window: .* 5 query heads"):
        HybridLM(**dict(spec, window_heads=5))
    with pytest.raises(ValueError, match="rotary_scaling: keys"):
        HybridLM(**dict(spec, rotary_scaling={"factor": 4.0}))
    with pytest.raises(ValueError, match="at least one attention"):
        HybridLM(**dict(spec, layer_types=["window"] * 4))


def test_a_description_that_turns_nothing_on_has_none_of_it():
    """The per-kind keys are off unless a description turns them on: the
    attention kind's graph names no gate, no partial or scaled rotation."""
    from mxnet_tpu.models import HybridLM

    fam = HybridLM(vocab_size=V, hidden=32, layer_types=["attention"] * 2,
                   num_heads=4, kv_heads=2, head_dim=8, intermediate=64,
                   rotary_theta=100.0)
    text = fam.decode_symbol(S, 4).tojson()
    for word in ("gate_weight", "attention_factor", "original_max",
                 "beta_fast", "WindowAttention", "state_slot"):
        assert word not in text
    assert not fam.has_slots and fam.ring_bytes() == 0


@pytest.mark.parametrize("key,value,says", [
    ("attention_bias", True, "no bias"),
    ("hidden_act", "gelu", "SiLU-gated"),
    ("moe_apply_router_weight_on_input", True, "weight their OUTPUT"),
    ("moe_router_logit_softcapping", 30.0, "no soft cap"),
    ("n_group", 8, "no expert groups"),
    ("gating", "elementwise", "ONE a head"),
    ("gating_types", ["per_head", "elementwise"], "ONE a head"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("layer_types", [FULL, "linear_attention"] * 4, "layer kinds"),
    ("layer_types", [SLIDING] * 8, "no full layer"),
    ("mlp_layer_types", ["sparse", "dense"] * 4, "dense layers lead"),
    ("num_attention_heads_per_layer", [4, 6, 6, 8] * 2, "one count of"),
    ("rope_parameters", {FULL: dict(YARN, rope_type="llama3"),
                         SLIDING: {"rope_theta": 50.0}}, "rope_type"),
    ("rope_parameters", {FULL: dict(YARN, truncate=False),
                         SLIDING: {"rope_theta": 50.0}}, "truncate"),
    ("rope_parameters", {FULL: YARN, SLIDING: {
        "rope_theta": 50.0, "partial_rotary_factor": 0.5}}, "whole head")])
def test_the_builder_refuses_by_name_what_the_program_cannot_build(key, value,
                                                                   says):
    with pytest.raises(ValueError, match=says):
        builder.family_spec(dict(_cfg(), **{key: value}))


@pytest.mark.parametrize("what,kw", [
    ("draft=", {"draft": {"params": {}, "k": 2}}),
    ("prefix_cache_pages > 0", {"prefix_cache_pages": 8})])
def test_what_needs_a_windowed_graph_is_refused_by_name(what, kw):
    """A ring cannot be rewound past a rejected token, nor rebuilt from
    cached pages: prefix cache and speculation are refused by name."""
    cfg, _, params = _weights()
    with pytest.raises(MXNetError, match="no windowed.*ring"):
        _engine(cfg, params, start=False, warmup=False, **kw)


def test_a_nan_past_a_lanes_live_ring_entries_reaches_nothing():
    """A lane 3 tokens deep has read 3 of its ring's 8 entries; NaN in the
    other 5 (what a freed slot's last owner may have left) changes no
    logit, through prefill and five decode steps."""
    cfg, w, params = _weights()
    outs = []
    for poison in (False, True):
        eng = _engine(cfg, params, start=False)
        if poison:
            for name, plane in zip(eng.pool.plane_names(),
                                   eng.pool.planes()):
                if name.endswith("_ring"):
                    plane._data = plane._data.at[:].set(np.nan)
        st = eng.submit(_prompts([3], seed=5)[0], 5)
        eng._admit()
        rows = []
        while eng._active or eng._inflight is not None:
            eng._decode_step()
            if eng._inflight is not None:
                rows.append(
                    eng._inflight.pred.get_outputs()[0].asnumpy()[0])
        eng.stop()
        assert st.done and len(st.tokens) == 5
        outs.append((st.tokens, np.stack(rows)))
    assert outs[0][0] == outs[1][0]
    assert np.isfinite(outs[1][1]).all()
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_spans_and_counters_of_the_rings(tmp_path):
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
    sent = [s for s in steps if "window_bytes" in s]
    assert sent
    lane = SLIDING_LAYERS * 2 * WINDOW * RING_ROW * 4
    assert int(sent[0]["window_bytes"]) == 2 * lane
    for s in sent:
        assert int(s["window_bytes"]) in (lane, 2 * lane)
        assert "state_bytes" not in s and "pages" in s
    assert any("expert_pairs" in s for s in steps)
    assert "mxtpu_gen_window_bytes" in text
    pools = [dict(e.stats) for e in events if e.name == "start:pool"]
    assert not pools  # built before the trace began


def test_start_pool_counts_the_rings():
    from mxnet_tpu import profiler
    from mxnet_tpu.generation.kv_pool import PagedKVPool
    from mxnet_tpu.models import HybridLM

    fam = HybridLM(**builder.family_spec(_cfg()))
    seen = []
    real = profiler.Frame.set

    def spy(self, **kw):
        seen.append(kw)
        return real(self, **kw)

    profiler.Frame.set = spy
    try:
        pool = PagedKVPool(12, 4, planes=fam.planes(), num_slots=5,
                           ctx=mx.cpu())
    finally:
        profiler.Frame.set = real
    rings = [kw["ring_bytes"] for kw in seen if "ring_bytes" in kw]
    assert rings == [5 * fam.ring_bytes()]
    paged_bytes = 12 * 4 * 2 * 2 * RING_ROW * 4   # 2 full layers, K and V
    assert pool.device_bytes() == paged_bytes + rings[0]
