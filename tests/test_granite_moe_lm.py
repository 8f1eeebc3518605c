"""The hybrid state-space / attention family WITH routed experts (Mamba-2
layers and routed experts in one program: ``router_score="softmax"``, no
selection bias, a shared MLP beside the experts of which this chip holds a
share, from layer 0; models/hybrid_lm.py) through the generation engine,
against the benchmark's plain reference
(perfbench/models/granite_moe_hybrid_lm.py: float32, the recurrence token by
token, every held expert over every row, the softmax over the picked logits
written out): the pattern ``m m a m m`` at toy widths, experts 2-5 of 8
held here.

Tolerances.  float32 weights: the program's chunked scan, gathers, fused
norms and grouped products against the reference's plain order of the same
float32 sums: 2e-4 on logits of order 1.  bfloat16 weights: the program
rounds every activation to bfloat16 through 5 layers where the reference
keeps float32: 0.15 on the same logits, twice the largest the runs read on
seeds 6 to 8 (0.051-0.070; the reference itself computed in bfloat16 reads up
to 0.092) and under half of the least the float8 control reads on a stream
(0.35).  The bfloat16 case picks ALL
8 experts a row (4 of them held), so no near-tie of the router's logits can
pick another expert than the reference (tests/test_lfm2_lm.py has the
reason); its softmax is then over all 8.  The fault tests (the router scored
by sigmoid, the shared MLP dropped) miss the float32 tolerance; that is
asserted.
"""
import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.generation import DecodeEngine
from mxnet_tpu.ops import moe
from perfbench.builders import granite_moe_hybrid_lm as builder
from perfbench.models import granite_moe_hybrid_lm as ref

V, S = 96, 48
TYPES = ["mamba", "mamba", "attention", "mamba", "mamba"]
CFG = dict(vocab_size=V, hidden_size=32, layer_types=TYPES,
           num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=16, shared_intermediate_size=32,
           num_local_experts=4, num_local_experts_published=8,
           first_expert=2, num_experts_per_tok=3, mamba_n_heads=4,
           mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
           mamba_n_groups=1, mamba_chunk_size=4, mamba_conv_bias=True,
           mamba_proj_bias=False, attention_bias=False, rms_norm_eps=1e-5,
           hidden_act="silu", normalization_function="rmsnorm",
           # as tests/test_hybrid_lm.py: at hidden 32 the published
           # multipliers (12 / 0.22 / 16) leave every layer a rounding error
           # beside the embedding; these make the layers carry the logits
           embedding_multiplier=1.0, residual_multiplier=1.0,
           attention_multiplier=0.125, logits_scaling=0.25,
           position_embedding_type="nope", tie_word_embeddings=True)
TOL = {"float32": 2e-4, "bfloat16": 0.15}
ENGINE = dict(max_seq_len=S, lane_buckets=(2, 4), page_size=4, num_pages=60,
              prefill_len_buckets=(8, 16, 32), prefill_batch_buckets=(1,))
LAYERS, EXPERTS = 5, 8


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
    key = (cfg["weights_dtype"], cfg["num_experts_per_tok"], prec)
    if key not in _SCORERS:
        _SCORERS[key] = ref.make_scorer(cfg, LAYERS, S, prec)
    ids = np.zeros((1, S), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(_SCORERS[key](w, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, V, size=n)] for n in lengths]


# ---------------------------------------------------------------------------
# the scoring rule
# ---------------------------------------------------------------------------

def _written_out(g, w, k):
    """The issue's rule in numpy: the k largest logits, the softmax over
    them."""
    z = g.astype(np.float64) @ w.astype(np.float64).T
    ids = np.argsort(-z, axis=-1, kind="stable")[:, :k]
    zp = np.take_along_axis(z, ids, axis=-1)
    e = np.exp(zp - zp.max(-1, keepdims=True))
    return z, ids, e / e.sum(-1, keepdims=True)


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_route_by_softmax_is_the_written_out_rule(scale):
    rng = np.random.default_rng(3)
    g = rng.standard_normal((24, 32)).astype(np.float32)
    w = (0.3 * rng.standard_normal((EXPERTS, 32))).astype(np.float32)
    live = (np.arange(24) % 5 != 2)
    ids, weights, load = moe.route(g, w, None, live.astype(np.float32),
                                   top_k=3, scale=scale, score="softmax")
    ids, weights, load = (np.asarray(a) for a in (ids, weights, load))
    z, want_ids, want_w = _written_out(g, w, 3)
    np.testing.assert_array_equal(ids[live], want_ids[live])
    np.testing.assert_allclose(weights[live], scale * want_w[live],
                               atol=1e-6, rtol=0)
    # the weights sum to one by construction, whatever ``normalize`` says
    np.testing.assert_allclose(weights[live].sum(-1), scale, atol=1e-6)
    again = moe.route(g, w, None, live.astype(np.float32), top_k=3,
                      scale=scale, score="softmax", normalize=False)
    np.testing.assert_array_equal(np.asarray(again[1]), weights)
    # equal to the softmax over ALL experts renormalised over the picks
    full = np.exp(z - z.max(-1, keepdims=True))
    full = full / full.sum(-1, keepdims=True)
    picked = np.take_along_axis(full, want_ids, axis=-1)
    np.testing.assert_allclose(
        weights[live], scale * (picked / picked.sum(-1, keepdims=True))[live],
        atol=1e-6, rtol=0)
    # a row that is not live picks expert E with weight 0, and loads nothing
    assert (ids[~live] == EXPERTS).all() and not weights[~live].any()
    assert load.sum() == 3 * live.sum()
    np.testing.assert_array_equal(
        load, np.bincount(want_ids[live].reshape(-1), minlength=EXPERTS))


def test_route_by_softmax_takes_no_bias_and_sigmoid_is_what_it_was():
    rng = np.random.default_rng(4)
    g = rng.standard_normal((6, 32)).astype(np.float32)
    w = (0.3 * rng.standard_normal((EXPERTS, 32))).astype(np.float32)
    bias = np.zeros((EXPERTS,), np.float32)
    with pytest.raises(ValueError, match="no selection bias"):
        moe.route(g, w, bias, top_k=2, score="softmax")
    with pytest.raises(ValueError, match="score is one of"):
        moe.route(g, w, None, top_k=2, score="tanh")
    # the default is the sigmoid rule, bit for bit what the keyword names
    for a, b in zip(moe.route(g, w, bias, top_k=2, scale=2.5),
                    moe.route(g, w, bias, top_k=2, scale=2.5,
                              score="sigmoid")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = 1.0 / (1.0 + np.exp(-(g.astype(np.float64) @ w.T)))
    top = np.sort(s, axis=-1)[:, ::-1][:, :2]
    np.testing.assert_allclose(
        np.asarray(moe.route(g, w, None, top_k=2)[1]),
        top / (top.sum(-1, keepdims=True) + moe.ROUTER_EPS), atol=1e-6)
    # and the two rules differ
    assert np.abs(np.asarray(moe.route(g, w, None, top_k=2)[1]) - np.asarray(
        moe.route(g, w, None, top_k=2, score="softmax")[1])).max() > 1e-2


def test_the_op_writes_the_score_only_where_it_departs():
    from mxnet_tpu.models import HybridLM

    spec = builder.family_spec(_cfg())
    fam = HybridLM(**spec)
    def routers(family):
        return [n["attr"] for n in json.loads(
            family.decode_symbol(S, 4).tojson())["nodes"]
            if n["op"] == "_contrib_MoERouter"]

    assert [(a["score"], a["use_bias"]) for a in routers(fam)] == \
        [("softmax", "False")] * LAYERS
    plain = routers(HybridLM(**dict(spec, router_score="sigmoid")))
    assert len(plain) == LAYERS and not any("score" in a for a in plain)
    with pytest.raises(ValueError, match="router_score"):
        HybridLM(**dict(spec, router_bias=True))
    with pytest.raises(ValueError, match="router_score"):
        HybridLM(**dict(spec, router_score="tanh"))


# ---------------------------------------------------------------------------
# the graphs against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_full_sequence_graph_is_the_reference(dtype):
    cfg, w, params = _weights(dtype)
    net = builder.scoring_symbol(mx, cfg, {"max_seq_len": 16})
    assert sorted(set(net.list_arguments()) - set(params)) == \
        ["data", "softmax_label"]
    assert set(params) <= set(net.list_arguments())
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


def _serve(cfg, params, lengths=(2, 8, 17, 5), new=9, **family):
    """The engine driven by hand: {(sid, position): logits row} of every
    prefill (at the prompt's last token) and every decode step (every lane,
    every position), the streams, each step's (lanes, expert load), the
    snapshot and the pool.  ``family`` overrides the builder's
    description."""
    eng = _engine(cfg, params, start=False,
                  family=dict(builder.family_spec(cfg), **family))
    got, streams, loads = {}, [], []
    for prompt in _prompts(lengths):
        st = eng.submit(prompt, new)
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
            loads.append((len(flight.lanes),
                          np.asarray(flight.extras["expert_load"])))
            for i, (seq, pos) in enumerate(flight.lanes):
                got[(seq.sid, pos)] = logits[i]
    snap = eng.snapshot()
    pool = eng.pool
    eng.stop()
    return got, streams, loads, snap, pool


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_logits_are_the_reference(dtype):
    """Prefill (the chunked scan, the dense attention, the experts over the
    prompt's live rows) and then decode through the pool (the state slots,
    the K/V pages, the experts over the live lanes) against the reference's
    whole forward pass over the finished transcript.  Prompts across bucket,
    chunk and page edges.  The float8 control misses the same tolerance on
    the same transcripts."""
    cfg, w, params = _weights(dtype)
    got, streams, loads, snap, pool = _serve(cfg, params)
    # Mamba-2 slots AND routed experts in one program
    assert pool.num_slots > 0 and snap["state_slots"]["live"] == 0 and \
        snap["state_slots"]["peak"] == 4
    assert snap["moe_experts"] == "ragged-dense"  # the host's formulation
    assert snap["ssm_step"] == "xla" and snap["ssm_scan"] == "xla"
    assert pool.plane_names() == [
        n for i, t in enumerate(TYPES) for n in (
            ["layer%d_k_pool" % i, "layer%d_v_pool" % i] if t == "attention"
            else ["layer%d_ssm_state" % i, "layer%d_conv_tail" % i])]
    # a padded lane of the bucket (known by its scratch slot) picks nothing:
    # live lanes x k a layer, over the router's whole width, every layer
    k = cfg["num_experts_per_tok"]
    for lanes, load in loads:
        assert load.shape == (LAYERS, EXPERTS)
        assert (load.sum(axis=1) == lanes * k).all()
    control_misses = 0
    for st in streams:
        assert st.done and st.exception() is None and len(st.tokens) == 9
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


@pytest.mark.parametrize("fault,program", [
    ("f32+router-sigmoid", dict(router_score="sigmoid")),
    ("f32+shared-zeroed", dict(shared_expert_width=0))])
def test_a_faulted_program_misses_the_reference(fault, program):
    """The router scored the old way (sigmoid, normalised) and a dropped
    shared MLP: each PROGRAM so built misses the reference by more than the
    float32 tolerance on every stream, and agrees with the reference faulted
    the same way: the comparison sees the fault, and the control is the
    fault it names."""
    cfg, w, params = _weights()
    if "shared_expert_width" in program:
        params = {k: v for k, v in params.items() if "_shared_" not in k}
    got, streams, _, _, _ = _serve(cfg, params, lengths=(8, 17), new=5,
                                   **program)
    for st in streams:
        seq = st.prompt + st.tokens
        want = _ref_logits(cfg, w, seq)
        same = _ref_logits(cfg, w, seq, fault)
        rows = sorted(p for (sid, p) in got if sid == st.sid)
        mine = np.stack([got[(st.sid, p)] for p in rows])
        assert np.abs(mine - want[rows]).max() > 10 * TOL["float32"]
        np.testing.assert_allclose(mine, same[rows], atol=TOL["float32"],
                                   rtol=0)


@pytest.mark.parametrize("control,moves", [
    ("f32+router-sigmoid", True), ("f32+shared-zeroed", True),
    ("f32+experts-zeroed", True), ("f32+layer3-zeroed", True),
    ("f32+experts-fp8", True), ("f32", False)])
def test_a_control_faults_what_it_names(control, moves):
    cfg, w, _ = _weights()
    seq = _prompts([24], seed=3)[0]
    want = _ref_logits(cfg, w, seq)
    got = _ref_logits(cfg, w, seq, control)
    assert bool(np.abs(got - want).max() > 1e-3) is moves
    with pytest.raises(ValueError, match="unknown control"):
        ref.control("bf16+router-zeroed")


def test_the_state_control_rounds_the_state():
    """``state-bf16`` at the layer itself: at toy widths the state's part of
    a logit is too small to read there."""
    cfg, w, _ = _weights()
    z = ref.sizes(cfg)
    p = {k[len("layer0_"):]: 20.0 * v if k.endswith("in_proj_weight") else v
         for k, v in w.items() if k.startswith("layer0_")}
    h = np.random.default_rng(1).standard_normal((24, 32)).astype(np.float32)
    plain, rounded = (np.asarray(ref._mamba(h, p, z, "f32", fault))
                      for fault in (None, "bf16"))
    gap = np.abs(plain - rounded).max()
    assert 1e-5 < gap < 0.02 * np.abs(plain).max()
    assert ref.control("bf16+state-bf16") == ("bf16", "state", "bf16")


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """The guide's share test, two chips a layer: the routed parts that
    experts 0-3 and 4-7 give, plus the shared MLP counted once, are the
    uncut layer of the reference; a share's leaves are the whole layer's
    slice; and the program's ops told a share give that share's part."""
    whole = dict(_cfg(), num_local_experts=8, first_expert=0)
    w = ref.make_weights(whole, 11)
    z = ref.sizes(whole)
    g = np.random.default_rng(2).standard_normal((24, 32)).astype(np.float32)
    p = {k[len("layer3_"):]: v for k, v in w.items()
         if k.startswith("layer3_")}
    want = ref.shared(g, p, z, "f32") + ref.routed(g, p, z, "f32")
    total = np.asarray(ref.shared(g, p, z, "f32"))
    for first in (0, 4):
        cut = dict(whole, num_local_experts=4, first_expert=first)
        ws = ref.make_weights(cut, 11)
        for name in ws:  # everything but the stacked experts is shared
            if name.endswith(("experts_w13", "experts_w2")):
                np.testing.assert_array_equal(
                    np.asarray(ws[name]),
                    np.asarray(w[name])[first:first + 4])
            else:
                np.testing.assert_array_equal(np.asarray(ws[name]),
                                              np.asarray(w[name]))
        ps = {k[len("layer3_"):]: v for k, v in ws.items()
              if k.startswith("layer3_")}
        part = np.asarray(ref.routed(g, ps, ref.sizes(cut), "f32"))
        assert np.abs(part).max() > 0
        total = total + part
        ids, weights, _ = moe.route(g, ps["router_weight"], None, top_k=3,
                                    score="softmax")
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
    assert fam.lane_extras == ("expert_load",)
    assert fam.expert_layers == tuple(range(LAYERS)) and fam.has_slots
    assert (fam.num_experts, fam.experts_held, fam.first_expert,
            fam.experts_per_token) == (8, 4, 2, 3)
    assert lm_family(fam.spec()).spec() == fam.spec()
    lane = fam.decode_symbol(S, 4)
    assert lane.list_outputs()[-2:] == ["next_ids_output",
                                        "expert_load_output"]
    args = lane.list_arguments()
    assert "state_slot" in args and "lm_head_weight" not in args
    assert not any(a.endswith("router_bias") for a in args)
    assert not any("_mlp_" in a for a in args)  # no dense layer
    assert sum(a.endswith("_shared_in_weight") for a in args) == LAYERS
    assert sum(a.endswith("_experts_w13") for a in args) == LAYERS


@pytest.mark.parametrize("key,value,says", [
    ("num_local_experts", 0, "with routed experts"),
    ("position_embedding_type", "rope", "no positional encoding"),
    ("mamba_n_groups", 8, "one B/C group"),
    ("attention_bias", True, "no projection bias"),
    ("tie_word_embeddings", False, "head is tied"),
    ("hidden_act", "gelu", "SiLU-gated"),
    ("n_group", 4, "no expert groups"),
    ("num_dense_layers", 1, "every layer of this family routes"),
    ("shared_intermediate_size", 0, "shared MLP"),
    ("layer_types", ["mamba", "conv", "attention", "mamba", "mamba"],
     "layer kinds")])
def test_the_builder_refuses_by_name_what_the_program_cannot_build(key, value,
                                                                   says):
    with pytest.raises(ValueError, match=says):
        builder.family_spec(dict(_cfg(), **{key: value}))


def test_a_step_carries_state_and_expert_arguments_at_once(tmp_path):
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
    slot_bytes, one_expert = eng.pool.slot_bytes, eng.family.expert_bytes()
    eng.stop()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    events = [e for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    steps = [dict(e.stats) for e in events if e.name == "gen:step"]
    both = [s for s in steps if "state_bytes" in s and "experts_hit" in s]
    assert both
    for s in both:
        assert int(s["state_bytes"]) == int(s["lanes"]) * slot_bytes
        pairs, held = int(s["expert_pairs"]), int(s["expert_pairs_held"])
        # the step read was of 1 or 2 live lanes: 3 picks in 5 layers each
        assert pairs in (15, 30) and 0 <= held <= pairs
        hit = int(s["experts_hit"])
        assert hit <= min(held, 4 * LAYERS) and (hit > 0) == (held > 0)
        assert int(s["expert_bytes"]) == hit * one_expert
        assert "latent_bytes" not in s
    prefills = [dict(e.stats) for e in events if e.name == "gen:prefill"]
    assert prefills and all("state_slot" in s and "expert_pairs" in s
                            for s in prefills)
    assert all(0 < int(s["scan_chunks_live"]) <= int(s["scan_chunks"])
               for s in prefills)
    # which pairs the prefill's expert layers move, and what chose it
    # (ops/moe.py ``experts_path``): on the host every pair
    assert all(s["experts_path"] == "all"
               and float(s["expert_pairs_moved"]) == 1.0 for s in prefills)
    for name in ("mxtpu_gen_expert_pairs_held", "mxtpu_gen_experts_hit",
                 "mxtpu_gen_expert_picks"):
        assert name in text
