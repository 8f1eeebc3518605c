"""The latent-attention family (the ``latent`` mixer, a shared expert beside
routed experts of which this chip holds a share, a router without a
selection bias, sandwich norms and an untied head of models/hybrid_lm.py)
through the generation engine, against the benchmark's plain reference
(perfbench/models/latent_moe_lm.py: float32, the EXPANDED attention only,
every held expert over every row, no cache): one dense and three expert
layers at toy widths, experts 2-5 of 8 held here.

The reference has no absorbed form and no cache: the engine's decode path
(``_contrib_PagedLatentAttention`` over ONE paged plane a layer of ``[c |
k_r]`` rows) is held to the reference's whole forward pass position by
position, and the absorbed op to the expanded op directly.

Tolerances.  float32 weights: the program's absorbed products, gathers and
fused norms against the reference's plain order of the same float32 sums:
2e-4 on logits of order 1.  bfloat16 weights: the program rounds every
activation to bfloat16 through 4 layers of width 32 where the reference
keeps float32: 0.15 on the same logits, twice the largest the runs read on
seeds 6 to 8 (0.074; the reference itself computed in bfloat16 reads up to
0.107) and a quarter of the least the float8 control reads on a stream
(0.59).  The bfloat16 case picks ALL 8 experts a row (4 of them held), so no
near-tie of the router's scores can pick another expert than the reference
(tests/test_lfm2_lm.py has the reason).  The float8 control fails both
tolerances; that is asserted.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.generation import DecodeEngine
from perfbench.builders import latent_moe_lm as builder
from perfbench.models import latent_moe_lm as ref

V, S = 96, 48
CFG = dict(vocab_size=V, hidden_size=32, intermediate_size=64,
           moe_intermediate_size=16, num_hidden_layers=4,
           first_k_dense_replace=1, n_routed_experts=4,
           n_routed_experts_published=8, first_expert=2, n_shared_experts=1,
           num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, q_lora_rank=16, kv_lora_rank=12,
           num_experts_per_tok=2, norm_topk_prob=True,
           routed_scaling_factor=2.5, rope_theta=25600000,
           rms_norm_eps=1e-5, sandwich_norm=True, tie_word_embeddings=False,
           attention_bias=False, hidden_act="silu",
           num_nextn_predict_layers=1,
           left_out={"num_nextn_predict_layers": "never read"})
TOL = {"float32": 2e-4, "bfloat16": 0.15}
ENGINE = dict(max_seq_len=S, lane_buckets=(2, 4), page_size=4, num_pages=60,
              prefill_len_buckets=(8, 16, 32), prefill_batch_buckets=(1,))
LAYERS, EXPERT_LAYERS, WIDTH = 4, 3, 12 + 4


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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_logits_are_the_reference(dtype):
    """The engine driven by hand: the logits of every prefill (expanded, at
    the prompt's last token) and of every decode step (absorbed over the
    latent plane; every lane, every position) against the reference's whole
    forward pass over the finished transcript.  Prompts across bucket and
    page edges.  The float8 control misses the same tolerance on the same
    transcripts."""
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
    # ONE paged plane a layer, no slot plane, no state_slot
    assert "state_slots" not in snap and eng.pool.num_slots == 0
    assert eng.pool.plane_names() == ["layer%d_latent_pool" % i
                                      for i in range(LAYERS)]
    assert eng._latent_token_bytes == LAYERS * WIDTH * (
        4 if dtype == "float32" else 2)
    assert eng.pool.device_bytes() == 60 * 4 * eng._latent_token_bytes
    assert snap["latent_attention"] == {
        "prefill": "xla-expanded-head-blocks",
        "decode": "xla-absorbed-gather"}
    assert snap["paged_attention"] == "xla"
    assert snap["moe_experts"] == "ragged-dense"  # the host's formulation
    assert eng._moe_experts == (np.dtype(dtype), 32, 16)
    # a padded lane of the bucket (known by its scratch page) picks nothing:
    # live lanes x k a layer, over the router's whole width
    k = cfg["num_experts_per_tok"]
    for lanes, load in loads:
        assert load.shape == (EXPERT_LAYERS, 8)
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


@pytest.mark.parametrize("control,moves", [
    ("f32+attn-norope", True), ("f32+attn-nonorm", True),
    ("f32+shared-zeroed", True), ("f32+experts-zeroed", True),
    ("f32+layer2-zeroed", True), ("f32+experts-rotated", True),
    ("f32+layer2-rotated", True), ("f32+experts-fp8", True),
    ("f32", False)])
def test_a_control_faults_what_it_names(control, moves):
    cfg, w, _ = _weights()
    seq = _prompts([24], seed=3)[0]
    want = _ref_logits(cfg, w, seq)
    got = _ref_logits(cfg, w, seq, control)
    assert bool(np.abs(got - want).max() > 1e-3) is moves
    with pytest.raises(ValueError, match="unknown control"):
        ref.control("bf16+attn-zeroed")


# ---------------------------------------------------------------------------
# the two forms of the attention, and the share
# ---------------------------------------------------------------------------

def test_the_absorbed_form_over_pages_is_the_expanded_form():
    """Token by token through ``paged_latent_attention`` over a paged plane
    (pages out of order, a scratch page, an idle lane) against ONE call of
    the expanded ``latent_attention`` over the same rows."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.paged import latent_attention, paged_latent_attention

    rng = np.random.default_rng(0)
    heads, nope, rope, v, rank, ps, n = 4, 8, 4, 8, 12, 4, 11
    q_n = rng.standard_normal((1, n, heads, nope)).astype(np.float32)
    q_r = rng.standard_normal((1, n, heads, rope)).astype(np.float32)
    rows = rng.standard_normal((1, n, rank + rope)).astype(np.float32)
    w = rng.standard_normal((heads * (nope + v), rank)).astype(np.float32)
    scale = (nope + rope) ** -0.5
    want = np.asarray(latent_attention(q_n, q_r, rows, w, scale=scale))[0]
    pool = jnp.zeros((6, ps, rank + rope), jnp.float32)
    table = np.array([[4, 2, 5], [0, 0, 0]], np.int32)  # lane 1 idles
    for t in range(n):
        out, pool = paged_latent_attention(
            np.stack([q_n[0, t], q_n[0, 0]]), np.stack([q_r[0, t],
                                                        q_r[0, 0]]),
            np.stack([rows[0, t], rows[0, 0]]), w, pool, table,
            np.array([t, 0], np.int32), scale=scale)
        np.testing.assert_allclose(np.asarray(out)[0], want[t], atol=2e-5,
                                   rtol=0)
    flat = np.asarray(pool)[[4, 2, 5]].reshape(-1, rank + rope)
    np.testing.assert_array_equal(flat[:n], rows[0])
    assert not np.asarray(pool)[[1, 3]].any()


def test_the_expanded_form_in_query_blocks_is_the_whole_square(monkeypatch):
    """Blocks of queries against the keys up to their last row (what a
    2,048-token prefill runs) against one block over the whole sequence."""
    from mxnet_tpu.ops import paged

    rng = np.random.default_rng(0)
    heads, nope, rope, v, rank, n = 4, 8, 4, 8, 12, 37
    q_n = rng.standard_normal((2, n, heads, nope)).astype(np.float32)
    q_r = rng.standard_normal((2, n, heads, rope)).astype(np.float32)
    rows = rng.standard_normal((2, n, rank + rope)).astype(np.float32)
    w = rng.standard_normal((heads * (nope + v), rank)).astype(np.float32)
    whole = np.asarray(paged.latent_attention(q_n, q_r, rows, w, scale=0.3))
    monkeypatch.setattr(paged, "_LATENT_QUERY_BLOCK", 16)
    blocked = np.asarray(paged.latent_attention.__wrapped__(
        q_n, q_r, rows, w, scale=0.3))
    np.testing.assert_allclose(blocked, whole, atol=1e-5, rtol=0)


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that all 4 shares of 2
    experts give, plus the shared expert counted once, are the uncut layer
    of the reference; and the program's ops told a share give that share's
    part (the engine test above runs experts 2-5 through the graphs)."""
    from mxnet_tpu.ops import moe

    whole = dict(_cfg(), n_routed_experts=8, first_expert=0)
    w = ref.make_weights(whole, 11)
    z = ref.sizes(whole)
    g = np.random.default_rng(2).standard_normal((24, 32)).astype(np.float32)
    p = {k[len("layer2_"):]: v for k, v in w.items()
         if k.startswith("layer2_")}
    want = ref.shared(g, p, z, "f32") + ref.routed(g, p, z, "f32")
    total = np.asarray(ref.shared(g, p, z, "f32"))
    for first in (0, 2, 4, 6):
        cut = dict(whole, n_routed_experts=2, first_expert=first)
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
        # the program's ops, told the same share, give the same part
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
    assert fam.planes() == [("layer%d_latent_pool" % i, "paged", (WIDTH,),
                             "float32") for i in range(LAYERS)]
    assert fam.lane_extras == ("expert_load",)
    assert fam.expert_layers == (1, 2, 3) and not fam.has_slots
    assert fam.latent_token_bytes() == LAYERS * WIDTH * 4
    assert lm_family(fam.spec()).spec() == fam.spec()
    lane = fam.decode_symbol(S, 4)
    assert lane.list_outputs()[-2:] == ["next_ids_output",
                                        "expert_load_output"]
    args = lane.list_arguments()
    assert "state_slot" not in args and "lm_head_weight" in args
    assert not any(a.endswith("router_bias") for a in args)
    assert sum(a.endswith("_shared_in_weight") for a in args) == 3
    assert sum("post_norm" in a for a in args) == 2 * LAYERS
    with pytest.raises(ValueError, match="latent: head_dim"):
        HybridLM(**dict(spec, head_dim=8))
    with pytest.raises(ValueError, match="missing .*kv_rank"):
        HybridLM(**dict(spec, kv_rank=None))


@pytest.mark.parametrize("key,value,says", [
    ("rope_scaling", {"type": "yarn"}, "no scaling"),
    ("n_group", 8, "no expert groups"),
    ("topk_method", "noaux_tc", "selection bias"),
    ("scoring_func", "softmax", "scores by sigmoid"),
    ("left_out", {}, "multi-token-prediction"),
    ("attention_bias", True, "no bias"),
    ("num_key_value_heads", 2, "one latent row")])
def test_the_builder_refuses_by_name_what_the_program_cannot_build(key, value,
                                                                   says):
    with pytest.raises(ValueError, match=says):
        builder.family_spec(dict(_cfg(), **{key: value}))


@pytest.mark.parametrize("what,kw", [
    ("draft=", {"draft": {"params": {}, "k": 2}}),
    ("prefix_cache_pages > 0", {"prefix_cache_pages": 8})])
def test_what_needs_a_windowed_graph_is_refused_by_name(what, kw):
    cfg, _, params = _weights()
    with pytest.raises(MXNetError, match="no windowed"):
        _engine(cfg, params, start=False, warmup=False, **kw)


def test_spans_and_counters_of_the_latent_plane(tmp_path):
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
    sent = [s for s in steps if "latent_bytes" in s]
    assert sent
    token = LAYERS * WIDTH * 4
    # the first step feeds positions 5 and 9: 6 + 10 live tokens
    assert int(sent[0]["latent_bytes"]) == 16 * token
    for s in sent:
        assert int(s["latent_bytes"]) % token == 0
        assert "state_bytes" not in s
    assert any("expert_pairs" in s for s in steps)
    for name in ("mxtpu_gen_latent_bytes", "mxtpu_gen_expert_picks"):
        assert name in text
