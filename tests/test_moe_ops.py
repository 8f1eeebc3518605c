"""The routed expert layer and rotary positions (ops/moe.py) against plain
NumPy / jax.numpy written out, and against the benchmark's plain reference
(perfbench/models/lfm2_moe_lm.py: every expert over every row, combined by a
dense weight matrix).

Tolerances.  float32 operands throughout: the grouped products sum the same
terms in another order than the dense ones (1e-5 on outputs of order 0.1-1);
the router's scores are compared to 1e-6 and its picks exactly (the seeds
hold no tie).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import moe
from perfbench.models import lfm2_moe_lm as ref

H, F, E, K = 32, 16, 8, 2
TOL = dict(atol=1e-5, rtol=1e-5)


def _layer(seed, rows=11, experts=E, hidden=H, width=F):
    r = np.random.RandomState(seed)
    return dict(
        g=r.randn(rows, hidden).astype(np.float32),
        router=(r.randn(experts, hidden) / np.sqrt(hidden)).astype(np.float32),
        bias=(0.05 * r.randn(experts)).astype(np.float32),
        w13=(r.randn(experts, hidden, 2 * width)
             / np.sqrt(hidden)).astype(np.float32),
        w2=(r.randn(experts, width, hidden)
            / np.sqrt(width)).astype(np.float32))


def _dense_experts(g, ids, weights, w13, w2, first=0):
    """Every held expert over every row, then the picks' weighted sum."""
    out = np.zeros_like(g)
    for e in range(w13.shape[0]):
        h = g @ w13[e]
        a, b = np.split(h, 2, axis=-1)
        y = (a / (1 + np.exp(-a)) * b) @ w2[e]
        w = np.where(ids == e + first, weights, 0.0).sum(-1)
        out += w[:, None] * y
    return out


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("normalize,scale", [(True, 1.0), (False, 1.0),
                                             (True, 2.5)])
def test_router_is_numpys_top_k_of_score_plus_bias(normalize, scale):
    a = _layer(0, rows=40)
    ids, w, load = moe.route(a["g"], a["router"], a["bias"], top_k=3,
                             normalize=normalize, scale=scale)
    s = 1 / (1 + np.exp(-(a["g"] @ a["router"].T)))
    want = np.argsort(-(s + a["bias"]), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(ids), want)
    picked = np.take_along_axis(s, want, axis=-1)  # WITHOUT the bias
    if normalize:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(w), scale * picked, atol=1e-6)
    assert ids.dtype == jnp.int32 and w.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(load),
                                  np.bincount(want.reshape(-1), minlength=E))
    if normalize:  # the 1e-6 is in the sum: weights add up to just under 1
        total = np.asarray(w).sum(-1) / scale
        assert (total < 1).all() and (total > 1 - 1e-5).all()


def test_the_bias_changes_picks_and_never_weights():
    a = _layer(1, rows=200)
    plain, w0, _ = moe.route(a["g"], a["router"], 0 * a["bias"], top_k=K)
    ids, w, _ = moe.route(a["g"], a["router"], a["bias"], top_k=K)
    moved = (np.sort(np.asarray(plain)) != np.sort(np.asarray(ids))).any(-1)
    assert 0 < moved.sum() < 100  # some picks, not most
    same = ~moved
    np.testing.assert_allclose(np.sort(np.asarray(w)[same]),
                               np.sort(np.asarray(w0)[same]), atol=1e-6)


def test_a_row_that_is_not_live_picks_nothing():
    a = _layer(2, rows=6)
    live = np.array([1, 0, 1, 1, 0, 0], np.float32)
    ids, w, load = moe.route(a["g"], a["router"], a["bias"], live, top_k=K)
    ids, w = np.asarray(ids), np.asarray(w)
    assert (ids[live == 0] == E).all() and (w[live == 0] == 0).all()
    whole, _, _ = moe.route(a["g"], a["router"], a["bias"], top_k=K)
    np.testing.assert_array_equal(ids[live == 1],
                                  np.asarray(whole)[live == 1])
    assert int(np.asarray(load).sum()) == 3 * K


def test_router_scores_are_float32_whatever_the_rows():
    """bfloat16 rows against bfloat16 weights: scores and weights float32,
    and the picks those of the float32 product of the same rounded
    operands."""
    a = _layer(3, rows=64)
    g16, r16 = (jnp.asarray(a[k], jnp.bfloat16) for k in ("g", "router"))
    ids, w, _ = moe.route(g16, r16, a["bias"], top_k=K)
    want, w32, _ = moe.route(g16.astype(jnp.float32),
                             r16.astype(jnp.float32), a["bias"], top_k=K)
    assert w.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w32), atol=1e-6)


def test_near_ties_pick_another_expert_in_bfloat16():
    """What a bfloat16 row costs the router (the benchmark's limits file
    counts the same at the cell's widths): of 4,096 rows the set of picks
    differs from the float32 rows' in a few of a hundred, never in most."""
    a = _layer(4, rows=4096, experts=32, hidden=64)
    ids32, _, _ = moe.route(a["g"], a["router"], a["bias"], top_k=4)
    ids16, _, _ = moe.route(jnp.asarray(a["g"], jnp.bfloat16), a["router"],
                            a["bias"], top_k=4)
    differ = (np.sort(np.asarray(ids32)) != np.sort(np.asarray(ids16))).any(-1)
    assert 0.005 < differ.mean() < 0.15


# ---------------------------------------------------------------------------
# the routed experts
# ---------------------------------------------------------------------------

def test_routed_experts_are_the_dense_formulation():
    a = _layer(5)
    ids, w, _ = moe.route(a["g"], a["router"], a["bias"], top_k=K)
    got = moe.routed_experts(a["g"], ids, w, a["w13"], a["w2"])
    want = _dense_experts(a["g"], np.asarray(ids), np.asarray(w), a["w13"],
                          a["w2"])
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_uneven_loads_an_idle_expert_and_consecutive_picks():
    """Rows 0-4 all pick experts (0, 1); row 5 picks (6, 7); row 6 picks (2,
    3); expert 4 and 5 get nothing: groups of 5, 5, 1, 1, 0, 0, 1, 1."""
    a = _layer(6, rows=7)
    ids = np.array([[0, 1]] * 5 + [[6, 7], [2, 3]], np.int32)
    w = np.random.RandomState(0).rand(7, 2).astype(np.float32)
    got = moe.routed_experts(a["g"], jnp.asarray(ids), jnp.asarray(w),
                             a["w13"], a["w2"])
    np.testing.assert_allclose(
        np.asarray(got), _dense_experts(a["g"], ids, w, a["w13"], a["w2"]),
        **TOL)


def test_a_row_whose_picks_are_four_groups_apart():
    """One row alone, its four picks in four different groups with idle
    groups between them."""
    a = _layer(7, rows=1)
    ids, w = np.array([[7, 0, 5, 2]], np.int32), \
        np.array([[0.4, 0.3, 0.2, 0.1]], np.float32)
    got = moe.routed_experts(a["g"], jnp.asarray(ids), jnp.asarray(w),
                             a["w13"], a["w2"])
    np.testing.assert_allclose(
        np.asarray(got), _dense_experts(a["g"], ids, w, a["w13"], a["w2"]),
        **TOL)


def test_padded_rows_add_nothing_and_poison_nothing():
    """Rows that are not live (expert ``E``, weight 0) give exact zeros even
    where their activations are inf or NaN, and leave their neighbours'
    outputs to the bit."""
    a = _layer(8, rows=6)
    live = np.array([1, 1, 0, 1, 0, 0], np.float32)
    g = a["g"].copy()
    g[2], g[4] = np.inf, np.nan
    ids, w, _ = moe.route(g, a["router"], a["bias"], live, top_k=K)
    got = np.asarray(moe.routed_experts(jnp.asarray(g), ids, w, a["w13"],
                                        a["w2"]))
    assert (got[live == 0] == 0).all() and np.isfinite(got[live == 1]).all()
    alone = g[live == 1]
    ids1, w1, _ = moe.route(alone, a["router"], a["bias"], top_k=K)
    np.testing.assert_allclose(
        got[live == 1],
        np.asarray(moe.routed_experts(alone, ids1, w1, a["w13"], a["w2"])),
        **TOL)


# the three expert cells' shares (experts, held, first, picks a row) and one
# that does not start at expert 0
SHARES = {"16_of_256_k8": (256, 16, 0, 8), "36_of_72_k10": (72, 36, 0, 10),
          "32_of_32_k4": (32, 32, 0, 4), "16_of_256_from_48": (256, 16, 48, 8)}
# row tiles of 16 sorted pairs, so that a few rows' pairs straddle several
_TILES = dict(rows=(16, 16), interpret=True)
_ALL = functools.partial(moe._kernel_grouped, **_TILES)
_HELD = functools.partial(moe._kernel_held, **_TILES)


@pytest.mark.parametrize("rows", [3, 13])
@pytest.mark.parametrize("lands", ["as_routed", "all_here", "none_here"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_a_share_moves_its_own_pairs_and_is_the_dense_formulation(
        share, lands, rows):
    """``routed_experts`` on the path that moves only the held pairs' rows
    (``held=_kernel_held``: the gather loop, ``moe_grouped`` and
    ``moe_combine`` in interpret mode) against every held expert over every
    row, and against the path that moves every pair: as the router sent them
    (a third of the rows not live, inf and NaN in them), with EVERY pick on
    a held expert (nothing is bounded: every row tile is live) and with none
    (exact zeros, nothing read from memory nobody wrote).  Both paths add a
    row's picks in float32, the held path in the sorted pairs' order and
    the other in whatever order XLA reduces: they agree to the bit in the
    rows with at most one held pick, else to the file's tolerance."""
    experts, held, first, k = SHARES[share]
    a = _layer(12, rows=rows, experts=experts)
    r = np.random.RandomState(rows)
    live = np.ones(rows, bool)
    if lands == "as_routed":
        live = np.arange(rows) % 3 != 1
        ids, w, _ = moe.route(a["g"], a["router"], None, live.astype("f"),
                              top_k=k)
        ids, w = np.array(ids), np.array(w)
    else:
        here = np.arange(first, first + held)
        pool = here if lands == "all_here" else \
            np.setdiff1d(np.arange(experts), here)
        if len(pool) < k:  # every expert held: nothing lands elsewhere
            live[:] = False
            pool = np.arange(experts)
        ids = np.stack([r.permutation(pool)[:k] for _ in range(rows)])
        ids = np.where(live[:, None], ids, experts).astype(np.int32)
        w = np.where(live[:, None], r.rand(rows, k), 0.0).astype(np.float32)
    g = a["g"].copy()
    g[~live] = np.where(np.arange(H) % 2, np.inf, np.nan)
    w13, w2 = (a[name][first:first + held] for name in ("w13", "w2"))
    want = _dense_experts(np.where(live[:, None], g, 0.0), ids, w, w13, w2,
                          first=first)
    run = functools.partial(moe.routed_experts, jnp.asarray(g),
                            jnp.asarray(ids), jnp.asarray(w), w13, w2,
                            first_expert=first)
    got, every = np.asarray(run(held=_HELD)), np.asarray(run(grouped=_ALL))
    assert np.isfinite(got).all() and (got[~live] == 0).all()
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(got, every, **TOL)
    here = (ids >= first) & (ids < first + held)
    one = here.sum(1) <= 1  # one term: no order, no fused multiply-add
    np.testing.assert_array_equal(got[one], every[one])
    if lands == "none_here":
        assert not here.any() and (got == 0).all()
    elif lands == "all_here":
        assert here[live].all()
    assert (np.abs(got).max() > 0.01) == bool(here.any())


@pytest.mark.parametrize("grouped", [
    moe._ragged_grouped,
    functools.partial(moe._kernel_grouped, interpret=True)],
    ids=["ragged-dense", "pallas"])
def test_two_shares_add_up_to_the_whole_layer_and_to_the_reference(grouped):
    """The guide's share test: an op that holds experts 0-15 and one that
    holds 16-31, both routed over all 32, add up to the op that holds all 32
    -- and that is the plain reference's whole layer (every expert over
    every row).  No part is counted twice: there is no shared expert.  In
    both formulations: XLA's, and the kernel ``moe_grouped`` in interpret
    mode (a share's 16 leaves, the other share's pairs in no group)."""
    experts = functools.partial(moe.routed_experts, grouped=grouped)
    cfg = dict(vocab_size=64, hidden_size=H, layer_types=["conv"],
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=64, moe_intermediate_size=F, conv_L_cache=3,
               num_dense_layers=0, num_experts=32, num_experts_per_tok=4,
               norm_topk_prob=True, routed_scaling_factor=1,
               rope_theta=1e6, norm_eps=1e-5, weights_dtype="float32")
    p = {k[len("layer0_"):]: v for k, v in ref.make_weights(cfg, 9).items()
         if k.startswith("layer0_")}
    g = jnp.asarray(np.random.RandomState(9).randn(23, H), jnp.float32)
    ids, w, load = moe.route(g, p["router_weight"], p["router_bias"],
                             top_k=4)
    whole = experts(g, ids, w, p["experts_w13"], p["experts_w2"])
    parts = [experts(g, ids, w, p["experts_w13"][lo:lo + 16],
                     p["experts_w2"][lo:lo + 16], first_expert=lo)
             for lo in (0, 16)]
    assert all(float(jnp.abs(part).max()) > 0.01 for part in parts)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), **TOL)
    want = ref._experts(g, p, ref.sizes(cfg), "f32")
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), **TOL)
    # and the reference's own shares are the op's
    for lo, part in zip((0, 16), parts):
        share = dict(cfg, first_expert=lo, experts_held=16)
        ps = dict(p, experts_w13=p["experts_w13"][lo:lo + 16],
                  experts_w2=p["experts_w2"][lo:lo + 16])
        np.testing.assert_allclose(
            np.asarray(part),
            np.asarray(ref._experts(g, ps, ref.sizes(share), "f32")), **TOL)
    assert int(load.sum()) == 23 * 4


def test_a_shares_seeded_weights_are_the_whole_layers_slice():
    cfg = dict(vocab_size=64, hidden_size=H, layer_types=["conv"],
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=64, moe_intermediate_size=F, conv_L_cache=3,
               num_dense_layers=0, num_experts=8, num_experts_per_tok=2,
               norm_topk_prob=True, routed_scaling_factor=1,
               rope_theta=1e6, norm_eps=1e-5, weights_dtype="float32")
    whole = ref.make_weights(cfg, 3)
    share = ref.make_weights(dict(cfg, first_expert=2, experts_held=3), 3)
    assert share["layer0_experts_w13"].shape == (3, H, 2 * F)
    for k in whole:
        want = whole[k][2:5] if "experts_w" in k else whole[k]
        np.testing.assert_array_equal(np.asarray(share[k]), np.asarray(want))


def test_the_ops_through_the_symbol_surface():
    """``mx.sym._contrib_MoERouter`` / ``_contrib_RoutedExperts`` bound and
    run: three outputs and one, the held range checked by name."""
    a = _layer(10, rows=5)
    data = mx.sym.Variable("data")
    ids, w, load = mx.sym._contrib_MoERouter(
        data, mx.sym.Variable("router", shape=(E, H)),
        mx.sym.Variable("bias", shape=(E,)), top_k=K, name="r")
    out = mx.sym._contrib_RoutedExperts(
        data, ids, w, mx.sym.Variable("w13", shape=(4, H, 2 * F)),
        mx.sym.Variable("w2", shape=(4, F, H)), num_experts=E,
        first_expert=2, name="x")
    ex = mx.sym.Group([out, load]).simple_bind(mx.cpu(), grad_req="null",
                                               data=(5, H))
    feed = dict(data=a["g"], router=a["router"], bias=a["bias"],
                w13=a["w13"][2:6], w2=a["w2"][2:6])
    for k, v in feed.items():
        ex.arg_dict[k][:] = v
    got, cnt = (o.asnumpy() for o in ex.forward(is_train=False))
    rid, rw, rload = moe.route(a["g"], a["router"], a["bias"], top_k=K)
    np.testing.assert_allclose(
        got, _dense_experts(a["g"], np.asarray(rid), np.asarray(rw),
                            a["w13"][2:6], a["w2"][2:6], first=2), **TOL)
    np.testing.assert_array_equal(cnt, np.asarray(rload))
    with pytest.raises(Exception, match="not among the router's 8"):
        moe._routed_experts(None, {"num_experts": 8, "first_expert": 6},
                            a["g"], rid, rw, a["w13"][:4], a["w2"][:4])


def test_ops_carry_their_scopes_and_the_formulation_is_an_observation():
    a = _layer(11)

    def layer(g):
        ids, w, _ = moe._moe_router(None, {"top_k": K}, g, a["router"],
                                    a["bias"])
        y = moe._routed_experts(None, {"num_experts": E}, g, ids, w,
                                a["w13"], a["w2"])
        return moe._rotary(None, {"theta": 1e6}, y.reshape(11, 2, 16),
                           jnp.arange(11.0))

    text = jax.jit(layer).lower(a["g"]).as_text(debug_info=True)
    for scope in ("moe_router", "moe_experts", "rotary"):
        assert scope in text
    # what the op runs is read off its operands (here float32 on the host),
    # the same facts the engine's ``snapshot()`` hands over
    assert "ragged_dot" in text and "moe_grouped" not in text
    assert moe.experts_formulation("cpu", a["w13"].dtype, H,
                                   F) == "ragged-dense"
    assert moe.experts_formulation("tpu", jnp.bfloat16, 2048,
                                   1792) == "pallas"


@pytest.mark.parametrize("platform,dtype,hidden,width,want", [
    ("tpu", "bfloat16", 2048, 1792, "pallas"),   # the LFM2 cell's widths
    ("tpu", "bfloat16", 256, 128, "pallas"),
    ("tpu", "float32", 2048, 1792, "ragged"),    # float32 leaves
    ("tpu", "bfloat16", 2048, 1760, "ragged"),   # 2F splits off a lane tile
    ("tpu", "bfloat16", 2000, 1792, "ragged"),
    ("cpu", "bfloat16", 2048, 1792, "ragged-dense"),
    ("gpu", "float32", 32, 16, "ragged-dense")])
def test_experts_formulation_is_read_off_the_operands(platform, dtype, hidden,
                                                      width, want):
    assert moe.experts_formulation(platform, dtype, hidden, width) == want


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def test_rotary_is_the_references_and_keeps_norms():
    x = np.random.RandomState(12).randn(9, 4, 16).astype(np.float32)
    got = moe.rotary(x, jnp.arange(9), theta=1e6)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.rotary(jnp.asarray(x), 1e6)),
                               atol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(got), axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[0]), x[0])  # position 0


def test_rotary_at_lane_positions_is_the_sequence_graphs():
    """The lane layout ((lanes, heads, d) at ``positions``) against the
    sequence layout ((b, L, heads, d) at 0..L-1): the same rotation of the
    same row, to the bit, in bfloat16 too."""
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(np.random.RandomState(13).randn(2, 12, 4, 16), dtype)
        seq = moe.rotary(x, jnp.arange(12, dtype=jnp.float32), theta=1e4)
        assert seq.dtype == dtype
        pos = np.array([11, 0, 5], np.float32)  # the float carrier
        lanes = moe.rotary(x[1, pos.astype(int)], jnp.asarray(pos),
                           theta=1e4)
        np.testing.assert_array_equal(
            np.asarray(lanes, np.float32),
            np.asarray(seq[1, pos.astype(int)], np.float32))


def test_partial_rotary_passes_the_rest():
    x = np.random.RandomState(14).randn(5, 2, 16).astype(np.float32)
    got = np.asarray(moe.rotary(x, jnp.arange(5), theta=1e4, rotary_dim=8))
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        got[..., :8], np.asarray(ref.rotary(jnp.asarray(x[..., :8]), 1e4)),
        atol=1e-6)
