"""Short-convolution / attention decoder LM with routed experts in plain
jax.numpy: the reference family of ``lfm2_moe`` configurations (LiquidAI
LFM2-8B-A1B).  Imports nothing of the program under test.

For layer ``l`` (``layer_types[l]`` is ``conv`` or ``full_attention``), rows
``x`` of width ``H``, no bias anywhere::

    x0 = E[ids]
    h  = RMSNorm(x; norm1)                                  eps, plain gain
    conv:  [B | C | X] = W_in_proj h   (H -> 3H, in this order)
           u_t = B_t * X_t
           c_t = sum_{k=0..K-1} w[:, k] * u_{t-(K-1)+k}     depthwise, causal,
                                                            no bias, NO activation
           mix = W_out_proj (C_t * c_t)
    attn:  q = RMSNorm_head(W_q h; q_norm)  (heads x hd)
           k = RMSNorm_head(W_k h; k_norm)  (kv_heads x hd),   v = W_v h
           q, k = rotary(q, k; position, rope_theta, all hd features, the
                  halves (i, i + hd/2) paired)
           mix = W_o softmax_causal(q k^T / sqrt(hd)) v     head i over K/V
                                                            head i // group
    a  = x + mix
    g  = RMSNorm(a; norm2)
    l <  num_dense_layers:  x' = a + W_out(silu(g1) * g3),  [g1 | g3] = W_in g
    l >= num_dense_layers:  s = sigmoid(W_r g)   (E scores, float32)
           I = top_k(s + bias);   w_i = routed_scaling_factor * s_i
                                        / (sum_{j in I} s_j + 1e-6)
           x' = a + sum_{i in I} w_i W2_i (silu(W1_i g) * W3_i g)
    logits = RMSNorm(x_last; norm_f) E^T                    (E is tied)

Everything is float32 at ``highest`` matmul precision (or, for a control,
the matrix products at a stated lower precision, and for an expert-only
control a fault in the expert layers beside: :func:`control`) from the
weights as they were seeded.  EVERY expert is computed for EVERY row and the results are
combined by the dense (rows, E) weight matrix that is zero off the picks;
the convolution is ``K`` shifted products; no cache, no batching.  One layer
is one jitted call (its experts one at a time inside it), so a layer's
weights are upcast one layer, and one expert, at a time.

A configuration may state a share of the experts (``first_expert``,
``experts_held``; the guide's cut for models whose experts outnumber the
chip): the router keeps its width, the held experts' part of the sum is
computed and what the others would add is left out.  The benchmark's cell
holds all 32.

Departures from the published description are in ``make_weights`` (the
published weights are not used) and under ``assumed`` in the configuration's
file.  Parameter names and layouts (``[out, in]`` matrices but for the
stacked experts, which are ``[expert, in, out]``):

  tok_embed_weight (V, H)               norm_f_gamma (H,)
  layer{i}_norm1_gamma, _norm2_gamma (H,)
  conv:       layer{i}_in_proj_weight (3H, H), _conv_weight (H, K),
              _out_proj_weight (H, H)
  attention:  layer{i}_q_weight (heads*hd, H), _k_weight, _v_weight
              (kv_heads*hd, H), _o_weight (H, heads*hd), _q_norm_gamma,
              _k_norm_gamma (hd,)
  dense:      layer{i}_mlp_in_weight (2I, H) [W1 | W3], _mlp_out_weight (H, I)
  experts:    layer{i}_router_weight (E, H), _router_bias (E,),
              _experts_w13 (held, H, 2F) [W1 | W3], _experts_w2 (held, F, H)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .hybrid_lm import _rms, einsum
from .precision import seed_key

ROUTER_EPS = 1e-6


def sizes(cfg, layers=None):
    """The sizes of a config dict, under this file's names."""
    types = list(cfg["layer_types"])
    if layers is not None:
        types = types[:int(layers)]
    heads, experts = int(cfg["num_attention_heads"]), int(cfg["num_experts"])
    first = int(cfg.get("first_expert", 0))
    return dict(
        vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
        types=types, heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads,
        inter=int(cfg["intermediate_size"]), conv_k=int(cfg["conv_L_cache"]),
        dense=int(cfg["num_dense_layers"]), experts=experts,
        top_k=int(cfg["num_experts_per_tok"]),
        expert_inter=int(cfg["moe_intermediate_size"]), first=first,
        held=int(cfg.get("experts_held", experts - first)),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["norm_eps"]))


def param_shapes(cfg, layers=None):
    z = sizes(cfg, layers)
    h, hd = z["hidden"], z["head_dim"]
    shapes = {"tok_embed_weight": (z["vocab"], h), "norm_f_gamma": (h,)}
    for i, kind in enumerate(z["types"]):
        pre = "layer%d_" % i
        shapes.update({pre + "norm1_gamma": (h,), pre + "norm2_gamma": (h,)})
        if kind == "full_attention":
            shapes.update({
                pre + "q_weight": (z["heads"] * hd, h),
                pre + "k_weight": (z["kv_heads"] * hd, h),
                pre + "v_weight": (z["kv_heads"] * hd, h),
                pre + "o_weight": (h, z["heads"] * hd),
                pre + "q_norm_gamma": (hd,), pre + "k_norm_gamma": (hd,)})
        else:
            shapes.update({pre + "in_proj_weight": (3 * h, h),
                           pre + "conv_weight": (h, z["conv_k"]),
                           pre + "out_proj_weight": (h, h)})
        if i < z["dense"]:
            shapes.update({pre + "mlp_in_weight": (2 * z["inter"], h),
                           pre + "mlp_out_weight": (h, z["inter"])})
        else:
            f = z["expert_inter"]
            shapes.update({pre + "router_weight": (z["experts"], h),
                           pre + "router_bias": (z["experts"],),
                           pre + "experts_w13": (z["held"], h, 2 * f),
                           pre + "experts_w2": (z["held"], f, h)})
    return shapes


def n_params(cfg, layers=None):
    return sum(int(np.prod(s)) for s in param_shapes(cfg, layers).values())


# Matrices (the embedding and the stacked experts too) are drawn from
# N(0, (GAIN / sqrt(fan_in))^2): 0.0199 at the published hidden size, what
# the family's N(0, 0.02) is there, and the same O(1) rows at a test's toy
# widths, where a fixed 0.02 passes almost nothing on and the model repeats
# its last token (PERF.md section 6, PR 32).  Every sublayer, the expert
# layers too, then adds about the residual stream's own size.  Three kinds
# are drawn otherwise.
#
# A layer's experts share a matrix: expert ``e`` is EXPERTS_OWN x its own
# draw + sqrt(1 - EXPERTS_OWN^2) x a draw common to the layer, in both of
# its matrices, as experts that were copied from one dense MLP and then
# trained apart are (sparse upcycling: Komatsuzaki et al., arXiv
# 2212.05055; whether the published model was made so is not known: the
# published weights are not used).  Why: a near-tie in the router's score +
# bias picks another expert in bfloat16 than in float32 in 1-3 rows of a
# hundred a layer (tests/test_moe_ops.py counts it), and the comparison that
# decides ``correct`` takes the WORST served token.  With independent experts
# one such pick is 0.7 of its layer's output, and the program then reads
# what the float8 control reads (2.36-2.59 against 4.02 on the chip, PR 34),
# or, with the experts' second matrix shrunk until a pick costs little
# (0.3 x, this file's first form), the expert layers carry 2 % of the
# logits' variance and a layer that adds nothing passes.  With a common part
# the whole of every product stays in the logits (a layer whose experts add
# nothing, add noise or come back to the wrong rows fails), and only WHICH
# expert a row was sent to is worth EXPERTS_OWN of it: every pick sent to
# the next expert still fails, one flipped pick does not.  What it cannot
# see is in the cell's limits file.
#
# The depthwise convolution's three taps a channel and the router's selection
# bias have no fan-in to speak of: taps N(0, 0.3) (at 0.02 the convolution
# layers would add a hundredth of what the attention layers add, and a wrong
# tail would move no logit), bias N(0, 0.05) (wide enough that it changes
# some picks, narrow enough that it does not pick alone).
GAIN = 0.9
EXPERTS_OWN = 0.1
_STD = {"conv_weight": 0.3, "router_bias": 0.05}
_STACKED = ("experts_w13", "experts_w2")


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _seeded_leaf(key, i, kind, shape, dtype, first=0, experts=0):
    """Leaf ``i`` (in the order of the sorted names) of the seeded weights;
    ``kind`` is its name without the layer.  A stacked leaf of a share is
    the share's slice of the whole layer's ``experts``, over the layer's
    common draw."""
    k = jax.random.fold_in(key, i)
    whole = (experts,) + shape[1:] if kind in _STACKED else shape
    if kind.endswith("_gamma"):
        std = 0.02
    elif kind in _STD:
        std = _STD[kind]
    else:  # [out, in], or the experts' [expert, in, out]
        std = GAIN / np.sqrt(shape[1] if kind in _STACKED else shape[-1])
    x = std * jax.random.normal(k, whole, jnp.float32)
    if kind.endswith("_gamma"):
        x = x + 1.0
    if kind in _STACKED:
        common = std * jax.random.normal(jax.random.fold_in(k, 1), whole[1:],
                                         jnp.float32)
        x = (EXPERTS_OWN * x + np.sqrt(1 - EXPERTS_OWN ** 2) * common)[
            first:first + shape[0]]
    return x.astype(dtype)


def make_weights(cfg, seed, layers=None):
    """Seeded weights in the dtype the configuration holds them in
    (``weights_dtype``, bfloat16 unless it says otherwise), made on the
    default device: matrices, the embedding and the experts N(0, (0.9 /
    sqrt(fan_in))^2), a layer's experts 0.1 their own and the rest common
    to the layer, the convolution's taps N(0, 0.3), the router's bias
    N(0, 0.05) (see ``GAIN``, ``EXPERTS_OWN`` and ``_STD``), norm gains
    1 + N(0, 0.02).  Every leaf is rounded to the weights' dtype; the
    reference upcasts what it is given."""
    z = sizes(cfg, layers)
    shapes = param_shapes(cfg, layers)
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    key = seed_key(seed)

    def kind(name):
        return name.split("_", 1)[1] if name.startswith("layer") else name

    return {name: _seeded_leaf(key, i, kind(name), shp, dtype, z["first"],
                               z["experts"])
            for i, (name, shp) in enumerate(sorted(shapes.items()))}


def _dense(x, w, prec):
    return einsum("...k,nk->...n", x, w, prec)


def rotary(x, theta):
    """``x`` (s, heads, hd) at positions 0..s-1: feature ``i`` paired with
    ``i + hd/2``, angle ``position * theta^(-2i/hd)``."""
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, p, z, prec):
    """(s, hidden) -> (s, hidden): causal grouped-query attention over
    normed, rotated queries and keys."""
    s, hd = h.shape[0], z["head_dim"]
    q = _dense(h, p["q_weight"], prec).reshape(s, z["heads"], hd)
    k = _dense(h, p["k_weight"], prec).reshape(s, z["kv_heads"], hd)
    v = _dense(h, p["v_weight"], prec).reshape(s, z["kv_heads"], hd)
    q = rotary(_rms(q, p["q_norm_gamma"], z["eps"]), z["theta"])
    k = rotary(_rms(k, p["k_norm_gamma"], z["eps"]), z["theta"])
    group = z["heads"] // z["kv_heads"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    sc = einsum("qhd,khd->hqk", q, k, prec) * hd ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v, prec)
    return _dense(att.reshape(s, z["heads"] * hd), p["o_weight"], prec)


def _short_conv(h, p, z, prec):
    """(s, hidden) -> (s, hidden): the gated short convolution as ``K``
    shifted products."""
    s, K = h.shape[0], z["conv_k"]
    b, c, x = jnp.split(_dense(h, p["in_proj_weight"], prec), 3, axis=-1)
    u = jnp.pad(b * x, ((K - 1, 0), (0, 0)))
    w = p["conv_weight"].astype(jnp.float32)  # column K-1: the current token
    conv = sum(u[k:k + s] * w[:, k] for k in range(K))
    return _dense(c * conv, p["out_proj_weight"], prec)


def route(g, p, z, prec):
    """Rows ``g`` (s, hidden) -> picks (s, k) and their weights (s, k)."""
    scores = jax.nn.sigmoid(_dense(g, p["router_weight"], prec))
    _, picks = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                             z["top_k"])
    w = jnp.take_along_axis(scores, picks, axis=-1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return picks, w * z["routed_scale"]


def _experts(g, p, z, prec, fault=None):
    """(s, hidden) -> (s, hidden): every held expert over every row, then
    the dense combination.  ``fault`` (a control's): ``fp8`` the two
    products in float8, ``rotated`` every pick sent to the next expert,
    ``zeroed`` the layer adds nothing."""
    picks, w = route(g, p, z, prec)
    if fault == "zeroed":
        return jnp.zeros_like(g)
    if fault == "rotated":
        picks = (picks + 1) % z["experts"]
    if fault == "fp8":
        prec = "fp8"
    combine = jnp.sum(jax.nn.one_hot(picks, z["experts"], dtype=jnp.float32)
                      * w[..., None], axis=1)  # (s, E), zero off the picks
    combine = combine[:, z["first"]:z["first"] + z["held"]]

    def expert(weights):
        w13, w2 = weights
        g1, g3 = jnp.split(einsum("sk,kn->sn", g, w13, prec), 2, axis=-1)
        return einsum("sk,kn->sn", jax.nn.silu(g1) * g3, w2, prec)

    every = jax.lax.map(expert, (p["experts_w13"], p["experts_w2"]))
    return jnp.einsum("se,esh->sh", combine, every,
                      precision=jax.lax.Precision.HIGHEST)


def _layer(x, p, kind, dense, z, prec, fault=None):
    mixer = _attention if kind == "full_attention" else _short_conv
    x = x + mixer(_rms(x, p["norm1_gamma"], z["eps"]), p, z, prec)
    g = _rms(x, p["norm2_gamma"], z["eps"])
    if not dense:
        return x + _experts(g, p, z, prec, fault)
    g1, g3 = jnp.split(_dense(g, p["mlp_in_weight"], prec), 2, axis=-1)
    return x + _dense(jax.nn.silu(g1) * g3, p["mlp_out_weight"], prec)


def control(prec):
    """``"bf16"`` -> ("bf16", None, None); ``"bf16+experts-fp8"`` ->
    ("bf16", "experts", "fp8"): the rest of the model at the first
    precision, and a fault (``fp8``, ``rotated``, ``zeroed``: see
    :func:`_experts`) in every expert layer (``experts``) or in one
    (``layer9``)."""
    rest, _, fault = prec.partition("+")
    if not fault:
        return rest, None, None
    where, _, what = fault.partition("-")
    if what not in ("fp8", "rotated", "zeroed"):
        raise ValueError("unknown control %r" % prec)
    return rest, where, what


def make_scorer(cfg, layers, length, prec="f32"):
    """(params, tokens (1, length)) -> logits (length, V), float32.  One
    jitted call a layer kind, the layers in a Python loop.  ``prec`` is a
    precision of the matrix products or a control (:func:`control`)."""
    z = sizes(cfg, layers)
    cold = {k: v for k, v in z.items() if k != "types"}
    prec, where, fault = control(prec)

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def layer(x, p, kind, dense, fault):
        return _layer(x, p, kind, dense, cold, prec, fault)

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def head(x, gamma, table):
        return _dense(_rms(x, gamma, z["eps"]), table, prec)

    def score(params, tokens):
        x = embed(params["tok_embed_weight"], jnp.asarray(tokens)[0])
        for i, kind in enumerate(z["types"]):
            pre = "layer%d_" % i
            x = layer(x, {k[len(pre):]: v for k, v in params.items()
                          if k.startswith(pre)}, kind, i < z["dense"],
                      fault if where in ("experts", "layer%d" % i) else None)
        return head(x, params["norm_f_gamma"], params["tok_embed_weight"])

    return score
