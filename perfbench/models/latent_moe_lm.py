"""Latent-attention decoder LM with a shared expert beside routed experts and
sandwich norms, in plain jax.numpy: the reference family of
``pangu_ultra_moe`` configurations (openPangu-Ultra-MoE-718B).  Imports
nothing of the program under test.

For layer ``l``, rows ``x`` of width ``H``, no bias anywhere::

    x0 = E[ids]                                        (no multiplier)
    a  = x + RMSNorm(Attn(RMSNorm(x; norm1)); post_norm1)
    x' = a + RMSNorm(FF_l(RMSNorm(a; norm2)); post_norm2)
    logits = W_head RMSNorm(x_last; norm_f)            (the head is untied)

    Attn:  c_q         = RMSNorm(W_qa h; q_a_norm)              (q_lora_rank)
           [q_n | q_r] = W_qb c_q      a head (nope | rope); q_r rotated
           [c | k_r]   = W_kva h       (kv_lora_rank | rope)
           c = RMSNorm(c; kv_a_norm);  k_r rotated, ONE for all heads
           [k_n | v]   = W_kvb c       a head (nope | v)
           score_ij    = (q_n,i . k_n,j + q_r,i . k_r,j) / sqrt(nope + rope)
           out = W_o concat_heads(softmax_causal(score) v)
           (rotation: feature i paired with i + rope/2, angle position *
           theta^(-2i/rope))
    FF, l <  first_k_dense_replace:  W_out(silu(g1) * g3), [g1 | g3] = W_in g
    FF, l >= first_k_dense_replace:
           s = sigmoid(W_g g)   (E scores, float32; NO selection bias, no
                                 groups);  I = top_k(s)
           w_i = routed_scaling_factor * s_i / (sum_{j in I} s_j + 1e-20)
           Shared(g) + sum_{i in I, held here} w_i Expert_i(g)
           (Shared and every Expert_i: W2 (silu(W1 g) * W3 g), width
           moe_intermediate_size; Shared n_shared_experts times as wide)

The attention is the EXPANDED form only (every head's K and V made from the
latent rows), a block of heads at a time; there is no cache and no absorbed
form here: the program's decode path runs that, and is held to this.

Everything is float32 at ``highest`` matmul precision (or, for a control,
the matrix products at a stated lower precision, and a fault beside:
:func:`control`) from the weights as they were seeded.  EVERY held expert is
computed for EVERY row and the results are combined by the dense (rows,
held) weight matrix that is zero off the picks.  One layer is one jitted
call (its experts and its blocks of heads one at a time inside it), so a
layer's weights are upcast one layer, one expert, at a time.

A configuration states a share of the experts (``first_expert``, and
``n_routed_experts`` the count HELD; ``n_routed_experts_published`` the
router's width): the router keeps its width and its picks a row, the held
experts' part of the sum is computed and what the others would add is left
out; the shared expert is computed whole.  The multi-token-prediction module
(``num_nextn_predict_layers``) is a block the forward pass never reads: it
is left out.

Departures from the published description are in ``make_weights`` (the
published weights are not used) and under ``assumed`` in the configuration's
file.  Parameter names and layouts (``[out, in]`` matrices but for the
stacked experts, which are ``[expert, in, out]``):

  tok_embed_weight, lm_head_weight (V, H)       norm_f_gamma (H,)
  layer{i}_norm1_gamma, _post_norm1_gamma, _norm2_gamma, _post_norm2_gamma
  attention:  layer{i}_q_a_weight (q_rank, H), _q_a_norm_gamma (q_rank,),
              _q_b_weight (heads*(nope+rope), q_rank), _kv_a_weight
              (rank+rope, H), _kv_a_norm_gamma (rank,), _kv_b_weight
              (heads*(nope+v), rank) a head's rows [k_n | v], _o_weight (H,
              heads*v)
  dense:      layer{i}_mlp_in_weight (2I, H) [W1 | W3], _mlp_out_weight (H, I)
  experts:    layer{i}_router_weight (E, H), _experts_w13 (held, H, 2F)
              [W1 | W3], _experts_w2 (held, F, H), _shared_in_weight (2S, H)
              [W1 | W3], _shared_out_weight (H, S)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .hybrid_lm import _dense, _rms, einsum
from .lfm2_moe_lm import rotary
from .precision import seed_key

ROUTER_EPS = 1e-20
# heads whose scores exist at once
HEAD_BLOCK = 16


def sizes(cfg, layers=None):
    """The sizes of a config dict, under this file's names."""
    n = int(cfg["num_hidden_layers"] if layers is None else layers)
    held = int(cfg["n_routed_experts"])
    return dict(
        vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
        layers=n, heads=int(cfg["num_attention_heads"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), q_rank=int(cfg["q_lora_rank"]),
        rank=int(cfg["kv_lora_rank"]), inter=int(cfg["intermediate_size"]),
        dense=int(cfg["first_k_dense_replace"]), held=held,
        experts=int(cfg.get("n_routed_experts_published", held)),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_inter=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"])
        * int(cfg["moe_intermediate_size"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def param_shapes(cfg, layers=None):
    z = sizes(cfg, layers)
    h, heads = z["hidden"], z["heads"]
    shapes = {"tok_embed_weight": (z["vocab"], h),
              "lm_head_weight": (z["vocab"], h), "norm_f_gamma": (h,)}
    for i in range(z["layers"]):
        pre = "layer%d_" % i
        shapes.update({pre + n + "_gamma": (h,) for n in
                       ("norm1", "post_norm1", "norm2", "post_norm2")})
        shapes.update({
            pre + "q_a_weight": (z["q_rank"], h),
            pre + "q_a_norm_gamma": (z["q_rank"],),
            pre + "q_b_weight": (heads * (z["nope"] + z["rope"]),
                                 z["q_rank"]),
            pre + "kv_a_weight": (z["rank"] + z["rope"], h),
            pre + "kv_a_norm_gamma": (z["rank"],),
            pre + "kv_b_weight": (heads * (z["nope"] + z["v"]), z["rank"]),
            pre + "o_weight": (h, heads * z["v"])})
        if i < z["dense"]:
            shapes.update({pre + "mlp_in_weight": (2 * z["inter"], h),
                           pre + "mlp_out_weight": (h, z["inter"])})
        else:
            f = z["expert_inter"]
            shapes.update({pre + "router_weight": (z["experts"], h),
                           pre + "experts_w13": (z["held"], h, 2 * f),
                           pre + "experts_w2": (z["held"], f, h)})
            if z["shared"]:
                shapes.update({
                    pre + "shared_in_weight": (2 * z["shared"], h),
                    pre + "shared_out_weight": (h, z["shared"])})
    return shapes


def n_params(cfg, layers=None):
    return sum(int(np.prod(s)) for s in param_shapes(cfg, layers).values())


# Matrices (the embedding, the head and the stacked experts too) are drawn
# from N(0, (GAIN / sqrt(fan_in))^2), as ``lfm2_moe_lm`` draws them and for
# its reasons; norm gains 1 + N(0, 0.02).  With sandwich norms every branch
# reaches the residual stream at the size of its post-norm's gain whatever
# its matrices' scale, so no branch needs a scale of its own.
#
# A layer's routed experts share a matrix: expert ``e`` is EXPERTS_OWN x its
# own draw + sqrt(1 - EXPERTS_OWN^2) x a draw common to the layer, in both
# of its matrices (``lfm2_moe_lm``: a near-tie in the router's scores picks
# another expert in bfloat16 than in float32, and the comparison that decides
# ``correct`` takes the worst served token).  Expert ``e``'s own draw is
# keyed by ``e`` itself, so a share's stacked leaf is the whole layer's
# slice without the whole layer ever being made (256 experts of 7680 x 4096
# are 32 GB in float32).  The shared expert is drawn alone.
#
# A routed expert's SECOND matrix is drawn at ROUTED_OUT x the fan-in scale,
# so an expert adds half of what the shared expert adds.  Why: this chip
# holds 16 of 256 experts, so a near-tie between the 8th and 9th score of a
# row that picks a held expert in bfloat16 and an absent one in float32 (or
# the other way) adds or removes a WHOLE pick, which no common part
# softens: about two rows of a hundred a layer (8 % of the served tokens over
# four layers).  At the full scale such a pick is 0.3 of the shared expert's
# output: the program's worst served token read 0.19-0.47 over seven seeds
# on the chip with a tail (fitted to the per-request maxima) that passes
# 0.66 once in fifteen runs, where the float8 control reads 0.92-1.12: no
# limit stands between them for the dozen runs of one check.  Halved, the
# program's tail lies well under the limit and the control does not move
# (the dense parts carry it).  What that costs is in the cell's limits file:
# faults confined to the routed experts are worth half as much too.
GAIN = 0.9
EXPERTS_OWN = 0.1
ROUTED_OUT = 0.5
_STACKED = ("experts_w13", "experts_w2")


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _seeded_leaf(key, i, kind, shape, dtype, first=0):
    """Leaf ``i`` (in the order of the sorted names) of the seeded weights;
    ``kind`` is its name without the layer."""
    k = jax.random.fold_in(key, i)
    if kind.endswith("_gamma"):
        return (1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if kind not in _STACKED:  # [out, in]
        std = GAIN / np.sqrt(shape[-1])
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    std = GAIN / np.sqrt(shape[1])  # [expert, in, out]
    if kind == "experts_w2":
        std = std * ROUTED_OUT
    common = np.sqrt(1 - EXPERTS_OWN ** 2) * jax.random.normal(
        jax.random.fold_in(k, 1), shape[1:], jnp.float32)

    def expert(e):
        own = jax.random.normal(jax.random.fold_in(k, 2 + e), shape[1:],
                                jnp.float32)
        return (std * (EXPERTS_OWN * own + common)).astype(dtype)

    return jax.lax.map(expert, first + jnp.arange(shape[0]))


def make_weights(cfg, seed, layers=None):
    """Seeded weights in the dtype the configuration holds them in
    (``weights_dtype``, bfloat16 unless it says otherwise), made on the
    default device (see ``GAIN``, ``EXPERTS_OWN`` and ``ROUTED_OUT``).  Every leaf is
    rounded to the weights' dtype; the reference upcasts what it is
    given."""
    z = sizes(cfg, layers)
    shapes = param_shapes(cfg, layers)
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    key = seed_key(seed)

    def kind(name):
        return name.split("_", 1)[1] if name.startswith("layer") else name

    return {name: _seeded_leaf(key, i, kind(name), shp, dtype, z["first"])
            for i, (name, shp) in enumerate(sorted(shapes.items()))}


def _attention(h, p, z, prec, fault=None):
    """(s, hidden) -> (s, hidden): causal latent attention, expanded.
    ``fault`` (a control's): ``norope`` the term ``q_r . k_r`` left out of
    the scores, ``nonorm`` the latent ``c`` taken before its norm."""
    s, heads, nope, rope = h.shape[0], z["heads"], z["nope"], z["rope"]
    c_q = _rms(_dense(h, p["q_a_weight"], prec), p["q_a_norm_gamma"],
               z["eps"])
    q = _dense(c_q, p["q_b_weight"], prec).reshape(s, heads, nope + rope)
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], z["theta"])
    ckr = _dense(h, p["kv_a_weight"], prec)
    c = ckr[:, :z["rank"]]
    if fault != "nonorm":
        c = _rms(c, p["kv_a_norm_gamma"], z["eps"])
    k_r = rotary(ckr[:, None, z["rank"]:], z["theta"])[:, 0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    block = max(d for d in range(1, HEAD_BLOCK + 1) if heads % d == 0)
    scale = (nope + rope) ** -0.5

    def attend(operands):
        qn, qr, w = operands  # (s, block, nope | rope), (block * (nope+v), r)
        kv = _dense(c, w, prec).reshape(s, block, nope + z["v"])
        sc = einsum("qhd,khd->hqk", qn, kv[..., :nope], prec)
        if fault != "norope":
            sc = sc + einsum("qhd,kd->hqk", qr, k_r, prec)
        sc = jnp.where(causal, sc * scale, -jnp.inf)
        return einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                      kv[..., nope:], prec)

    def blocks(x):  # (s, heads, d) -> (heads / block, s, block, d)
        return jnp.moveaxis(x.reshape(s, heads // block, block, -1), 1, 0)

    att = jax.lax.map(attend, (
        blocks(q_n), blocks(q_r),
        p["kv_b_weight"].reshape(heads // block, block * (nope + z["v"]),
                                 z["rank"])))
    att = jnp.moveaxis(att, 0, 1).reshape(s, heads * z["v"])
    return _dense(att, p["o_weight"], prec)


def route(g, p, z, prec):
    """Rows ``g`` (s, hidden) -> picks (s, k) and their weights (s, k)."""
    scores = jax.nn.sigmoid(_dense(g, p["router_weight"], prec))
    w, picks = jax.lax.top_k(scores, z["top_k"])
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + ROUTER_EPS)
    return picks, w * z["routed_scale"]


def _gated(g, w_in, w_out, prec):
    g1, g3 = jnp.split(_dense(g, w_in, prec), 2, axis=-1)
    return _dense(jax.nn.silu(g1) * g3, w_out, prec)


def routed(g, p, z, prec, fault=None):
    """(s, hidden) -> (s, hidden): the held experts' part of the layer:
    every held expert over every row, then the dense combination.
    ``fault``: ``fp8`` the two products in float8, ``rotated`` every pick
    sent to the next expert, ``zeroed`` the routed part adds nothing."""
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


def shared(g, p, z, prec, fault=None):
    """(s, hidden) -> (s, hidden): the expert every row takes (zeros where
    the configuration has none, or under the fault ``zeroed``)."""
    if not z["shared"] or fault == "zeroed":
        return jnp.zeros_like(g)
    return _gated(g, p["shared_in_weight"], p["shared_out_weight"], prec)


def _layer(x, p, dense, z, prec, faults):
    """``faults``: {"attn" | "experts" | "shared": the fault there}."""
    h = _attention(_rms(x, p["norm1_gamma"], z["eps"]), p, z, prec,
                   faults.get("attn"))
    x = x + _rms(h, p["post_norm1_gamma"], z["eps"])
    g = _rms(x, p["norm2_gamma"], z["eps"])
    if dense:
        ff = _gated(g, p["mlp_in_weight"], p["mlp_out_weight"], prec)
    else:
        ff = shared(g, p, z, prec, faults.get("shared")) \
            + routed(g, p, z, prec, faults.get("experts"))
    return x + _rms(ff, p["post_norm2_gamma"], z["eps"])


_FAULTS = {"attn": ("norope", "nonorm"), "shared": ("zeroed",),
           "experts": ("fp8", "rotated", "zeroed")}


def control(prec):
    """``"bf16"`` -> ("bf16", None, None); ``"bf16+attn-norope"`` ->
    ("bf16", "attn", "norope"): the rest of the model at the first
    precision, and a fault in every layer's attention (``attn``: ``norope``,
    ``nonorm``), in every expert layer's shared expert (``shared``:
    ``zeroed``), or in the routed part of every expert layer (``experts``)
    or of one (``layer3``): ``fp8``, ``rotated``, ``zeroed``."""
    rest, _, fault = prec.partition("+")
    if not fault:
        return rest, None, None
    where, _, what = fault.partition("-")
    kind = "experts" if where.startswith("layer") else where
    if what not in _FAULTS.get(kind, ()):
        raise ValueError("unknown control %r" % prec)
    return rest, where, what


def make_scorer(cfg, layers, length, prec="f32"):
    """(params, tokens (1, length)) -> logits (length, V), float32.  One
    jitted call a layer kind, the layers in a Python loop.  ``prec`` is a
    precision of the matrix products or a control (:func:`control`)."""
    z = sizes(cfg, layers)
    prec, where, what = control(prec)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def layer(x, p, dense, faults):
        return _layer(x, p, dense, z, prec, dict(faults))

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def head(x, gamma, table):
        return _dense(_rms(x, gamma, z["eps"]), table, prec)

    def faults(i):
        if where in ("attn", "shared", "experts"):
            return ((where, what),)
        return (("experts", what),) if where == "layer%d" % i else ()

    def score(params, tokens):
        x = embed(params["tok_embed_weight"], jnp.asarray(tokens)[0])
        for i in range(z["layers"]):
            pre = "layer%d_" % i
            x = layer(x, {k[len(pre):]: v for k, v in params.items()
                          if k.startswith(pre)}, i < z["dense"], faults(i))
        return head(x, params["norm_f_gamma"], params["lm_head_weight"])

    return score
