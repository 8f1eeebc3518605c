"""Decoder LM whose layers attend either to every earlier token or to a
sliding window, with another count of query heads and another rotation in
each kind, a gate a head on the attention's output, and a shared expert
beside routed experts, in plain jax.numpy: the reference family of ``laguna``
configurations (poolside Laguna-XS.2).  Imports nothing of the program under
test.

For layer ``l`` of kind ``k`` in {full_attention, sliding_attention}, ``H_k``
query heads over ``kv`` K/V heads of ``d`` features (``G_k = H_k / kv``), row
``x_t`` at position ``t``, no bias anywhere::

    x0  = E[ids]
    h   = RMSNorm(x; norm1)
    q_i = W_q[i] h  (i < H_k),   k_j = W_k[j] h,   v_j = W_v[j] h  (j < kv)
    full:     the first r = partial_rotary_factor d features of a head
              rotated (rotate-half over those r; the rest pass) by angles
              t f_m, m < r / 2, YaRN's table (:func:`yarn_table`), cos and
              sin both times attention_factor
    sliding:  all d features rotated, f_m = theta^(-2m / d), no factor
    s_ij(t, u) = q_i(t) . k_{i // G_k}(u) / sqrt(d)
    full:     u <= t                sliding:  t - window < u <= t
    o_i = sum_u softmax_u(s_i)(t, u) v_{i // G_k}(u)
    g   = sigmoid(W_g h)  (W_g (H_k, hidden): ONE gate a head),  o_i <- g_i o_i
    a   = x + W_o [o_0 | ... | o_{H_k - 1}]
    h2  = RMSNorm(a; norm2)
    dense layers:   x' = a + W_down(silu(W_gate h2) * W_up h2)
    sparse layers:  z = sigmoid(W_r h2) (float32, E outputs), P the top_k
                    largest, w_e = scale z_e / (sum_P z + 1e-20);
                    x' = a + sum_{e in P, held here} w_e E_e(h2) + Shared(h2)
    logits = W_head RMSNorm(x_last; norm_f)            (the head is untied)

The whole sequence at once under an explicit ``(t, u)`` mask: no ring, no
pages, no blocks of queries; a K/V head's group of query heads at a time, so
that ``H x L x L`` scores never exist at once.  Everything is float32 at
``highest`` matmul precision (or, for a control, the matrix products at a
stated lower precision, and a fault beside: :func:`control`) from the
weights as they were seeded.  EVERY held expert is computed for EVERY row
(``latent_moe_lm.routed``).  One layer is one jitted call.

A configuration states a share of the experts (``first_expert``, and
``num_experts`` the count HELD; ``num_experts_published`` the router's
width): the router keeps its width and its picks a row, the held experts'
part of the sum is computed and what the others would add is left out; the
shared expert is computed whole.

Departures from the published description are in ``make_weights`` (the
published weights are not used) and under ``assumed`` in the configuration's
file.  Parameter names and layouts (``[out, in]`` matrices but for the
stacked experts, which are ``[expert, in, out]``):

  tok_embed_weight, lm_head_weight (V, H)       norm_f_gamma (H,)
  layer{i}_norm1_gamma, _norm2_gamma (H,)
  attention:  layer{i}_q_weight (H_k*d, H), _k_weight, _v_weight (kv*d, H),
              _gate_weight (H_k, H), _o_weight (H, H_k*d)
  dense:      layer{i}_mlp_in_weight (2I, H) [W_gate | W_up],
              _mlp_out_weight (H, I)
  experts:    layer{i}_router_weight (E, H), _experts_w13 (held, H, 2F)
              [W1 | W3], _experts_w2 (held, F, H), _shared_in_weight (2S, H)
              [W1 | W3], _shared_out_weight (H, S)
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .hybrid_lm import _dense, _rms, einsum
from .latent_moe_lm import _gated, routed, shared
from .precision import seed_key

FULL, SLIDING = "full_attention", "sliding_attention"


def sizes(cfg, layers=None):
    """The sizes of a config dict, under this file's names (the expert
    layer's as ``latent_moe_lm`` names them: its functions compute it)."""
    n = int(cfg["num_hidden_layers"] if layers is None else layers)
    held = int(cfg["num_experts"])
    rope = cfg["rope_parameters"]
    full, sliding = rope[FULL], rope[SLIDING]
    hd = int(cfg["head_dim"])
    return dict(
        vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
        layers=n, types=tuple(cfg["layer_types"][:n]),
        heads=tuple(int(h) for h in cfg["num_attention_heads_per_layer"][:n]),
        sparse=tuple(t == "sparse" for t in cfg["mlp_layer_types"][:n]),
        kv_heads=int(cfg["num_key_value_heads"]), head_dim=hd,
        window=int(cfg["sliding_window"]),
        inter=int(cfg["intermediate_size"]), held=held,
        experts=int(cfg.get("num_experts_published", held)),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_inter=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["shared_expert_intermediate_size"]),
        norm_topk=True, routed_scale=float(cfg["moe_routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        full_rot=int(round(float(full["partial_rotary_factor"]) * hd)),
        full_theta=float(full["rope_theta"]),
        yarn=dict(factor=float(full["factor"]),
                  original=int(full["original_max_position_embeddings"]),
                  beta_fast=float(full["beta_fast"]),
                  beta_slow=float(full["beta_slow"]),
                  attention_factor=float(full["attention_factor"])),
        sliding_theta=float(sliding["rope_theta"]))


def param_shapes(cfg, layers=None):
    z = sizes(cfg, layers)
    h, hd, kv = z["hidden"], z["head_dim"], z["kv_heads"]
    shapes = {"tok_embed_weight": (z["vocab"], h),
              "lm_head_weight": (z["vocab"], h), "norm_f_gamma": (h,)}
    for i in range(z["layers"]):
        pre, heads = "layer%d_" % i, z["heads"][i]
        shapes.update({
            pre + "norm1_gamma": (h,), pre + "norm2_gamma": (h,),
            pre + "q_weight": (heads * hd, h), pre + "k_weight": (kv * hd, h),
            pre + "v_weight": (kv * hd, h), pre + "gate_weight": (heads, h),
            pre + "o_weight": (h, heads * hd)})
        if not z["sparse"][i]:
            shapes.update({pre + "mlp_in_weight": (2 * z["inter"], h),
                           pre + "mlp_out_weight": (h, z["inter"])})
        else:
            f = z["expert_inter"]
            shapes.update({
                pre + "router_weight": (z["experts"], h),
                pre + "experts_w13": (z["held"], h, 2 * f),
                pre + "experts_w2": (z["held"], f, h),
                pre + "shared_in_weight": (2 * z["shared"], h),
                pre + "shared_out_weight": (h, z["shared"])})
    return shapes


def n_params(cfg, layers=None):
    return sum(int(np.prod(s)) for s in param_shapes(cfg, layers).values())


# Seeding, as ``latent_moe_lm``'s and for its reasons: matrices (the
# embedding, the head, the gate and the stacked experts too) N(0, (GAIN /
# sqrt(fan_in))^2), norm gains 1 + N(0, 0.02); a layer's routed experts each
# EXPERTS_OWN x their own draw (keyed by the expert's PUBLISHED index, so a
# share is a slice of the whole layer) + sqrt(1 - EXPERTS_OWN^2) x a draw
# common to the layer, in both matrices; the shared expert drawn alone.
#
# A routed expert's SECOND matrix is drawn at ROUTED_OUT x the fan-in scale:
# a tenth, where the latent cell's is a half.  Why: this chip holds 32 of 256
# experts, so a near-tie of the 8th and 9th score that picks a held expert in
# bfloat16 and an absent one in float32 (or the other way) adds or removes a
# WHOLE pick, about two rows of a hundred a layer, here in 19 layers and with
# no sandwich norm to bound a branch: the residual stream is 0.4 sqrt(layer)
# wide, a pick at 0.5 is 0.06, so a flip in layer 1 is 15 % of the stream and
# moves a logit by 0.5.  At 0.5 the program's worst of 1,600 served tokens
# read 0.76 on the chip and the reference computed in bfloat16 itself 1.14
# (4,745 tokens of a whole run: 1.50), where top-two margins are 0.15; at 0.1
# they read 0.14 and 0.12 and no control moved (float8 2.0-2.8, no window
# 1.5-2.0: PERF.md section 6, PR 51).  What that costs is in the cell's limits
# file: a fault confined to the routed experts is worth a tenth.
GAIN = 0.9
EXPERTS_OWN = 0.1
ROUTED_OUT = 0.1
_STACKED = ("experts_w13", "experts_w2")


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _seeded_leaf(key, i, kind, shape, dtype, first, out):
    """Leaf ``i`` (in the order of the sorted names) of the seeded weights;
    ``kind`` is its name without the layer, ``out`` the routed experts'
    ``ROUTED_OUT``."""
    k = jax.random.fold_in(key, i)
    if kind.endswith("_gamma"):
        return (1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if kind not in _STACKED:  # [out, in]
        std = GAIN / np.sqrt(shape[-1])
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    std = GAIN / np.sqrt(shape[1])  # [expert, in, out]
    if kind == "experts_w2":
        std = std * out
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
    default device leaf by leaf (see ``GAIN``, ``EXPERTS_OWN`` and
    ``ROUTED_OUT``).
    ``W_g`` is a matrix like any other: its fan-in is the hidden width and
    its input a normed row, so the gate's logits have a deviation near 0.9
    and the gates spread over (0.1, 0.9): a gate that sits at 0.5 would be a
    constant the check cannot see.  Every leaf is rounded to the weights'
    dtype; the reference upcasts what it is given."""
    z = sizes(cfg, layers)
    shapes = param_shapes(cfg, layers)
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    key = seed_key(seed)

    def kind(name):
        return name.split("_", 1)[1] if name.startswith("layer") else name

    return {name: _seeded_leaf(key, i, kind(name), shp, dtype, z["first"],
                               float(ROUTED_OUT))
            for i, (name, shp) in enumerate(sorted(shapes.items()))}


def yarn_table(theta, r, yarn):
    """The ``r / 2`` frequencies of the scaled rotation over ``r`` features,
    float64: ``e_m = theta^(-2m / r)``; ``c(b) = r ln(original / (2 pi b)) /
    (2 ln theta)``; ``lo = max(floor(c(beta_fast)), 0)``, ``hi =
    min(ceil(c(beta_slow)), r - 1)``; ``ramp_m = clip((m - lo) / (hi - lo),
    0, 1)`` (``hi + 0.001`` if ``hi = lo``); ``f_m = (e_m / factor) ramp_m +
    e_m (1 - ramp_m)``.  Returns (f, lo, hi)."""
    m = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2.0 * m / r)

    def c(b):
        return r * math.log(yarn["original"] / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    lo = max(math.floor(c(yarn["beta_fast"])), 0)
    hi = min(math.ceil(c(yarn["beta_slow"])), r - 1)
    ramp = np.clip((m - lo) / ((hi + 0.001 if hi == lo else hi) - lo), 0, 1)
    return e / yarn["factor"] * ramp + e * (1 - ramp), lo, hi


def rotate(x, freqs, factor=1.0):
    """``x`` (s, heads, d) at positions 0..s-1: the first ``2 len(freqs)``
    features in two halves, feature ``i`` paired with ``i + len(freqs)``, by
    the angle ``position * freqs[i]``, cosine and sine times ``factor``; the
    rest pass."""
    half = len(freqs)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]
    cos = (factor * jnp.cos(ang))[:, None, :]
    sin = (factor * jnp.sin(ang))[:, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def _attention(h, p, z, kind, prec, fault=None):
    """(s, hidden) -> (s, hidden): gated grouped-query attention of one
    kind.  ``fault`` (a control's): ``nowindow`` a sliding layer attends to
    every earlier token, ``plainangles`` a full layer's table is
    ``theta^(-2m / r)`` (the factor stays), ``nogate`` every gate is 1,
    ``heads48`` the last 16 query heads of a sliding layer add nothing."""
    s, hd, kv = h.shape[0], z["head_dim"], z["kv_heads"]
    heads = p["q_weight"].shape[0] // hd
    group = heads // kv
    q = _dense(h, p["q_weight"], prec).reshape(s, heads, hd)
    k = _dense(h, p["k_weight"], prec).reshape(s, kv, hd)
    v = _dense(h, p["v_weight"], prec).reshape(s, kv, hd)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]   # t - u
    mask = ahead >= 0
    if kind == FULL:
        r = z["full_rot"]
        freqs = z["full_theta"] ** (-2.0 * np.arange(r // 2) / r) \
            if fault == "plainangles" \
            else yarn_table(z["full_theta"], r, z["yarn"])[0]
        q, k = (rotate(x, freqs, z["yarn"]["attention_factor"])
                for x in (q, k))
    else:
        freqs = z["sliding_theta"] ** (-2.0 * np.arange(hd // 2) / hd)
        q, k = rotate(q, freqs), rotate(k, freqs)
        if fault != "nowindow":
            mask = mask & (ahead < z["window"])

    def attend(operands):  # one K/V head and its group of query heads
        qg, kj, vj = operands  # (s, group, d), (s, d), (s, d)
        kj, vj = (jnp.repeat(x[:, None], group, axis=1) for x in (kj, vj))
        sc = einsum("qgd,kgd->gqk", qg, kj, prec) * hd ** -0.5
        sc = jnp.where(mask, sc, -jnp.inf)
        return einsum("gqk,kgd->qgd", jax.nn.softmax(sc, -1), vj, prec)

    att = jax.lax.map(attend, (
        jnp.moveaxis(q.reshape(s, kv, group, hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    att = jnp.moveaxis(att, 0, 1).reshape(s, heads, hd)
    if fault != "nogate":
        att = att * jax.nn.sigmoid(_dense(h, p["gate_weight"],
                                          prec))[..., None]
    if fault == "heads48" and kind == SLIDING:
        att = att.at[:, 48:].set(0.0)
    return _dense(att.reshape(s, heads * hd), p["o_weight"], prec)


def _layer(x, p, kind, sparse, z, prec, faults):
    """``faults``: {"attn" | "experts" | "shared": the fault there}."""
    x = x + _attention(_rms(x, p["norm1_gamma"], z["eps"]), p, z, kind, prec,
                       faults.get("attn"))
    g = _rms(x, p["norm2_gamma"], z["eps"])
    if not sparse:
        return x + _gated(g, p["mlp_in_weight"], p["mlp_out_weight"], prec)
    return x + shared(g, p, z, prec, faults.get("shared")) \
        + routed(g, p, z, prec, faults.get("experts"))


_FAULTS = {"attn": ("nowindow", "plainangles", "nogate", "heads48"),
           "shared": ("zeroed",), "experts": ("fp8", "rotated", "zeroed")}


def control(prec):
    """``"bf16"`` -> ("bf16", None, None); ``"bf16+attn-nowindow"`` ->
    ("bf16", "attn", "nowindow"): the rest of the model at the first
    precision, and a fault in every layer's attention (``attn``:
    ``nowindow``, ``plainangles``, ``nogate``, ``heads48``), in every sparse
    layer's shared expert (``shared``: ``zeroed``), or in the routed part of
    every sparse layer (``experts``) or of one (``layer10``): ``fp8``,
    ``rotated``, ``zeroed``."""
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
    jitted call a layer shape, the layers in a Python loop.  ``prec`` is a
    precision of the matrix products or a control (:func:`control`)."""
    z = sizes(cfg, layers)
    prec, where, what = control(prec)

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def layer(x, p, kind, sparse, faults):
        return _layer(x, p, kind, sparse, z, prec, dict(faults))

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
                          if k.startswith(pre)}, z["types"][i],
                      z["sparse"][i], faults(i))
        return head(x, params["norm_f_gamma"], params["lm_head_weight"])

    return score
