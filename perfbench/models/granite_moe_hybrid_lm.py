"""Hybrid state-space / attention decoder LM with routed experts beside a
shared MLP in every layer, in plain jax.numpy: the reference family of
``granitemoehybrid`` configurations WITH experts (IBM Granite 4.0-H Small).
Imports nothing of the program under test.

For layer ``l`` (``layer_types[l]`` is ``mamba`` or ``attention``), rows
``x``, ``m = residual_multiplier``, no bias but the convolution's::

    h0     = embedding_multiplier * E[ids]
    a      = x + m * Mixer_l(RMSNorm(x; norm1))
    g      = RMSNorm(a; norm2)
    x'     = a + m * (Routed(g) + Shared(g))
    logits = RMSNorm(x; norm_f) E^T / logits_scaling          (E is tied)

    Shared(g) = W_out (silu(W1 g) * W3 g)            shared_intermediate_size
    z      = W_r g                       float32, (rows, E), no bias
    I      = top_k(z)                    the k largest LOGITS
    w_i    = exp(z_i - max_I z) / sum_{j in I} exp(z_j - max_I z),   i in I
             (the softmax over the picked logits: no eps, no scale; equal
             to the softmax over all E renormalised over the picks)
    Routed(g) = sum_{i in I, i held here} w_i W2_i (silu(W1_i g) * W3_i g)
                                                     intermediate_size wide

The mixers are the family's without experts (``perfbench/models/
hybrid_lm.py``, whose docstring has their equations): grouped-query
attention with no positional encoding, and the Mamba-2 recurrence token by
token, written out here once more so that a control can fault its state.

Everything is float32 at ``highest`` matmul precision (or, for a control,
the matrix products at a stated lower precision and a fault beside:
:func:`control`) from the weights as they were seeded.  EVERY held expert is
computed for EVERY row and the results are combined by the dense (rows,
held) weight matrix that is zero off the picks; no chunks, no cache, no
batching.  One layer is one jitted call (its experts one at a time inside
it), so a layer's weights are upcast one layer, one expert, at a time.

A configuration states a share of the experts (``first_expert``, and
``num_local_experts`` the count HELD; ``num_local_experts_published`` the
router's width): the router keeps its width and its picks a row, the held
experts' part of the sum is computed and what the others would add is left
out; the shared MLP is computed whole.

Departures from the published description are in ``make_weights`` (the
published weights are not used) and under ``assumed`` in the configuration's
file.  Parameter names and layouts (``[out, in]`` matrices but for the
stacked experts, which are ``[expert, in, out]``):

  tok_embed_weight (V, H)               norm_f_gamma (H,)
  layer{i}_norm1_gamma, _norm2_gamma (H,)
  attention, mamba:  as ``perfbench/models/hybrid_lm.py``
  experts:    layer{i}_router_weight (E, H), _experts_w13 (held, H, 2F)
              [W1 | W3], _experts_w2 (held, F, H), _shared_in_weight
              (2S, H) [W1 | W3], _shared_out_weight (H, S)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import hybrid_lm as _mixers
from .hybrid_lm import _attention, _dense, _rms, einsum
from .precision import seed_key


def sizes(cfg, layers=None):
    """The sizes of a config dict, under this file's names (the mixers'
    under ``perfbench/models/hybrid_lm.py``'s)."""
    held = int(cfg["num_local_experts"])
    return dict(
        _mixers.sizes(cfg, layers), held=held,
        experts=int(cfg.get("num_local_experts_published", held)),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        expert_inter=int(cfg["intermediate_size"]),
        shared=int(cfg["shared_intermediate_size"]))


_FF = ("mlp_in_weight", "mlp_out_weight")


def param_shapes(cfg, layers=None):
    z = sizes(cfg, layers)
    h, f, s = z["hidden"], z["expert_inter"], z["shared"]
    shapes = {k: v for k, v in _mixers.param_shapes(cfg, layers).items()
              if not k.endswith(_FF)}
    for i in range(len(z["types"])):
        pre = "layer%d_" % i
        shapes.update({pre + "router_weight": (z["experts"], h),
                       pre + "experts_w13": (z["held"], h, 2 * f),
                       pre + "experts_w2": (z["held"], f, h),
                       pre + "shared_in_weight": (2 * s, h),
                       pre + "shared_out_weight": (h, s)})
    return shapes


def n_params(cfg, layers=None):
    return sum(int(np.prod(s)) for s in param_shapes(cfg, layers).values())


# The mixers, the norms and the embedding are seeded as the family without
# experts seeds them (``hybrid_lm._seeded_leaf``: N(0, 0.02), the mixers'
# output projections N(0, 0.1), the recurrence's own initialisation).  The
# feed-forward's kinds are drawn by fan-in:
#
# * first matrices (the shared MLP's, an expert's ``[W1 | W3]``) and the
#   router N(0, (IN_GAIN / sqrt(H))^2): 0.02 at the published hidden size,
#   what the family's N(0, 0.02) is there, and the same O(1) rows at a
#   test's toy widths.  The router's logits then have a deviation of 1.28
#   whatever the width, so the ten picked of 72 weigh about 0.25 down to
#   0.05: neither uniform nor one-hot (readings in the cell's limits file);
# * second matrices N(0, (OUT_GAIN / sqrt(F))^2) with OUT_GAIN = 0.1 x
#   sqrt(8192): what the dense MLP of the family's sibling (Granite 4.0-H
#   Micro: N(0, 0.1) at its width 8192) writes into the residual stream an
#   element, at this model's narrower 1536 and 768: the feed-forward then
#   adds about what a mixer adds, and a fault in it moves the logits.
#
# A layer's routed experts share a matrix: expert ``e`` is EXPERTS_OWN x its
# own draw + sqrt(1 - EXPERTS_OWN^2) x a draw common to the layer, in both
# of its matrices (``lfm2_moe_lm``: a near-tie in the router's logits picks
# another expert in bfloat16 than in float32, and the comparison that
# decides ``correct`` takes the worst served token).  Expert ``e``'s own
# draw is keyed by ``e`` itself, its PUBLISHED index, so a share's stacked
# leaf is the whole layer's slice (tests/test_granite_moe_lm.py).
# ROUTED_OUT scales a routed expert's second matrix (``latent_moe_lm``'s
# remedy for a held / absent near-tie that moves a whole pick): 1.0, not
# used, because here the pick a near-tie moves is the LEAST of ten softmax
# weights (about 0.05), where the latent cell's sigmoid weights were near
# uniform (0.31 each); the limits file has the readings that say so.
IN_GAIN = 1.28
OUT_GAIN = 0.1 * 8192 ** 0.5
EXPERTS_OWN = 0.1
ROUTED_OUT = 1.0
_FANIN = ("router_weight", "shared_in_weight", "shared_out_weight")
_STACKED = ("experts_w13", "experts_w2")


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _seeded_leaf(key, i, kind, shape, dtype, first=0):
    """Leaf ``i`` (in the order of the sorted names) of the feed-forward's
    seeded weights; ``kind`` is its name without the layer."""
    k = jax.random.fold_in(key, i)
    if kind in _FANIN:  # [out, in]
        gain = OUT_GAIN if kind == "shared_out_weight" else IN_GAIN
        std = gain / np.sqrt(shape[-1])
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    # [expert, in, out]
    std = (OUT_GAIN * ROUTED_OUT if kind == "experts_w2" else IN_GAIN) \
        / np.sqrt(shape[1])
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
    default device (see ``IN_GAIN`` .. ``ROUTED_OUT``).  Every leaf is
    rounded to the weights' dtype; the reference upcasts what it is
    given."""
    z = sizes(cfg, layers)
    shapes = param_shapes(cfg, layers)
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    key = seed_key(seed)
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes.items())):
        kind = name.split("_", 1)[1] if name.startswith("layer") else name
        if kind in _FANIN + _STACKED:
            out[name] = _seeded_leaf(key, i, kind, shp, dtype, z["first"])
        else:
            out[name] = _mixers._seeded_leaf(key, i, kind, shp, dtype)
    return out


def _mamba(h, p, z, prec, fault=None):
    """(s, hidden) -> (s, hidden): the plain recurrence, token by token, as
    ``hybrid_lm._mamba``.  ``fault`` (a control's): ``bf16`` the recurrent
    state rounded to bfloat16 after every token, as a state plane kept in
    bfloat16 would hold it."""
    s, inner, n = h.shape[0], z["inner"], z["state"]
    heads, hd, K = z["ssm_heads"], z["ssm_hd"], z["conv_k"]
    zxbcdt = _dense(h, p["in_proj_weight"], prec)
    gate, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:inner + z["conv_dim"]]
    dt = zxbcdt[:, inner + z["conv_dim"]:]
    # causal depthwise convolution: column K-1 multiplies the current token
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    w = p["conv_weight"].astype(jnp.float32)
    conv = sum(xp[k:k + s] * w[:, k] for k in range(K)) \
        + p["conv_bias"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, heads, hd)
    B, C = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    D = p["D"].astype(jnp.float32)

    def token(S, row):
        x_t, B_t, C_t, dt_t = row
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        if fault == "bf16":
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, einsum("hpn,n->hp", S, C_t, prec) + D[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), jnp.float32),
                        (x, B, C, dt))
    y = _rms(y.reshape(s, inner) * jax.nn.silu(gate), p["gate_norm_gamma"],
             z["eps"])
    return _dense(y, p["out_proj_weight"], prec)


def route(g, p, z, prec, fault=None):
    """Rows ``g`` (s, hidden) -> picks (s, k) and their weights (s, k): the
    ``k`` largest logits, weighted by the softmax over them, written out.
    ``fault`` ``sigmoid``: scored the way the program's other routed
    families are (the ``k`` largest sigmoid scores, normalised by their sum
    + 1e-6): the fault this rule exists to avoid."""
    logits = _dense(g, p["router_weight"], prec)
    if fault == "sigmoid":
        w, picks = jax.lax.top_k(jax.nn.sigmoid(logits), z["top_k"])
        return picks, w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    picked, picks = jax.lax.top_k(logits, z["top_k"])
    e = jnp.exp(picked - jnp.max(picked, -1, keepdims=True))
    return picks, e / jnp.sum(e, -1, keepdims=True)


def routed(g, p, z, prec, fault=None):
    """(s, hidden) -> (s, hidden): the held experts' part of the layer:
    every held expert over every row, then the dense combination.
    ``fault``: ``sigmoid`` (:func:`route`), ``fp8`` the two products in
    float8, ``zeroed`` the routed part adds nothing."""
    if fault == "zeroed":
        return jnp.zeros_like(g)
    picks, w = route(g, p, z, prec, fault)
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
    """(s, hidden) -> (s, hidden): the MLP every row takes (zeros under the
    fault ``zeroed``)."""
    if fault == "zeroed":
        return jnp.zeros_like(g)
    g1, g3 = jnp.split(_dense(g, p["shared_in_weight"], prec), 2, axis=-1)
    return _dense(jax.nn.silu(g1) * g3, p["shared_out_weight"], prec)


def _layer(x, p, kind, z, prec, faults):
    """``faults``: {"router" | "experts" | "shared" | "state": the fault
    there}."""
    h = _rms(x, p["norm1_gamma"], z["eps"])
    if kind == "attention":
        h = _attention(h, p, z, prec)
    else:
        h = _mamba(h, p, z, prec, faults.get("state"))
    x = x + z["res_mult"] * h
    g = _rms(x, p["norm2_gamma"], z["eps"])
    ff = shared(g, p, z, prec, faults.get("shared")) + routed(
        g, p, z, prec, faults.get("router") or faults.get("experts"))
    return x + z["res_mult"] * ff


_FAULTS = {"router": ("sigmoid",), "shared": ("zeroed",),
           "experts": ("fp8", "zeroed"), "state": ("bf16",)}


def control(prec):
    """``"bf16"`` -> ("bf16", None, None); ``"bf16+router-sigmoid"`` ->
    ("bf16", "router", "sigmoid"): the rest of the model at the first
    precision, and a fault in every layer's router (``router``:
    ``sigmoid``), shared MLP (``shared``: ``zeroed``), routed part
    (``experts``: ``fp8``, ``zeroed``) or recurrent state (``state``:
    ``bf16``), or in the routed part of one layer (``layer3``: as
    ``experts``)."""
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
    cold = {k: v for k, v in z.items() if k != "types"}
    prec, where, what = control(prec)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def layer(x, p, kind, faults):
        return _layer(x, p, kind, cold, prec, dict(faults))

    @jax.jit
    def embed(table, tokens):
        return z["emb_mult"] * table[tokens].astype(jnp.float32)

    @jax.jit
    def head(x, gamma, table):
        return _dense(_rms(x, gamma, z["eps"]), table, prec) \
            / z["logits_scaling"]

    def faults(i):
        if where in _FAULTS:
            return ((where, what),)
        return (("experts", what),) if where == "layer%d" % i else ()

    def score(params, tokens):
        x = embed(params["tok_embed_weight"], jnp.asarray(tokens)[0])
        for i, kind in enumerate(z["types"]):
            pre = "layer%d_" % i
            x = layer(x, {k[len(pre):]: v for k, v in params.items()
                          if k.startswith(pre)}, kind, faults(i))
        return head(x, params["norm_f_gamma"], params["tok_embed_weight"])

    return score
