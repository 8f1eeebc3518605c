"""Routed expert layer (mixture of experts) and rotary positions: what the
block of ``models/hybrid_lm.py`` needs for a sparse feed-forward and for
rotated queries and keys.

The expert layer, for rows ``g`` (tokens or lanes) of width ``H``, ``E``
experts of inner width ``F``, ``k`` picks a row::

    s   = sigmoid(W_r g)                      float32, (rows, E)
    I   = top_k(s + bias)                     the selection alone sees bias
    w_i = scale * s_i / (sum_{j in I} s_j + 1e-6)   (``normalize``), i in I
    y   = sum_{i in I} w_i * W2_i (silu(W1_i g) * W3_i g)

Two ops, so that a chip that holds a share of the experts routes over all of
them and computes its own part (``first_expert`` and the held count, read
from the stacked weights' leading axis): the parts of every share add up to
the whole layer (tests/test_moe_ops.py).  On one chip there is no exchange,
and nothing stands in for absent chips.

``_contrib_RoutedExperts`` computes every (row, pick) pair once and no
other: the pairs are sorted by expert, each held expert's group goes through
its two products (``jax.lax.ragged_dot`` over the sorted rows: no capacity,
no dropped token, no padding to a capacity), the results go back to their
rows weighted and summed.  A pick outside the held range adds nothing, and
so does a row the router was told is not live (a padded lane of a bucket,
a position past a prompt's length): it picks expert ``E``, which no share
holds.  :func:`experts_formulation` says what the grouped product is where
the operands live: on a TPU XLA's own grouped kernel (one pass over the
sorted rows; an expert's weights are fetched once for each row tile its
group touches, so once a decode step, and an expert no row picked is not
fetched), anywhere else XLA's dense expansion, which tests use as is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .param import Param
from .registry import register

_F32 = jnp.float32
ROUTER_EPS = 1e-6


def route(rows, weight, bias, live=None, *, top_k, normalize=True, scale=1.0):
    """``rows`` (n, H), ``weight`` (E, H), ``bias`` (E,), ``live`` (n,) or
    None.  Returns ids (n, k) int32, weights (n, k) float32 and the load
    (E,) int32: the live rows' picks by expert.  Scores are float32
    whatever the rows' dtype (a near-tie must not be a tie of rounded
    scores); a row that is not live picks expert ``E`` with weight 0."""
    experts = weight.shape[0]
    logits = lax.dot_general(rows, weight, (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32)
    scores = jax.nn.sigmoid(logits)
    _, ids = lax.top_k(scores + bias.astype(_F32), int(top_k))
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + ROUTER_EPS)
    picked = picked * float(scale)
    ids = ids.astype(jnp.int32)
    if live is not None:
        on = live.astype(bool)[:, None]
        ids = jnp.where(on, ids, experts)
        picked = jnp.where(on, picked, 0.0)
    load = jnp.sum(ids.reshape(-1)[:, None] == jnp.arange(experts)[None, :],
                   axis=0, dtype=jnp.int32)
    return ids, picked, load


def experts_formulation(platform):
    """What the grouped products of ``_contrib_RoutedExperts`` are where the
    operands live: ``"ragged"`` -- XLA's grouped kernel over the sorted
    pairs, whose work is the pairs' and whose weight traffic is the hit
    experts' -- on a TPU; ``"ragged-dense"`` -- the same ``ragged_dot``
    expanded by XLA into a masked dense product, every expert over every
    pair: the oracle's cost, fine at test sizes -- anywhere else.  An
    observation, as ``ops/paged.py`` ``decode_formulation`` is: no
    attribute, environment variable or autotune entry chooses."""
    return "ragged" if platform == "tpu" else "ragged-dense"


def routed_experts(rows, ids, weights, w13, w2, *, first_expert=0):
    """``rows`` (n, H), ``ids`` / ``weights`` (n, k), ``w13`` (held, H, 2F)
    ``[W1 | W3]`` and ``w2`` (held, F, H), the experts ``first_expert ..
    first_expert + held - 1``.  Returns (n, H) in ``rows``' dtype: the held
    experts' part of the layer's output."""
    n, k = ids.shape
    held = w13.shape[0]
    local = ids.reshape(-1) - int(first_expert)
    mine = (local >= 0) & (local < held)
    # pairs of other shares (and of rows that are not live) sort behind
    # every held group and belong to none: the grouped product leaves them
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    x = jnp.take(rows, order // k, axis=0)
    h = lax.ragged_dot(x, w13, sizes, preferred_element_type=_F32)
    g, u = jnp.split(h, 2, axis=-1)
    a = (jax.nn.silu(g) * u).astype(rows.dtype)
    y = lax.ragged_dot(a, w2, sizes, preferred_element_type=_F32)
    # back to (row, pick) order; a select, not a product with a zero weight:
    # what the grouped product leaves in a row of no group is not looked at
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(n, k, -1)
    w = weights.astype(_F32)[:, :, None]
    y = jnp.where(mine.reshape(n, k, 1), y * w, 0.0)
    return jnp.sum(y, axis=1).astype(rows.dtype)


def _router_inputs(attrs):
    return ["data", "weight", "bias"] + (["live"] if attrs.get("use_live")
                                          else [])


@register("_contrib_MoERouter", inputs=_router_inputs,
          params={"top_k": Param(int, required=True),
                  "normalize": Param(bool, True),
                  "scale": Param(float, 1.0),
                  "use_live": Param(bool, False)},
          num_outputs=3, no_grad_inputs=("live",),
          output_names=lambda attrs: ["ids", "weights", "load"],
          hint="moerouter")
@jax.named_scope("moe_router")
def _moe_router(opctx, attrs, data, weight, bias, *live):
    """:func:`route` as an op (sigmoid scores): reads ``data`` (rows, H),
    ``weight`` (E, H), ``bias`` (E,) and, with ``use_live``, ``live``
    (rows,; nonzero: the row routes); writes ``ids`` (rows, k) int32,
    ``weights`` (rows, k) float32 and ``load`` (E,) int32."""
    return route(data, weight, bias, live[0] if live else None,
                 top_k=int(attrs["top_k"]),
                 normalize=bool(attrs.get("normalize", True)),
                 scale=float(attrs.get("scale", 1.0)))


@register("_contrib_RoutedExperts",
          inputs=("data", "ids", "weights", "w13", "w2"),
          params={"num_experts": Param(int, required=True),
                  "first_expert": Param(int, 0)},
          no_grad_inputs=("ids",), hint="routedexperts")
@jax.named_scope("moe_experts")
def _routed_experts(opctx, attrs, data, ids, weights, w13, w2):
    """:func:`routed_experts` as an op: reads ``data`` (rows, H), the
    router's ``ids`` and ``weights`` (rows, k), ``w13`` (held, H, 2F) and
    ``w2`` (held, F, H); ``num_experts`` is the router's width,
    ``first_expert`` the first of the held ones.  Writes (rows, H)."""
    first, held = int(attrs.get("first_expert", 0)), w13.shape[0]
    if first < 0 or first + held > int(attrs["num_experts"]):
        raise ValueError("experts %d..%d are not among the router's %d"
                         % (first, first + held - 1,
                            int(attrs["num_experts"])))
    return routed_experts(data, ids, weights, w13, w2, first_expert=first)


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def rotary(x, positions, *, theta, rotary_dim=0):
    """``x`` (..., heads, head_dim) rotated by its position: the first
    ``rotary_dim`` features (0: all of them) in two halves, feature ``i``
    paired with ``i + rotary_dim / 2``, by the angle ``position *
    theta^(-2i / rotary_dim)``; the rest pass.  ``positions`` is the
    trailing part of ``x``'s leading axes ((L,) for (b, L, heads, d),
    (lanes,) for (lanes, heads, d)).  Angles, sines and the rotation are
    float32; returned in ``x``'s dtype."""
    d = int(rotary_dim) or x.shape[-1]
    half = d // 2
    inv = jnp.exp(jnp.arange(half, dtype=_F32) * (-2.0 / d)
                  * jnp.log(_F32(theta)))
    ang = positions.astype(_F32)[..., None, None] * inv  # (..., 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(_F32)
    a, b = x32[..., :half], x32[..., half:d]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                           x32[..., d:]], axis=-1)
    return out.astype(x.dtype)


@register("_contrib_Rotary", inputs=("data", "positions"),
          params={"theta": Param(float, 10000.0),
                  "rotary_dim": Param(int, 0)},
          no_grad_inputs=("positions",), hint="rotary")
@jax.named_scope("rotary")
def _rotary(opctx, attrs, data, positions):
    """:func:`rotary` as an op: reads ``data`` (..., heads, head_dim) and
    ``positions`` (the sequence axis' or the lanes'; float carrier or
    int), writes ``data``'s shape and dtype."""
    return rotary(data, positions, theta=float(attrs.get("theta", 10000.0)),
                  rotary_dim=int(attrs.get("rotary_dim", 0)))
