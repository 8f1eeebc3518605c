"""Hybrid state-space / attention decoder LM in plain jax.numpy: the
reference family of ``granitemoehybrid`` configurations without experts
(IBM Granite 4.0-H).  Imports nothing of the program under test.

For layer ``l`` (``layer_types[l]`` is ``mamba`` or ``attention``), with
``m = residual_multiplier``::

    h0 = embedding_multiplier * E[ids]
    a  = x + m * Mixer_l(RMSNorm(x; norm1))
    x' = a + m * W_out(silu(g) * u),   [g | u] = W_in RMSNorm(a; norm2)
    logits = RMSNorm(x; norm_f) E^T / logits_scaling        (E is tied)

* attention mixer: q (heads x head_dim), k, v (kv_heads x head_dim), no
  bias, NO positional encoding, causal softmax of ``q k^T *
  attention_multiplier``, query head ``i`` over K/V head ``i // group``;
* state-space mixer (Mamba-2, one B/C group): ``[z | xBC | dt] = W_in_proj
  h``; ``xBC <- silu(conv1d(xBC))`` causal, depthwise, width
  ``mamba_d_conv``, with bias; ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer)
  B_t``, ``y_t = S_t C_t + D x_t``; ``RMSNorm(y * silu(z); gate_norm)``
  over all channels; ``W_out_proj``.

Everything is float32 at ``highest`` matmul precision (or, for the control,
the matrix products at a stated lower precision) from the weights as they
were seeded; the state-space layers run the plain recurrence token by
token: no chunks, no cache, no batching.  One layer is one jitted call, so
a layer's weights are upcast one layer at a time.

Departures from the published description, all in ``make_weights`` (the
published weights are not used): see its docstring.  Parameter names and
layouts (``[out, in]`` matrices):

  tok_embed_weight (V, H)               norm_f_gamma (H,)
  layer{i}_norm1_gamma, _norm2_gamma    layer{i}_mlp_in_weight (2I, H)
  layer{i}_mlp_out_weight (H, I)
  attention:  layer{i}_q_weight (heads*hd, H), _k_weight, _v_weight
              (kv_heads*hd, H), _o_weight (H, heads*hd)
  mamba:      layer{i}_in_proj_weight (2*inner + 2*state + heads, H),
              _conv_weight (conv_dim, K), _conv_bias (conv_dim,),
              _A_log, _D, _dt_bias (heads,), _gate_norm_gamma (inner,),
              _out_proj_weight (H, inner)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .precision import einsum as _einsum
from .precision import seed_key


def sizes(cfg, layers=None):
    """The sizes of a config dict, under this file's names."""
    types = list(cfg["layer_types"])
    if layers is not None:
        types = types[:int(layers)]
    heads = int(cfg["num_attention_heads"])
    inner = int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])
    if int(cfg.get("mamba_n_groups", 1)) != 1:
        raise ValueError("one B/C group only")
    if inner != int(cfg.get("mamba_expand", 2)) * int(cfg["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    return dict(
        vocab=int(cfg["vocab_size"]), hidden=int(cfg["hidden_size"]),
        types=types, heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads,
        inter=int(cfg["intermediate_size"]),
        ssm_heads=int(cfg["mamba_n_heads"]), ssm_hd=int(cfg["mamba_d_head"]),
        state=int(cfg["mamba_d_state"]), conv_k=int(cfg["mamba_d_conv"]),
        inner=inner, conv_dim=inner + 2 * int(cfg["mamba_d_state"]),
        eps=float(cfg["rms_norm_eps"]),
        emb_mult=float(cfg["embedding_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        att_mult=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]))


def param_shapes(cfg, layers=None):
    z = sizes(cfg, layers)
    h, hd = z["hidden"], z["head_dim"]
    shapes = {"tok_embed_weight": (z["vocab"], h), "norm_f_gamma": (h,)}
    for i, kind in enumerate(z["types"]):
        pre = "layer%d_" % i
        shapes.update({pre + "norm1_gamma": (h,), pre + "norm2_gamma": (h,),
                       pre + "mlp_in_weight": (2 * z["inter"], h),
                       pre + "mlp_out_weight": (h, z["inter"])})
        if kind == "attention":
            shapes.update({
                pre + "q_weight": (z["heads"] * hd, h),
                pre + "k_weight": (z["kv_heads"] * hd, h),
                pre + "v_weight": (z["kv_heads"] * hd, h),
                pre + "o_weight": (h, z["heads"] * hd)})
        else:
            shapes.update({
                pre + "in_proj_weight": (2 * z["inner"] + 2 * z["state"]
                                         + z["ssm_heads"], h),
                pre + "conv_weight": (z["conv_dim"], z["conv_k"]),
                pre + "conv_bias": (z["conv_dim"],),
                pre + "A_log": (z["ssm_heads"],), pre + "D": (z["ssm_heads"],),
                pre + "dt_bias": (z["ssm_heads"],),
                pre + "gate_norm_gamma": (z["inner"],),
                pre + "out_proj_weight": (h, z["inner"])})
    return shapes


def n_params(cfg, layers=None):
    return sum(int(np.prod(s)) for s in param_shapes(cfg, layers).values())


# The projections that write into the residual stream (the mixers' output
# projections and the MLP's second matrix) are seeded five times wider than
# the rest.  With N(0, 0.02) everywhere and the published multipliers (the
# embedding x 12, every sublayer x 0.22, a tied head) the embedding outweighs
# 40 layers of mixing: the seeded model repeats its last token with top-two
# margins of 0.2-0.3, and no error of precision under 0.2 can change a served
# token, float8's included (PERF.md section 6, PR 32).  At 0.1 the layers
# carry the logits, the margins are 0.00-0.07, and float8 changes most picks.
_OUT_KINDS = ("out_proj_weight", "o_weight", "mlp_out_weight")
_OUT_STD = 0.1


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _seeded_leaf(key, i, kind, shape, dtype):
    """Leaf ``i`` (in the order of the sorted names) of the seeded weights;
    ``kind`` is its name without the layer.  One small program a kind, not
    one large one for the 552 leaves (which takes minutes to compile)."""
    k = jax.random.fold_in(key, i)
    if kind == "A_log":
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif kind == "D":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "dt_bias":
        # the inverse softplus of a step drawn log-uniformly in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        std = _OUT_STD if kind in _OUT_KINDS else 0.02
        x = std * jax.random.normal(k, shape, jnp.float32)
        if kind.endswith("_gamma"):
            x = x + 1.0
    return x.astype(dtype)


def make_weights(cfg, seed, layers=None):
    """Seeded weights in the dtype the configuration holds them in
    (``weights_dtype``, bfloat16 unless it says otherwise), made on the
    default device.  Matrices, the embedding, the convolution and its bias
    N(0, 0.02), the projections into the residual stream N(0, 0.1) (see
    ``_OUT_STD``); norm gains 1 + N(0, 0.02); ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of a log-uniform step in [0.001, 0.1],
    ``D = 1``: the family's own initialisation of the recurrence, so that
    on random weights the state neither dies nor blows up.  Every leaf is
    rounded to the weights' dtype; the reference upcasts what it is
    given."""
    shapes = param_shapes(cfg, layers)
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    key = seed_key(seed)

    def kind(name):
        return name.split("_", 1)[1] if name.startswith("layer") else name

    return {name: _seeded_leaf(key, i, kind(name), shp, dtype)
            for i, (name, shp) in enumerate(sorted(shapes.items()))}


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _e4m3(x):
    """``x`` rounded to float8 e4m3's grid under a per-tensor scale, as
    ``precision._q8`` rounds it, but by arithmetic: the significand to 4
    bits (3 stored), values under the smallest normal to its subnormal
    step.  ``x.astype(float8).astype(float32)`` is a pair of conversions the
    TPU compiler may drop as excess precision, which leaves a float8 control
    that rounds nothing (PERF.md section 6, PR 32); this it cannot drop."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)) / 448.0, 1e-30)
    m, e = jnp.frexp(x / s)  # |m| in [0.5, 1)
    e = jnp.maximum(e, -5)  # 2**-6 is the smallest normal: step 2**-9
    return jnp.round(x / s * jnp.exp2(4.0 - e)) * jnp.exp2(e - 4.0) * s


def einsum(spec, a, b, prec):
    """``precision.einsum``, with the float8 control's roundings (operands
    and the stored result, per tensor) made by :func:`_e4m3`."""
    if prec != "fp8":
        return _einsum(spec, a, b, prec)
    return _e4m3(_einsum(spec, _e4m3(a), _e4m3(b), "bf16"))


def _dense(x, w, prec):
    return einsum("...k,nk->...n", x, w, prec)


def _attention(h, p, z, prec):
    """(s, hidden) -> (s, hidden): causal grouped-query attention."""
    s, hd = h.shape[0], z["head_dim"]
    q = _dense(h, p["q_weight"], prec).reshape(s, z["heads"], hd)
    k = _dense(h, p["k_weight"], prec).reshape(s, z["kv_heads"], hd)
    v = _dense(h, p["v_weight"], prec).reshape(s, z["kv_heads"], hd)
    group = z["heads"] // z["kv_heads"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    sc = einsum("qhd,khd->hqk", q, k, prec) * z["att_mult"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    att = einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v, prec)
    return _dense(att.reshape(s, z["heads"] * hd), p["o_weight"], prec)


def _mamba(h, p, z, prec):
    """(s, hidden) -> (s, hidden): the plain recurrence, token by token."""
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
        return S, einsum("hpn,n->hp", S, C_t, prec) + D[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), jnp.float32),
                        (x, B, C, dt))
    y = _rms(y.reshape(s, inner) * jax.nn.silu(gate), p["gate_norm_gamma"],
             z["eps"])
    return _dense(y, p["out_proj_weight"], prec)


def _layer(x, p, kind, z, prec):
    mixer = _attention if kind == "attention" else _mamba
    x = x + z["res_mult"] * mixer(_rms(x, p["norm1_gamma"], z["eps"]), p, z,
                                  prec)
    h = _dense(_rms(x, p["norm2_gamma"], z["eps"]), p["mlp_in_weight"], prec)
    g, u = jnp.split(h, 2, axis=-1)
    return x + z["res_mult"] * _dense(jax.nn.silu(g) * u,
                                      p["mlp_out_weight"], prec)


def make_scorer(cfg, layers, length, prec="f32"):
    """(params, tokens (1, length)) -> logits (length, V), float32.  One
    jitted call a layer kind, the layers in a Python loop."""
    z = sizes(cfg, layers)
    cold = {k: v for k, v in z.items() if k != "types"}

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(x, p, kind):
        return _layer(x, p, kind, cold, prec)

    @jax.jit
    def embed(table, tokens):
        return z["emb_mult"] * table[tokens].astype(jnp.float32)

    @jax.jit
    def head(x, gamma, table):
        return _dense(_rms(x, gamma, z["eps"]), table, prec) \
            / z["logits_scaling"]

    def score(params, tokens):
        x = embed(params["tok_embed_weight"], jnp.asarray(tokens)[0])
        for i, kind in enumerate(z["types"]):
            pre = "layer%d_" % i
            x = layer(x, {k[len(pre):]: v for k, v in params.items()
                          if k.startswith(pre)}, kind)
        return head(x, params["norm_f_gamma"], params["tok_embed_weight"])

    return score
