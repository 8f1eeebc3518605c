"""State-space (Mamba-2) ops, and the norms and gates of the hybrid LM
(``models/hybrid_lm.py``): what a decoder needs beside attention when most
of its layers carry a recurrent state instead of a K/V cache.

The selective state-space layer, per head ``h`` of ``heads`` (head size
``P``, state size ``N``, one B/C group shared by all heads)::

    dt_t = softplus(dt_raw_t + dt_bias)          A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t        S is (P, N)
    y_t  = S_t C_t + D x_t

with ``[x | B | C]`` the output of a causal depthwise convolution (width
``K``, with bias) followed by SiLU.  Two forms of each, one numerics:

* over a prompt (prefill): :func:`causal_conv` and :func:`ssm_scan`, the
  chunked scan of the Mamba-2 paper in plain ``jax.numpy``.  Both take the
  prompts' TRUE lengths: a position at or past a prompt's length
  contributes nothing (its ``dt`` is forced to 0, so it neither decays the
  state nor adds to it) and the convolution's tail is read at
  ``length-(K-1) .. length-1``, so a right-padded prompt leaves exactly the
  state its unpadded self would;
* one token a lane (decode): :func:`conv_step` and :func:`ssm_step` over
  per-lane *slots* of a state plane, indexed by ``state_slot`` as a page
  table indexes K/V pages; the engine carries the planes through the step
  donated (``Executor.set_carried``), so the update is in place.  Slot 0
  is scratch: padded lanes point there (generation/kv_pool.py), and a step
  neither reads nor writes it.

``dt``, ``A``, the recurrence and the state are float32 whatever the
activations' dtype; every op returns activations in the dtype it was given.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .param import Param
from .registry import register

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# norms and gates
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, in float32;
    returned in ``x``'s dtype."""
    x32 = x.astype(_F32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * gamma.astype(_F32)).astype(x.dtype)


@register("_contrib_RMSNorm", inputs=("data", "gamma"),
          params={"eps": Param(float, 1e-5)}, hint="rmsnorm")
def _rms_norm(opctx, attrs, data, gamma):
    """Root-mean-square norm over the last axis: reads ``data`` (..., n)
    and ``gamma`` (n,), writes (..., n) in ``data``'s dtype."""
    return rms_norm(data, gamma, float(attrs.get("eps", 1e-5)))


@register("_contrib_GatedRMSNorm", inputs=("data", "gate", "gamma"),
          params={"eps": Param(float, 1e-5)}, hint="gatedrmsnorm")
def _gated_rms_norm(opctx, attrs, data, gate, gamma):
    """``RMSNorm(data * silu(gate); gamma)`` over the whole last axis (one
    group): the state-space mixer's output norm.  Reads ``data`` and
    ``gate`` (..., n), writes (..., n) in ``data``'s dtype."""
    y = data.astype(_F32) * jax.nn.silu(gate.astype(_F32))
    return rms_norm(y, gamma, float(attrs.get("eps", 1e-5))).astype(
        data.dtype)


@register("_contrib_SiluGate", inputs=("data",), hint="silugate")
def _silu_gate(opctx, attrs, data):
    """The gate of a SiLU-gated MLP: ``data`` (..., 2n) is ``[g | u]``,
    the output (..., n) is ``silu(g) * u`` (computed in float32)."""
    g, u = jnp.split(data.astype(_F32), 2, axis=-1)
    return (jax.nn.silu(g) * u).astype(data.dtype)


@register("_contrib_ScaledLogits", inputs=("data", "weight"),
          params={"scale": Param(float, 1.0)}, hint="scaledlogits")
def _scaled_logits(opctx, attrs, data, weight):
    """Vocabulary projection against a (tied) embedding table: ``data``
    (rows, hidden) x ``weight`` (vocab, hidden)^T x ``scale``, accumulated
    AND returned in float32 whatever the operands' dtype, so that the pick
    of a token is not a tie of rounded logits."""
    out = lax.dot_general(data, weight, (((1,), (1,)), ((), ())),
                          preferred_element_type=_F32)
    return out * float(attrs.get("scale", 1.0))


# ---------------------------------------------------------------------------
# causal depthwise convolution with a carried tail
# ---------------------------------------------------------------------------

def _conv_window(window, weight, bias, dtype):
    """``silu(sum_k window[..., k, c] * weight[c, k] + bias[c])``."""
    w = weight.astype(_F32).T  # (K, C)
    out = jnp.sum(window.astype(_F32) * w, axis=-2) + bias.astype(_F32)
    return jax.nn.silu(out).astype(dtype)


def causal_conv(x, weight, bias, length=None):
    """Prefill form.  ``x`` (b, L, C), ``weight`` (C, K) (column ``K-1``
    multiplies the current position), ``bias`` (C,), ``length`` (b,) or
    None for whole rows.  Returns ``silu(conv(x))`` (b, L, C) and the tail
    (b, K-1, C): ``x`` at ``length-(K-1) .. length-1``, zeros before the
    prompt's start."""
    b, L, C = x.shape
    K = weight.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    window = jnp.stack([xp[:, k:k + L] for k in range(K)], axis=2)
    out = _conv_window(window, weight, bias, x.dtype)
    if length is None:
        length = jnp.full((b,), L, jnp.int32)
    # x[t] sits at xp[t + K - 1]
    idx = length.astype(jnp.int32)[:, None] + jnp.arange(K - 1)[None, :]
    tail = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return out, tail


def _route(slot, slots):
    """``(lanes, slots)`` bool: lane ``l`` names slot ``s``, the scratch
    slot 0 left out (a lane parked there names nothing: scratch is never
    written, and reads as the zeros it was made of).  With it the lanes'
    small vectors go to slot order and come back by selection, so a step
    over a whole plane needs no gather and no scatter; a select, not a
    product with a one-hot matrix, so that a lane that has diverged (inf,
    NaN) stays alone with it."""
    s = jnp.arange(slots)[None, :]
    return (slot[:, None] == s) & (s > 0)


def _to_slots(route, rows):
    """Per-lane ``rows`` (lanes, ...) in slot order (slots, ...), zeros in
    the slots no lane names."""
    pick = route.reshape(route.shape + (1,) * (rows.ndim - 1))
    return jnp.sum(jnp.where(pick, rows.astype(_F32)[:, None], 0.0), axis=0)


def _to_lanes(route, rows):
    """Per-slot ``rows`` (slots, ...) in lane order (lanes, ...), zeros for
    a lane that names no slot."""
    pick = route.reshape(route.shape + (1,) * (rows.ndim - 1))
    return jnp.sum(jnp.where(pick, rows[None], 0.0), axis=1)


def conv_step(x, weight, bias, tails, slot):
    """Decode form.  ``x`` (lanes, C), ``tails`` (slots, K-1, C) the
    plane, ``slot`` (lanes,) int32, every live lane a slot of its own.  The
    whole plane in one pass: a slot a lane names takes this token behind its
    tail, the others keep theirs.  Returns ``silu(conv)`` (lanes, C) and
    the plane."""
    route = _route(slot, tails.shape[0])
    named = jnp.any(route, axis=0)  # (slots,)
    window = jnp.concatenate(
        [tails, _to_slots(route, x)[:, None].astype(tails.dtype)], axis=1)
    out = _to_lanes(route, _conv_window(window, weight, bias, _F32))
    return (out.astype(x.dtype),
            jnp.where(named[:, None, None], window[:, 1:], tails))


def _length_inputs(base):
    return lambda attrs: list(base) + (["length"] if attrs.get("use_length")
                                       else [])


@register("_contrib_CausalConv1D",
          inputs=_length_inputs(("data", "weight", "bias")),
          params={"use_length": Param(bool, False)}, num_outputs=2,
          output_names=lambda attrs: ["out", "tail"], hint="causalconv1d")
@jax.named_scope("ssm_scan")
def _causal_conv1d(opctx, attrs, data, weight, bias, *length):
    """:func:`causal_conv` as an op: reads ``data`` (b, L, C), ``weight``
    (C, K), ``bias`` (C,) and, with ``use_length``, ``length`` (b,);
    writes ``out`` (b, L, C) and ``tail`` (b, K-1, C)."""
    return causal_conv(data, weight, bias, length[0] if length else None)


@register("_contrib_CausalConv1DStep",
          inputs=("data", "weight", "bias", "tails", "state_slot"),
          num_outputs=2, no_grad_inputs=("state_slot",),
          output_names=lambda attrs: ["out", "tails_out"],
          hint="causalconv1dstep")
@jax.named_scope("ssm_step")
def _causal_conv1d_step(opctx, attrs, data, weight, bias, tails, state_slot):
    """:func:`conv_step` as an op: reads ``data`` (lanes, C), the lanes'
    rows of the plane ``tails`` (slots, K-1, C) at ``state_slot`` (lanes,;
    float carrier, cast to int32); writes ``out`` (lanes, C) and the plane
    with those rows replaced."""
    return conv_step(data, weight, bias, tails,
                     state_slot.astype(jnp.int32))


# ---------------------------------------------------------------------------
# the selective state-space recurrence
# ---------------------------------------------------------------------------

def _split_xbc(xbc, heads, head_dim, state):
    """``[x | B | C]`` of the last axis, in float32: x (..., heads,
    head_dim), B and C (..., state)."""
    inner = heads * head_dim
    if xbc.shape[-1] != inner + 2 * state:
        raise ValueError("xbc is %d wide; heads x head_dim + 2 x state is %d"
                         % (xbc.shape[-1], inner + 2 * state))
    xbc = xbc.astype(_F32)
    x = xbc[..., :inner].reshape(xbc.shape[:-1] + (heads, head_dim))
    return x, xbc[..., inner:inner + state], xbc[..., inner + state:]


def _dt_and_a(dt_raw, A_log, dt_bias):
    dt = jax.nn.softplus(dt_raw.astype(_F32) + dt_bias.astype(_F32))
    return dt, -jnp.exp(A_log.astype(_F32))


def ssm_scan(xbc, dt_raw, A_log, D, dt_bias, length=None, *, heads,
             head_dim, state, chunk=256):
    """Prefill form: the chunked scan.  ``xbc`` (b, L, heads*head_dim +
    2*state) after the convolution, ``dt_raw`` (b, L, heads), ``A_log``,
    ``D``, ``dt_bias`` (heads,), ``length`` (b,) or None.  Inside a chunk
    of ``chunk`` positions the outputs are one masked product of the decay
    matrix, between chunks the state is carried by a short recurrence.
    Returns ``y`` (b, L, heads*head_dim) in ``xbc``'s dtype and the final
    state (b, heads, head_dim, state), float32, as it stands after each
    prompt's TRUE length."""
    b, L = xbc.shape[:2]
    x, B, C = _split_xbc(xbc, heads, head_dim, state)
    dt, A = _dt_and_a(dt_raw, A_log, dt_bias)
    if length is not None:
        live = jnp.arange(L)[None, :] < length.astype(jnp.int32)[:, None]
        dt = jnp.where(live[:, :, None], dt, 0.0)
    Q = min(int(chunk), L)
    pad = -L % Q
    if pad:  # positions past the end: dt 0, nothing moves
        x, B, C, dt = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, B, C, dt))
    nc = (L + pad) // Q
    x = x.reshape(b, nc, Q, heads, head_dim)
    B, C = B.reshape(b, nc, Q, state), C.reshape(b, nc, Q, state)
    dt = dt.reshape(b, nc, Q, heads)
    cs = jnp.cumsum(dt * A, axis=2)  # (b, nc, Q, heads), log decay so far
    xdt = x * dt[..., None]

    # inside a chunk: y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (b, nc, t, s, heads)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    G = jnp.einsum("bctn,bcsn->bcts", C, B, precision=_HI)
    y = jnp.einsum("bctsh,bcshp->bcthp", G[..., None] * decay, xdt,
                   precision=_HI)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)  # (b, nc, Q, heads)
    states = jnp.einsum("bcshp,bcsn->bchpn", xdt * to_end[..., None], B,
                        precision=_HI)

    # between chunks: S_c = exp(cs_end_c) S_{c-1} + states_c
    chunk_decay = jnp.exp(cs[:, :, -1, :])  # (b, nc, heads)
    S = jnp.zeros((b, heads, head_dim, state), _F32)
    before = []
    for c in range(nc):
        before.append(S)
        S = chunk_decay[:, c, :, None, None] * S + states[:, c]
    before = jnp.stack(before, axis=1)  # the state each chunk starts from
    y = y + jnp.einsum("bctn,bchpn->bcthp", C, before, precision=_HI) \
        * jnp.exp(cs)[..., None]
    y = y + D.astype(_F32)[:, None] * x
    y = y.reshape(b, nc * Q, heads * head_dim)[:, :L]
    return y.astype(xbc.dtype), S


def ssm_step(xbc, dt_raw, A_log, D, dt_bias, states, slot, *, heads,
             head_dim, state):
    """Decode form: one token a lane.  ``xbc`` (lanes, ...), ``dt_raw``
    (lanes, heads), ``states`` (slots, heads, head_dim, state) float32 the
    plane, ``slot`` (lanes,) int32, every live lane a slot of its own.  One
    fused multiply-add over the WHOLE plane and one reduction over its last
    axis: the plane is read once and written once, in place when it is
    donated, with no gather and no scatter (the lanes' small vectors are
    routed to slot order instead, :func:`_route`); a slot no lane names
    decays by exactly 1 and takes exactly 0, so it keeps its bits.  The
    engine holds one slot a lane and the scratch slot, so a full step moves
    the lanes' state and one slot more.  Returns ``y`` (lanes,
    heads*head_dim) and the plane."""
    x, B, C = _split_xbc(xbc, heads, head_dim, state)
    dt, A = _dt_and_a(dt_raw, A_log, dt_bias)
    route = _route(slot, states.shape[0])
    idle = 1.0 - jnp.any(route, axis=0).astype(_F32)  # (slots,)
    decay = _to_slots(route, jnp.exp(dt * A)) + idle[:, None]
    u, Bs, Cs = (_to_slots(route, a) for a in (dt[:, :, None] * x, B, C))
    S = decay[:, :, None, None] * states.astype(_F32) \
        + u[..., None] * Bs[:, None, None, :]
    y = _to_lanes(route, jnp.sum(S * Cs[:, None, None, :], axis=-1)) \
        + D.astype(_F32)[None, :, None] * x
    y = y.reshape(xbc.shape[0], heads * head_dim).astype(xbc.dtype)
    return y, S.astype(states.dtype)


_SSM_PARAMS = {"heads": Param(int, required=True),
               "head_dim": Param(int, required=True),
               "state": Param(int, required=True)}


def _sizes(attrs):
    return {k: int(attrs[k]) for k in ("heads", "head_dim", "state")}


@register("_contrib_SSMScan",
          inputs=_length_inputs(("data", "dt", "A_log", "D", "dt_bias")),
          params=dict(_SSM_PARAMS, chunk=Param(int, 256),
                      use_length=Param(bool, False)),
          num_outputs=2, output_names=lambda attrs: ["out", "state"],
          hint="ssmscan")
@jax.named_scope("ssm_scan")
def _ssm_scan(opctx, attrs, data, dt, A_log, D, dt_bias, *length):
    """:func:`ssm_scan` as an op: reads ``data`` (b, L, inner + 2 state),
    ``dt`` (b, L, heads), the per-head ``A_log``, ``D``, ``dt_bias`` and,
    with ``use_length``, ``length`` (b,); writes ``out`` (b, L, inner) and
    ``state`` (b, heads, head_dim, state) float32."""
    return ssm_scan(data, dt, A_log, D, dt_bias,
                    length[0] if length else None,
                    chunk=int(attrs.get("chunk", 256)), **_sizes(attrs))


@register("_contrib_SSMStep",
          inputs=("data", "dt", "A_log", "D", "dt_bias", "states",
                  "state_slot"),
          params=dict(_SSM_PARAMS), num_outputs=2,
          no_grad_inputs=("state_slot",),
          output_names=lambda attrs: ["out", "states_out"], hint="ssmstep")
@jax.named_scope("ssm_step")
def _ssm_step(opctx, attrs, data, dt, A_log, D, dt_bias, states, state_slot):
    """:func:`ssm_step` as an op: reads ``data`` (lanes, inner + 2 state),
    ``dt`` (lanes, heads), the per-head vectors, the lanes' slots of the
    plane ``states`` (slots, heads, head_dim, state) at ``state_slot``
    (lanes,; float carrier, cast to int32); writes ``out`` (lanes, inner)
    and the plane with those slots replaced."""
    return ssm_step(data, dt, A_log, D, dt_bias, states,
                    state_slot.astype(jnp.int32), **_sizes(attrs))
