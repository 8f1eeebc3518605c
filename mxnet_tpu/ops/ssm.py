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
  chunked scan of the Mamba-2 paper.  Both take the prompts' TRUE lengths:
  a position at or past a prompt's length contributes nothing (its ``dt``
  is forced to 0, so it neither decays the state nor adds to it) and the
  convolution's tail is read at ``length-(K-1) .. length-1``, so a
  right-padded prompt leaves exactly the state its unpadded self would.
  The scan has two formulations, one op (:func:`scan_formulation`): on a
  TPU one Pallas kernel a layer walks each prompt's live chunks one at a
  time, a block of heads at a time, a head's decay matrix never leaving
  VMEM; anywhere else, and in a graph that is differentiated, plain
  ``jax.numpy`` takes the whole bucket at once, which is also the
  kernel's oracle;
* one token a lane (decode): :func:`conv_step` and :func:`ssm_step` over
  per-lane *slots* of a state plane, indexed by ``state_slot`` as a page
  table indexes K/V pages; the engine carries the planes through the step
  donated (``Executor.set_carried``), so the update is in place.  Slot 0
  is scratch: padded lanes point there (generation/kv_pool.py), and a step
  leaves its bits alone.  The recurrence's step has two formulations, one
  op (:func:`step_formulation` picks by where the operands live, as
  ``ops/paged.py`` does for attention): on a TPU one Pallas kernel a layer
  steps each live lane's slot where it lies in the plane; anywhere else
  XLA makes one fused pass over the whole plane, which is also the
  kernel's oracle (tests/test_ssm_ops.py).

``dt``, ``A``, the recurrence and the state are float32 whatever the
activations' dtype; every op returns activations in the dtype it was given.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .param import Param
from .registry import register

_F32, _BF16 = jnp.float32, jnp.bfloat16
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

_ACTIVATIONS = {"silu": jax.nn.silu, "none": lambda x: x}


def _conv_window(window, weight, bias, dtype, activation="silu"):
    """``act(sum_k window[..., k, c] * weight[c, k] + bias[c])``; ``bias``
    may be None, ``act`` is SiLU or nothing."""
    w = weight.astype(_F32).T  # (K, C)
    out = jnp.sum(window.astype(_F32) * w, axis=-2)
    if bias is not None:
        out = out + bias.astype(_F32)
    return _ACTIVATIONS[activation](out).astype(dtype)


def causal_conv(x, weight, bias, length=None, activation="silu"):
    """Prefill form.  ``x`` (b, L, C), ``weight`` (C, K) (column ``K-1``
    multiplies the current position), ``bias`` (C,) or None, ``length``
    (b,) or None for whole rows.  Returns ``act(conv(x))`` (b, L, C) and
    the tail (b, K-1, C): ``x`` at ``length-(K-1) .. length-1``, zeros
    before the prompt's start."""
    b, L, C = x.shape
    K = weight.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    window = jnp.stack([xp[:, k:k + L] for k in range(K)], axis=2)
    out = _conv_window(window, weight, bias, x.dtype, activation)
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


def conv_step(x, weight, bias, tails, slot, activation="silu"):
    """Decode form.  ``x`` (lanes, C), ``tails`` (slots, K-1, C) the
    plane, ``slot`` (lanes,) int32, every live lane a slot of its own.  The
    whole plane in one pass: a slot a lane names takes this token behind its
    tail, the others keep theirs.  Returns ``act(conv)`` (lanes, C) and
    the plane."""
    route = _route(slot, tails.shape[0])
    named = jnp.any(route, axis=0)  # (slots,)
    window = jnp.concatenate(
        [tails, _to_slots(route, x)[:, None].astype(tails.dtype)], axis=1)
    out = _to_lanes(route, _conv_window(window, weight, bias, _F32,
                                        activation))
    return (out.astype(x.dtype),
            jnp.where(named[:, None, None], window[:, 1:], tails))


def _length_inputs(base):
    return lambda attrs: list(base) + (["length"] if attrs.get("use_length")
                                       else [])


def _conv_inputs(tail):
    """``data``, ``weight``, ``bias`` unless ``no_bias``, then ``tail``."""
    return lambda attrs: (["data", "weight"]
                          + ([] if attrs.get("no_bias") else ["bias"])
                          + tail(attrs))


# both default to None, which a graph's JSON leaves out: a graph that names
# neither keeps the bytes (and the compile-cache fingerprint) it had
_CONV_PARAMS = {"activation": Param(str, None, enum=tuple(_ACTIVATIONS)),
                "no_bias": Param(bool, None)}
# the scope a convolution's device time is read under: the state-space
# layers' own where it is theirs (SiLU: Mamba's), the gated short
# convolution's where it is a mixer by itself (no activation)
_CONV_SCOPES = {"silu": ("ssm_scan", "ssm_step"),
                "none": ("short_conv", "short_conv_step")}


def _conv_args(attrs, rest):
    """(bias or None, what follows it, the activation) of a convolution
    op's trailing inputs."""
    activation = attrs.get("activation") or "silu"
    if attrs.get("no_bias"):
        return None, rest, activation
    return rest[0], rest[1:], activation


@register("_contrib_CausalConv1D",
          inputs=_conv_inputs(lambda attrs: ["length"]
                              if attrs.get("use_length") else []),
          params=dict(_CONV_PARAMS, use_length=Param(bool, False)),
          num_outputs=2,
          output_names=lambda attrs: ["out", "tail"], hint="causalconv1d")
def _causal_conv1d(opctx, attrs, data, weight, *rest):
    """:func:`causal_conv` as an op: reads ``data`` (b, L, C), ``weight``
    (C, K), ``bias`` (C,) unless ``no_bias`` and, with ``use_length``,
    ``length`` (b,); ``activation`` is ``silu`` or ``none``.  Writes ``out``
    (b, L, C) and ``tail`` (b, K-1, C)."""
    bias, length, activation = _conv_args(attrs, rest)
    with jax.named_scope(_CONV_SCOPES[activation][0]):
        return causal_conv(data, weight, bias,
                           length[0] if length else None, activation)


@register("_contrib_CausalConv1DStep",
          inputs=_conv_inputs(lambda attrs: ["tails", "state_slot"]),
          params=dict(_CONV_PARAMS), num_outputs=2,
          no_grad_inputs=("state_slot",),
          output_names=lambda attrs: ["out", "tails_out"],
          hint="causalconv1dstep")
def _causal_conv1d_step(opctx, attrs, data, weight, *rest):
    """:func:`conv_step` as an op: reads ``data`` (lanes, C), ``weight``,
    ``bias`` unless ``no_bias``, the lanes' rows of the plane ``tails``
    (slots, K-1, C) at ``state_slot`` (lanes,; float carrier, cast to
    int32); writes ``out`` (lanes, C) and the plane with those rows
    replaced."""
    bias, (tails, state_slot), activation = _conv_args(attrs, rest)
    with jax.named_scope(_CONV_SCOPES[activation][1]):
        return conv_step(data, weight, bias, tails,
                         state_slot.astype(jnp.int32), activation)


# ---------------------------------------------------------------------------
# the selective state-space recurrence
# ---------------------------------------------------------------------------

def _inner(xbc, heads, head_dim, state):
    """``heads x head_dim``, the width of ``x`` in ``[x | B | C]``; refuses
    an ``xbc`` of another width."""
    inner = heads * head_dim
    if xbc.shape[-1] != inner + 2 * state:
        raise ValueError("xbc is %d wide; heads x head_dim + 2 x state is %d"
                         % (xbc.shape[-1], inner + 2 * state))
    return inner


def _split_xbc(xbc, heads, head_dim, state):
    """``[x | B | C]`` of the last axis, in float32: x (..., heads,
    head_dim), B and C (..., state)."""
    inner = _inner(xbc, heads, head_dim, state)
    xbc = xbc.astype(_F32)
    x = xbc[..., :inner].reshape(xbc.shape[:-1] + (heads, head_dim))
    return x, xbc[..., inner:inner + state], xbc[..., inner + state:]


def _dt_and_a(dt_raw, A_log, dt_bias):
    dt = jax.nn.softplus(dt_raw.astype(_F32) + dt_bias.astype(_F32))
    return dt, -jnp.exp(A_log.astype(_F32))


def _chunked_scan(xbc, dt, A, D, length, *, chunk, heads, head_dim, state):
    """The XLA formulation, and the kernel's oracle: every chunk of the
    bucket at once.  ``xbc`` (b, L, ...) with ``L`` whole chunks, ``dt`` (b,
    L, heads) float32 and zero past each prompt's ``length``, which is not
    needed beside that: a dead chunk's products are zeros.  Differentiable,
    so it is also a training graph's path."""
    b, L = xbc.shape[:2]
    Q, nc = chunk, L // chunk
    x, B, C = _split_xbc(xbc, heads, head_dim, state)
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
    y = y + D[:, None] * x
    return y.reshape(b, L, heads * head_dim), S


def _pieces(a):
    """``a`` as bfloat16 addends whose float32 sum is ``a``: itself where it
    is bfloat16, else the three a float32 mantissa splits into."""
    if a.dtype == _BF16:
        return [a]
    hi = a.astype(_BF16)
    rest = a - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return [hi, mid, (rest - mid.astype(_F32)).astype(_BF16)]


def _dot(a, b, contract):
    """``a`` x ``b`` over ``contract`` (one axis of each), accumulated in
    float32 and as exact as ``precision=HIGHEST``, which is these passes for
    two float32 operands: every pair of :func:`_pieces` but the three
    smallest.  An operand that IS bfloat16 has one piece, so a float32 matrix
    times what a bfloat16 convolution wrote costs three passes, not six, for
    the same sum."""
    out = None
    for i, pa in enumerate(_pieces(a)):
        for j, pb in enumerate(_pieces(b)):
            if i + j < 3:
                part = lax.dot_general(pa, pb, ((contract[:1], contract[1:]),
                                                ((), ())),
                                       preferred_element_type=_F32)
                out = part if out is None else out + part
    return out


def _tile_heads(head_dim):
    """Heads a lane tile of ``x``: those of 64 go two and two."""
    return max(1, 128 // head_dim)


def _scan_kernel(live_ref, d_ref, x_ref, b_ref, c_ref, col_ref, row_ref,
                 dt_ref, y_ref, s_ref, *, head_dim):
    """One prompt, one block of its heads, one chunk; the chunk axis is the
    grid's last, so ``s_ref`` (the block's state, (heads a block x head_dim,
    state) float32: the output's own VMEM block, written back once, when
    the block of heads changes) carries from chunk to chunk.  ``x_ref`` (1,
    Q, heads a block x head_dim), ``b_ref`` / ``c_ref`` (1, Q, state) are
    blocks of ``xbc`` as the convolution wrote it; ``col_ref`` (1, 1, Q,
    heads a block) and ``row_ref`` (1, heads a block, Q)
    the chunk's cumulative log decay with the positions along the sublanes
    and along the lanes (``seg`` takes one of each), ``dt_ref`` likewise
    ``dt``, by rows.  A head's ``Q x Q`` decay matrix lives and dies in
    here.  The block is worked a lane tile of ``x`` at a time, in a loop
    (one tile's operations are all that shape inference and a program's
    trace walk): heads narrower than a tile share it, each one's product taking the tile
    with the other heads' lanes zeroed, which the MXU's 128 columns make no
    dearer than the head alone, so no value is ever moved across lanes."""
    from jax.experimental import pallas as pl

    prompt, block, c = (pl.program_id(i) for i in range(3))
    P, Q, width = head_dim, x_ref.shape[1], x_ref.shape[2]
    hb = width // P
    per = _tile_heads(P)
    W = per * P

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    # past the prompt: dt is 0 there, the state stands, nothing reads y
    @pl.when(c >= live_ref[prompt])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c < live_ref[prompt])
    def _():
        B, C = b_ref[0], c_ref[0]
        G = _dot(C, B, (1, 1))  # (Q, Q): C_t . B_s
        causal = (lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
                  <= lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
        lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
        # M is lower triangular: rows t in bands of T, each against the
        # positions s up to its end (3 of the 4 quarters of a 256 chunk)
        T = 128 if Q % 128 == 0 else Q

        def tile(i, carry):
            """One lane tile of ``x``: ``per`` heads."""
            at = pl.ds(pl.multiple_of(i * W, W), W)
            x, S = x_ref[0, :, at], s_ref[0, at, :]  # (Q, W), (W, state)
            x32 = x.astype(_F32)
            y = [jnp.zeros((T, W), _F32)] * (Q // T)
            scale, skip = jnp.zeros((Q, W), _F32), jnp.zeros((1, W), _F32)
            keep, to_end = [], []
            for k in range(per):
                h = i * per + k
                col = col_ref[0, 0, :, pl.ds(h, 1)]  # (Q, 1)
                row, dt = row_ref[0, pl.ds(h, 1), :], dt_ref[0, pl.ds(h, 1), :]
                mine = (lane >= k * P) & (lane < (k + 1) * P)
                xh = jnp.where(mine, x32, 0.0).astype(x.dtype)
                for band in range(Q // T):
                    t, n = slice(band * T, (band + 1) * T), (band + 1) * T
                    # M[t, s] = exp(cs_t - cs_s) (C_t . B_s) dt_s for s <= t
                    M = jnp.where(causal[t, :n],
                                  jnp.exp(col[t] - row[:, :n]), 0.0)
                    M = M * G[t, :n] * dt[:, :n]
                    y[band] = y[band] + _dot(M, xh[:n], (1, 0))
                scale = jnp.where(mine, jnp.exp(col), scale)
                skip = jnp.where(mine, d_ref[block * hb + h], skip)
                end = row[:, Q - 1:Q]  # (1, 1)
                keep.append(jnp.broadcast_to(jnp.exp(end), (P, 1)))
                to_end.append(jnp.broadcast_to(dt * jnp.exp(end - row),
                                               (P, Q)))
            # C_t S_prev, grown by exp(cs_t), and D x beside the chunk's own
            y = jnp.concatenate(y, axis=0) + _dot(C, S, (1, 1)) * scale \
                + skip * x32
            y_ref[0, :, at] = y.astype(y_ref.dtype)
            # S = exp(cs_end) S_prev + (x dt to_end)^T B
            s_ref[0, at, :] = jnp.concatenate(keep, axis=0) * S + _dot(
                x32.T * jnp.concatenate(to_end, axis=0), B, (1, 0))
            return carry

        # traced once, unrolled where it is lowered: a loop the compiler
        # cannot schedule across costs a third more time (my chip runs, PR 50)
        lax.fori_loop(0, hb // per, tile, 0, unroll=True)


# lanes of x a block of the scan's kernel at most: 8 heads at the cells' head
# size, which keeps a grid step's blocks, the state and the products'
# intermediates within the chip's default scoped VMEM
_SCAN_LANES = 512


def _scan_heads(heads, head_dim):
    """Heads a block of the scan's kernel, or None where no block is whole
    tiles: a divisor of ``heads`` that fills whole lane tiles of ``x`` and
    whole sublane tiles of the per-head rows (or is every head); the most
    that fit ``_SCAN_LANES``, else the fewest."""
    per = _tile_heads(head_dim)
    fit = [h for h in range(per, heads + 1, per)
           if heads % h == 0 and (h * head_dim) % 128 == 0
           and (h % 8 == 0 or h == heads)]
    small = [h for h in fit if h * head_dim <= _SCAN_LANES]
    return max(small) if small else min(fit, default=None)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "head_dim",
                                             "state", "interpret"))
def _kernel_scan(xbc, dt, A, D, length, *, chunk, heads, head_dim, state,
                 interpret=False):
    """The Pallas formulation: grid ``(prompt, block of heads, chunk)``,
    :func:`_scan_kernel` a step.  The chunks of each prompt that hold a
    token, (b,) int32 from ``length``, are the scalar-prefetch operand: the
    index maps of every input stop at a prompt's last live chunk, so a dead
    step fetches nothing, and the kernel does no arithmetic there.  ``xbc`` goes
    in three times as it lies (the heads' block of ``x``, ``B``, ``C``: no
    slice is copied out first).  Jitted on its own so that a prefill
    program's layers trace and lower the kernel once (as
    :func:`_kernel_step`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, L = xbc.shape[:2]
    Q, nc, inner = chunk, L // chunk, _inner(xbc, heads, head_dim, state)
    hb = _scan_heads(heads, head_dim)
    blocks, width = heads // hb, hb * head_dim
    cs = jnp.cumsum((dt * A).reshape(b, nc, Q, heads), axis=2)
    cs = cs.reshape(b, L, heads)
    live = (length + Q - 1) // Q

    def upto(i, c, live):  # a prompt's chunk c, or its last live one
        return jnp.minimum(c, jnp.maximum(live[i] - 1, 0))

    rows = pl.BlockSpec((1, hb, Q), lambda i, j, c, live:
                        (i, j, upto(i, c, live)))
    xbc_block = lambda n, at: pl.BlockSpec(  # noqa: E731
        (1, Q, n), lambda i, j, c, live: (i, upto(i, c, live), at(j)))
    y, S = pl.pallas_call(
        functools.partial(_scan_kernel, head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, blocks, nc),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      xbc_block(width, lambda j: j),
                      xbc_block(state, lambda j: inner // state),
                      xbc_block(state, lambda j: inner // state + 1),
                      pl.BlockSpec((1, 1, Q, hb), lambda i, j, c, live:
                                   (i, j, upto(i, c, live), 0)),
                      rows, rows],
            out_specs=[pl.BlockSpec((1, Q, width),
                                    lambda i, j, c, live: (i, c, j)),
                       pl.BlockSpec((1, width, state),
                                    lambda i, j, c, live: (i, j, 0))]),
        out_shape=[jax.ShapeDtypeStruct((b, L, inner), xbc.dtype),
                   jax.ShapeDtypeStruct((b, inner, state), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan", interpret=interpret,
    )(live, D, xbc, xbc, xbc,
      cs.reshape(b, L, blocks, hb).swapaxes(1, 2), cs.swapaxes(1, 2),
      dt.swapaxes(1, 2))
    return y, S.reshape(b, heads, head_dim, state)


def scan_formulation(platform, L, heads, head_dim, state, dtype, is_train,
                     chunk=256):
    """Which formulation ``_contrib_SSMScan`` runs: ``"pallas"`` -- the
    kernel that walks a prompt's live chunks one at a time -- where the
    operands live on a TPU, the op is not being differentiated (the kernel
    has no backward: a training graph keeps the XLA form and its gradient)
    and a chunk, a head and a head's state are whole tiles; ``"xla"`` --
    the whole bucket at once -- anywhere else.  An observation of the
    operands, as :func:`step_formulation` is."""
    tiled = (jnp.dtype(dtype) in (jnp.dtype(_BF16), jnp.dtype(_F32))
             and min(int(chunk), L) % 16 == 0 and state % 128 == 0
             and (heads * head_dim) % state == 0
             and _scan_heads(heads, head_dim) is not None)
    return ("pallas" if platform == "tpu" and tiled and not is_train
            else "xla")


def ssm_scan(xbc, dt_raw, A_log, D, dt_bias, length=None, *, heads,
             head_dim, state, chunk=256, scan=_chunked_scan):
    """Prefill form: the chunked scan.  ``xbc`` (b, L, heads*head_dim +
    2*state) after the convolution, ``dt_raw`` (b, L, heads), ``A_log``,
    ``D``, ``dt_bias`` (heads,), ``length`` (b,) or None.  Inside a chunk
    of ``chunk`` positions the outputs are one masked product of the decay
    matrix, between chunks the state is carried by a short recurrence:
    ``scan`` is :func:`_chunked_scan` over the whole bucket (here, and
    wherever :func:`scan_formulation` says ``"xla"``) or
    :func:`_kernel_scan` over each prompt's live chunks.  Returns ``y`` (b,
    L, heads*head_dim) in ``xbc``'s dtype and the final state (b, heads,
    head_dim, state), float32, as it stands after each prompt's TRUE
    length."""
    b, L = xbc.shape[:2]
    dt, A = _dt_and_a(dt_raw, A_log, dt_bias)
    if length is None:
        length = jnp.full((b,), L, jnp.int32)
    else:
        length = jnp.minimum(length.astype(jnp.int32), L)
        dt = jnp.where(jnp.arange(L)[None, :, None] < length[:, None, None],
                       dt, 0.0)
    Q = min(int(chunk), L)
    pad = -L % Q
    if pad:  # positions past the end: dt 0, nothing moves
        xbc, dt = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (xbc, dt))
    y, S = scan(xbc, dt, A, D.astype(_F32), length, chunk=Q, heads=heads,
                head_dim=head_dim, state=state)
    return y[:, :L].astype(xbc.dtype), S


def _routed_step(decay, u, B, C, states, slot):
    """The XLA formulation, and the kernel's oracle.  One fused multiply-add
    over the WHOLE plane and one reduction over its last axis: the plane is
    read once and written once, in place when it is donated, with no gather
    and no scatter (the lanes' small vectors are routed to slot order
    instead, :func:`_route`); a slot no lane names decays by exactly 1 and
    takes exactly 0, so it keeps its bits.  The engine holds one slot a lane
    and the scratch slot, so a full step moves the lanes' state and one slot
    more."""
    route = _route(slot, states.shape[0])
    idle = 1.0 - jnp.any(route, axis=0).astype(_F32)  # (slots,)
    decay = _to_slots(route, decay) + idle[:, None]
    u, Bs, Cs = (_to_slots(route, a) for a in (u, B, C))
    S = decay[:, :, None, None] * states.astype(_F32) \
        + u[..., None] * Bs[:, None, None, :]
    y = _to_lanes(route, jnp.sum(S * Cs[:, None, None, :], axis=-1))
    return y, S.astype(states.dtype)


# bytes of one block of the plane in VMEM: the kernel's pipeline holds four
# (two in flight each way) and the kernel a fifth, and its first read and
# last write hide behind nothing, so a block is a small part of a lane's
# slot (2 MB at the cell's shapes) and still a DMA long enough to run at
# the rate the HBM gives reads and writes together
_BLOCK_BYTES = 1 << 20


def _head_block(heads, head_dim, state):
    """Heads a block of the kernel: the most that divide ``heads`` and fit
    ``_BLOCK_BYTES``."""
    most = max(1, _BLOCK_BYTES // (4 * head_dim * state))
    return max(h for h in range(1, most + 1) if heads % h == 0)


def _step_kernel(slot_ref, decay_ref, u_ref, b_ref, c_ref, s_in, y_ref,
                 s_out, taken):
    """One lane, one block of its heads.  ``s_in`` / ``s_out`` are the same
    block of the plane (the lane's slot, by the index map), ``u_ref`` and
    ``y_ref`` (head_dim, heads of the block): a head's column lies along
    the sublanes, as a state tile's rows do.  Two passes over the block's
    heads, what the state takes (``u (outer) B``: a broadcast along the
    lanes) into ``taken`` first, then the update and its sum over the
    lanes: interleaved head by head the two cross-lane operations stall
    each other (124 us of arithmetic a call at the cell's shapes against
    44, where the DMAs take 105: my chip runs, PR 33)."""
    from jax.experimental import pallas as pl

    lane, block = pl.program_id(0), pl.program_id(1)
    heads = s_in.shape[1]
    live = slot_ref[lane] > 0

    @pl.when(live)
    def _():
        B, C = b_ref[pl.ds(lane, 1), :], c_ref[pl.ds(lane, 1), :]  # (1, N)
        u = u_ref[0, 0]
        for h in range(heads):
            taken[h] = u[:, h:h + 1] * B
        at = lax.broadcasted_iota(jnp.int32, u.shape, 1)
        y = jnp.zeros(u.shape, _F32)
        for h in range(heads):
            S = decay_ref[lane, block * heads + h] * s_in[0, h] + taken[h]
            s_out[0, h] = S
            y = jnp.where(at == h, jnp.sum(S * C, axis=-1, keepdims=True), y)
        y_ref[0, 0] = y

    # parked on scratch: the slot's bits go back as they came (several
    # padded lanes name it at once), and the lane's y is D x alone
    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_in[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_step(decay, u, B, C, states, slot, interpret=False):
    """The Pallas formulation: the LIVE lanes' slots only, where they lie.
    ``slot`` is the scalar-prefetch operand and the plane's index map reads
    it, so each grid step's block is ``(1, heads a block, head_dim, state)``
    of the plane at ``slot[lane]``, fetched and written back by the call's
    own pipeline; the plane is aliased to its output, so a donated one goes
    through in place and a slot no lane names is not touched at all (XLA
    sees no whole-plane operand to stage in another memory space).  Each
    live lane owns its slot (the pool's contract): in-place blocks never
    overlap.  ``decay`` (lanes, heads) comes in SMEM (a scalar a head),
    ``B`` / ``C`` (lanes, state) whole in VMEM, ``u`` (lanes, heads,
    head_dim) with the heads of a block last.  Jitted on its own so that a
    lane program's 36 call sites trace and lower the kernel once a start
    (as ``ops/paged.py`` ``_kernel_decode``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, head_dim = u.shape
    state = states.shape[-1]
    hb = _head_block(heads, head_dim, state)
    blocks = heads // hb
    columns = pl.BlockSpec((1, 1, head_dim, hb),
                           lambda lane, b, slot: (lane, b, 0, 0))
    whole = pl.BlockSpec((lanes, state), lambda lane, b, slot: (0, 0))
    in_place = pl.BlockSpec((1, hb, head_dim, state),
                            lambda lane, b, slot: (slot[lane], b, 0, 0))
    y, S = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(lanes, blocks),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), columns, whole,
                      whole, in_place],
            out_specs=[columns, in_place],
            scratch_shapes=[pltpu.VMEM((hb, head_dim, state), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((lanes, blocks, head_dim, hb), _F32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operands count the scalar-prefetch one: the plane is 5
        input_output_aliases={5: 1},
        name="ssm_step", interpret=interpret,
    )(slot, decay, u.reshape(lanes, blocks, hb, head_dim).swapaxes(2, 3), B,
      C, states)
    return y.swapaxes(2, 3).reshape(lanes, heads, head_dim), S


def step_formulation(platform, head_dim, state, dtype):
    """Which formulation ``_contrib_SSMStep`` runs: ``"pallas"`` -- the
    kernel that steps the live lanes' slots where they lie -- where the
    operands live on a TPU, the plane is float32 and a head's state is
    whole tiles (a block of heads is then one contiguous piece of a slot);
    ``"xla"`` -- one pass over the whole plane -- anywhere else.  An
    observation of the operands, as ``ops/paged.py`` ``decode_formulation``
    is: no attribute or environment variable chooses."""
    tiled = (jnp.dtype(dtype) == jnp.float32 and head_dim % 8 == 0
             and state % 128 == 0)
    return "pallas" if platform == "tpu" and tiled else "xla"


def ssm_step(xbc, dt_raw, A_log, D, dt_bias, states, slot, *, heads,
             head_dim, state, step=_routed_step):
    """Decode form: one token a lane.  ``xbc`` (lanes, ...), ``dt_raw``
    (lanes, heads), ``states`` (slots, heads, head_dim, state) float32 the
    plane, ``slot`` (lanes,) int32, every live lane a slot of its own::

        S[slot] <- exp(dt A) S[slot] + (dt x) (outer) B      y = S C + D x

    for every lane at a slot above 0; a lane parked on the scratch slot 0
    leaves it alone and gets ``D x``.  XLA makes the lanes' small vectors;
    ``step`` moves the state: :func:`_routed_step` over the whole plane
    (here, and wherever :func:`step_formulation` says ``"xla"``) or
    :func:`_kernel_step` over the live lanes' slots.  Returns ``y`` (lanes,
    heads*head_dim) and the plane."""
    x, B, C = _split_xbc(xbc, heads, head_dim, state)
    dt, A = _dt_and_a(dt_raw, A_log, dt_bias)
    y, S = step(jnp.exp(dt * A), dt[:, :, None] * x, B, C, states, slot)
    y = y + D.astype(_F32)[None, :, None] * x
    return y.reshape(xbc.shape[0], heads * head_dim).astype(xbc.dtype), S


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
    from .interpret import platform_of

    sizes, chunk = _sizes(attrs), int(attrs.get("chunk", 256))
    scan = {"pallas": _kernel_scan, "xla": _chunked_scan}[scan_formulation(
        platform_of(data), data.shape[1], sizes["heads"], sizes["head_dim"],
        sizes["state"], data.dtype, getattr(opctx, "is_train", False),
        chunk)]
    return ssm_scan(data, dt, A_log, D, dt_bias,
                    length[0] if length else None, chunk=chunk, scan=scan,
                    **sizes)


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
    from .interpret import platform_of

    sizes = _sizes(attrs)
    step = {"pallas": _kernel_step, "xla": _routed_step}[step_formulation(
        platform_of(data, states), sizes["head_dim"], sizes["state"],
        states.dtype)]
    return ssm_step(data, dt, A_log, D, dt_bias, states,
                    state_slot.astype(jnp.int32), step=step, **sizes)
