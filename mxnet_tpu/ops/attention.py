"""Fused attention — Pallas TPU kernels, forward AND backward (new
capability; the reference predates attention, SURVEY.md §5.7).

Forward: the flash-attention schedule — Q tiles on the grid, K/V STREAMED
block-by-block through VMEM via the grid's innermost dimension (BlockSpec
index maps; nothing is staged whole), online-softmax (m, l, acc) carried in
VMEM scratch across K steps, logsumexp written out for the backward.  What
the schedule is since PR 40 (one kernel; causal or not, the count and the
size of the blocks decide which of its bodies exist):
  * the running maximum and sum live as [rows, 128], every lane of a row
    the same value — the layout a row reduction leaves and a broadcast
    along the keys reads — and not as [rows] vectors a row a lane, whose
    five relayouts a block were half the kernel's time;
  * under the causal mask a dead step revisits the block that is in VMEM
    (the K/V index maps are clamped to the q tile's last live block), a
    block wholly under the diagonal builds no mask, and a square block on
    the diagonal leaves out its tiles above it;
  * a fetched block is worked through in 512 x 512 tiles unrolled in the
    body, so the tiles of different row groups (independent chains of
    product, row maximum, exp2, product) overlap; a call that names no
    block sizes fetches up to 2048 rows a block.
Measured on v5e (my chip runs, PR 40; device trace, causal, bfloat16,
4 x 2048 x 16 x 128, the LM cell's shape): 1.995 ms a call before, 0.868
with 512 x 512 blocks, 0.755 with 1024, 0.620 with the default 2048 (ten
live tiles a (batch, head) pair, 0.97 us each, 70 % of the MXU's peak on
the FLOPs executed); non-causal 2.893 -> 0.879.
Since PR 52 the same kernel takes, as static parameters of ``_fwd_call``:
  * a group: query head ``h`` reads K/V head ``h // group`` through the K/V
    index map, K and V never repeated in memory;
  * a band (``window`` > 0: token ``t`` attends to ``t - window < u <= t``):
    the grid's innermost dimension is the most blocks a q tile's band
    touches and its steps start at the tile's first live block, the mask is
    the diagonal's test, the band's or both by which kind of block a step
    holds;
  * operands and result by rows (``heads``: a token one row of its heads,
    head ``h`` column block ``h``), with no transpose around the call.
``ops/paged.py`` runs the hybrid block's sequence attention through it
(forward only, ``sequence_formulation``).  Measured on v5e (my chip runs,
PR 52; host clock, one prompt of 4,096, 64 heads over 8, a window of 512):
1.50 ms a call in 512 x 512 blocks (two steps a q tile, 1.56 us a masked
tile) where XLA's query blocks take 4.88; 1,024-row blocks 1.76, 256 2.43.
With no group, window or rows the traced program is PR 40's.

Backward: two Pallas kernels in the flash-v2 style, recomputing P per block
from (Q, K, logsumexp):
  * dQ kernel — grid over Q tiles, K/V streamed innermost,
    dQ += (P ∘ (dO·Vᵀ − Δ))·K with Δ = rowsum(dO ∘ O);
  * dK/dV kernel — grid over K tiles, Q/dO streamed innermost,
    dV += Pᵀ·dO,  dK += (P ∘ (dO·Vᵀ − Δ))ᵀ·Q.
Both run O(s²) time in O(s) memory — sequence length is bounded by HBM,
not VMEM, so ≥16k-token training steps fit on one chip.  Their index maps
are clamped at the diagonal like the forward's (same shape, PR 40: dQ
1.000 -> 0.975 ms, dK/dV 1.400 -> 1.265); a second, unmasked body for the
blocks under the diagonal made both SLOWER (1.113 / 1.325) and is not there.

On a non-TPU device the same kernels run in Pallas interpret mode (chosen
from where the operands live — ops/interpret.py), so the CPU test mesh
exercises the real kernel logic. Registered through the
public ``mx.register_pallas_op`` mechanism (its first user) as
``_contrib_FlashAttention`` (inputs [b, s, h, d]); also usable
functionally and as ``ulysses_attention(attn_fn=flash_attention)``.
"""
from __future__ import annotations

import functools

import numpy as np

from .interpret import interpret_for, over_batch_shards

_NEG = -1e30
# exp2-based softmax: fold log2(e) into the QK scale so the kernel's
# exponentials are exp2 (the VPU's native transcendental; jnp.exp lowers
# to exp2(x*log2e) anyway — folding removes that multiply from the
# bq*bk-element hot loop). The lse written at the boundary stays NATURAL
# log (the ring/backward contract).
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453
# The three pallas_calls below pass no ``dimension_semantics`` grid hint
# (bh and q-tile parallel, the stream dim sequential), on a chip
# measurement: on v5e at s=8k d=128 the hint made the train step ~40%
# slower and erratic from run to run (20.7 against 34.3 TFLOP/s at bq=512
# bk=1024); Mosaic's default sequential pipelining double-buffers the
# streamed blocks on its own.


def _reference_attention(q, k, v, causal, scale):
    """Dense oracle — the single implementation lives in parallel.ring."""
    from ..parallel.ring import local_attention

    return local_attention(q, k, v, causal=causal, scale=scale)


def _pick_block(block, seq):
    """Largest block <= ``block`` that divides ``seq``, halving from the
    requested size. Sequences shorter than the requested block run as one
    whole-sequence block (legal under the Mosaic equal-to-dim rule);
    longer non-divisible sequences raise rather than silently staging an
    unbounded (seq, seq) score tile into VMEM."""
    b = min(block, seq)
    while b > 128 and seq % b:
        b //= 2
    if seq % b:
        if seq <= block:
            return seq
        raise ValueError(
            "flash_attention: sequence length %d is not divisible by any "
            "block size <= %d; pad the sequence or pass block sizes that "
            "divide it" % (seq, block))
    return b


# ---------------------------------------------------------------------------
# forward kernel — K/V streamed over the innermost grid dimension
# ---------------------------------------------------------------------------

_LANES = 128
# A fetched block is worked through in compute tiles of at most this many
# rows and keys, unrolled in the body: the tiles of different row groups are
# independent chains (product, row maximum, exp2, product), which the
# scheduler overlaps; a tile of 256 keys is slower on v5e (PERF.md, PR 40).
_FWD_TILE = 512
# The forward's own default block: as many rows as keep one operand block
# within 512 KiB, at most 2048 (2048 at d=128 in bfloat16: 10 MB of VMEM
# with both buffers, the state and a tile's scores).
_FWD_BLOCK_BYTES = 512 * 1024
_FWD_BLOCK_MAX = 2048


def _lanes(x, n):
    """``x`` [rows, 128], every lane of a row the same value, as [rows, n]
    with no relayout where ``n`` is a multiple of the lane count."""
    import jax.numpy as jnp

    if n % _LANES == 0:
        return x if n == _LANES else jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _last_live_block(i, bq, bk):
    """Index of the last K/V block q tile ``i`` reads under the causal mask.
    The grid still steps past it; the index maps hand those steps the block
    that is already in VMEM, so the pipeline issues no copy for them."""
    return (i * bq + bq - 1) // bk


def _first_live_block(i, bq, bk, window):
    """Index of the first K/V block q tile ``i`` (traced, or an array of
    tiles) reads under a band of ``window`` keys: the one that holds key ``i
    * bq - window + 1``.  The grid's steps of a tile start there."""
    return (i * bq - window + 1).clip(0) // bk


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, bq, bk, nq, nk, tq, tk, scale, causal, window=0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)
    d = q_ref.shape[-1]
    # the K/V block this step holds: a band's steps count from the q tile's
    # first live block (``nk`` is then the most blocks a tile's band touches)
    kb = j + _first_live_block(qi, bq, bk, window) if window else j

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(a, c, kind):
        """Online-softmax update of row group ``a`` with key chunk ``c``
        (m and l as [rows, 128], every lane of a row the same: see the
        module's note).  ``kind``: ``open``, or which tests mask it:
        ``masked`` (the diagonal's), ``banded`` (the band's far side: a row
        that it leaves no key of the tile weighs the tile's keys equally
        until a later tile's maximum, which its own position always gives
        it, scales that away) or ``both``."""
        rows = slice(a * tq, (a + 1) * tq)
        keys = slice(c * tk, (c + 1) * tk)
        # dots stay in the input dtype (bf16 on TPU -> MXU) with f32
        # accumulation; only the softmax state is f32. Scores live in the
        # base-2 domain (scale folded with log2e — see _LOG2E note).
        vblk = v_ref[0, keys, :]
        s = jax.lax.dot_general(q_ref[0, rows, :], k_ref[0, keys, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        if kind != "open":
            q_pos = qi * bq + a * tq \
                + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            k_pos = kb * bk + c * tk \
                + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
            keep = q_pos - k_pos < window if kind == "banded" \
                else q_pos >= k_pos
            if kind == "both":
                keep = jnp.logical_and(keep, q_pos - k_pos < window)
            s = jnp.where(keep, s, _NEG)
        m = m_scr[rows, :]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp2(s - _lanes(m_new, tk))
        corr = jnp.exp2(m - m_new)
        l_scr[rows, :] = l_scr[rows, :] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[rows, :] = acc_scr[rows, :] * _lanes(corr, d) \
            + jax.lax.dot_general(p.astype(vblk.dtype), vblk,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_scr[rows, :] = m_new

    def block(status):
        for c in range(bk // tk):
            for a in range(bq // tq):
                kind = status(a, c)
                if kind != "dead":
                    tile(a, c, kind)

    def on_diagonal(a, c):
        """Tile of a block whose first row and first key are the same
        position (bq == bk): what the diagonal leaves of it is static."""
        if (c + 1) * tk - 1 <= a * tq:
            return "open"
        if c * tk > (a + 1) * tq - 1:
            return "dead"
        return "masked"

    def in_band(status):
        """``status`` of a tile on the diagonal block, with the band's test
        where the tile's last row reaches ``window`` or more past its first
        key."""
        def banded(a, c):
            kind = status(a, c)
            if a * tq - (c + 1) * tk + 1 >= window:
                return "dead"
            if kind == "dead" or (a + 1) * tq - 1 - c * tk < window:
                return kind
            return {"open": "banded", "masked": "both"}[kind]

        return banded

    if not causal:
        block(lambda a, c: "open")
    elif window:
        # A band's blocks, by two tests each: wholly under the diagonal or
        # crossed by it, wholly inside the band or crossed by its far side.
        # Which of the four kinds the grid holds is static (at 512 x 512
        # blocks under a window of 512: the block before the diagonal,
        # crossed by the band, then the diagonal's, inside it); a dead
        # step (a first tile's second) runs nothing and fetched nothing.
        def place(i, b):
            """Whether K/V block ``b`` holds a key q tile ``i`` reads, lies
            wholly under its diagonal, wholly inside its band (traced, or
            of Python numbers)."""
            return (b * bk <= i * bq + bq - 1, (b + 1) * bk - 1 <= i * bq,
                    i * bq + bq - 1 - b * bk < window)

        first = _first_live_block(np.arange(nq), bq, bk, window).tolist()
        kinds = sorted({place(i, b)[1:] for i in range(nq)
                        for b in range(first[i], first[i] + nk)
                        if place(i, b)[0]})
        def tiles_of(below, within):
            """``status`` of the tiles of a block of that kind."""
            if below:
                return lambda a, c: "open" if within else "banded"
            if bq != bk:
                return lambda a, c: "masked" if within else "both"
            return on_diagonal if within else in_band(on_diagonal)

        live, under, inside = place(qi, kb)
        for below, within in kinds:
            pl.when(functools.reduce(jnp.logical_and, (
                live, under if below else jnp.logical_not(under),
                inside if within else jnp.logical_not(inside))))(
                    functools.partial(block, tiles_of(below, within)))
    else:
        # Three kinds of block: wholly under the diagonal (no mask is
        # built), crossed by it (masked; where the block is square, its
        # tiles above the diagonal are not computed at all), and dead
        # (nothing runs, and nothing was fetched: _last_live_block).
        under = (j + 1) * bk - 1 <= qi * bq
        live = j * bk <= qi * bq + bq - 1
        if (nq - 1) * bq >= bk - 1:   # else no q tile has a block under it
            pl.when(under)(lambda: block(lambda a, c: "open"))
        pl.when(jnp.logical_and(live, jnp.logical_not(under)))(
            lambda: block(on_diagonal if bq == bk
                          else lambda a, c: "masked"))

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        lsafe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / _lanes(lsafe, d)).astype(o_ref.dtype)
        # back to natural log at the boundary (ring/backward contract), and
        # to a row a lane for the [1, 1, bq] output: the one relayout a q
        # tile pays
        lse_ref[0, 0] = ((m_scr[...] + jnp.log2(lsafe)) * _LN2)[:, 0]


@functools.lru_cache(maxsize=None)
def _fwd_call(causal, scale, bq, bk, interpret, group=1, window=0, heads=0):
    """The forward ``pallas_call`` on flattened [b*h, s, d] operands, jitted
    on its own: a graph of N attention layers traces and lowers the
    kernel's unrolled body once, not N times.  ``group`` query heads read
    one K/V head (``kt`` / ``vt`` [b*h/group, s, d], through the index map:
    never repeated in memory); ``window`` > 0 is a band under the diagonal
    (token ``t`` attends to ``t - window < u <= t``), whose q tiles step
    through the blocks their band touches and no other.  With ``heads`` the
    operands and the result hold a token as ONE row, [b, s, heads * d] and
    [b, s, heads / group * d], as a projection leaves it and the paged
    planes keep it: head ``h`` is column block ``h`` of the row, and nothing
    is transposed around the call."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(qt, kt, vt):
        # where (batch, head) pair ``n``'s block of rows lies, and its K/V
        # head's (``group`` 1 adds nothing to the maps PR 40 measured)
        shared = (lambda h: h) if group == 1 else (lambda h: h // group)
        if heads:
            bh, sq, d = qt.shape[0] * heads, qt.shape[1], \
                qt.shape[2] // heads
            q_at = lambda n, block: (n // heads, block, n % heads)
            kv_at = lambda n, block: (n // heads, block, shared(n % heads))
        else:
            bh, sq, d = qt.shape
            q_at = lambda n, block: (n, block, 0)
            kv_at = lambda n, block: (shared(n), block, 0)
        nq, nk = sq // bq, kt.shape[1] // bk
        if window:
            tiles = np.arange(nq)
            nk = int((_last_live_block(tiles, bq, bk)
                      - _first_live_block(tiles, bq, bk, window)).max()) + 1
        kernel = functools.partial(
            _fwd_kernel, bq=bq, bk=bk, nq=nq, nk=nk,
            tq=_FWD_TILE if bq % _FWD_TILE == 0 else bq,
            tk=_FWD_TILE if bk % _FWD_TILE == 0 else bk,
            scale=scale, causal=causal, window=window)
        if window:
            kv_block = lambda i, j: jnp.minimum(
                j + _first_live_block(i, bq, bk, window),
                _last_live_block(i, bq, bk))
        elif causal:
            kv_block = lambda i, j: jnp.minimum(
                j, _last_live_block(i, bq, bk))
        else:
            kv_block = lambda i, j: j
        q_map = lambda n, i, j: q_at(n, i)
        kv_map = lambda n, i, j: kv_at(n, kv_block(i, j))
        # lse carries a singleton middle dim so its block's trailing dims
        # (1, bq) satisfy the Mosaic tiling rule (second-to-last equals the
        # array dim, last divisible by 128); squeezed before returning
        vma = jax.typeof(qt).vma
        return pl.pallas_call(
            kernel,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qt.shape, qt.dtype, vma=vma),
                jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32, vma=vma)],
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
            interpret=interpret,
            # the kernel's name in the compiled program and the device trace
            name="flash_fwd",
        )(qt, kt, vt)

    return jax.jit(call)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=0, rows=False):
    """Returns (o, lse) with o: [b, s, h, d], lse: [b*h, s] (f32).  ``k`` /
    ``v`` may hold fewer heads than ``q`` (grouped-query: query head ``i``
    reads K/V head ``i // (h / kv_heads)``); ``window`` > 0 bands the causal
    mask; ``rows`` hands the kernel the operands as they are, a token one
    row of its heads (on a TPU for heads of whole lane tiles), where the
    default is by heads, [b*h, s, d], a transpose each way."""
    b, sq, h, d = q.shape
    sk, kv_heads = k.shape[1:3]
    if h % kv_heads or (window and not causal):
        raise ValueError("flash_attention: %d query heads over %d K/V heads,"
                         " window %d, causal %s" % (h, kv_heads, window,
                                                    causal))
    bq = _pick_block(block_q, sq)
    bk = _pick_block(block_k, sk)
    call = functools.partial(_fwd_call, bool(causal), float(scale), bq, bk,
                             bool(interpret), h // kv_heads, int(window))
    if rows:
        o, lse = call(h)(*(x.reshape(x.shape[:2] + (-1,))
                           for x in (q, k, v)))
        return o.reshape(q.shape), lse.reshape(b * h, sq)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kv_heads, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kv_heads, sk, d)
    o, lse = call(0)(qt, kt, vt)
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse.reshape(b * h, sq)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, bq, bk, nk, scale, causal):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = (j * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        # s and lse both carried in the base-2 domain
        p = jnp.exp2(s - (lse * _LOG2E)[:, None])
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(kblk.dtype)
        acc_scr[...] += jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, bq, bk, nq, scale,
                    causal):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (i * bq + bq - 1 >= kj * bk) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp2(s - (lse * _LOG2E)[:, None])  # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, d]

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_precompute(q, o, lse, do):
    """Loop-invariant backward inputs: flattened q/dO layouts, the global
    row lse, and delta_i = rowsum(dO ∘ O) (cheap elementwise+reduce, fused
    by XLA). Split out so callers that sweep many K/V blocks against one Q
    (the ring backward) compute these once, not per block. lse/delta get a
    singleton middle dim so their (1, 1, bq) blocks pass the Mosaic
    trailing-dims tiling rule (see _flash_forward)."""
    import jax.numpy as jnp

    b, sq, h, d = q.shape
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    dot = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    ot = o.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)
    return (qt, dot, lse.reshape(b * h, 1, sq),
            delta.reshape(b * h, 1, sq))


def _flash_backward(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                    interpret, pre=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(block_q, sq)
    bk = _pick_block(block_k, sk)
    nq, nk = sq // bq, sk // bk
    if pre is None:
        pre = _flash_bwd_precompute(q, o, lse, do)
    qt, dot, lse3, delta3 = pre
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    from jax.experimental.pallas import tpu as pltpu

    def scratch(shape):
        return pltpu.VMEM(shape, jnp.float32)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(qt).vma)

    # Under the causal mask a dead step (its body is guarded off) is handed
    # the block the live step beside it holds: no copy is issued for it.
    # dQ's dead steps follow a q tile's last live K/V block; dK/dV's come
    # before a k tile's first live q block.
    if causal:
        kv_of = lambda i, j: jnp.minimum(j, _last_live_block(i, bq, bk))
        q_of = lambda j, i: jnp.maximum(
            i, jnp.minimum((j * bk) // bq, nq - 1))
    else:
        kv_of = lambda i, j: j
        q_of = lambda j, i: i

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                          causal=causal),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),   # q
            pl.BlockSpec((1, bk, d),
                         lambda bh, i, j: (bh, kv_of(i, j), 0)),     # k
            pl.BlockSpec((1, bk, d),
                         lambda bh, i, j: (bh, kv_of(i, j), 0)),     # v
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),   # do
            pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=sds((b * h, sq, d), q.dtype),
        scratch_shapes=[scratch((bq, d))],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq, scale=scale,
                          causal=causal),
        grid=(b * h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda bh, j, i: (bh, q_of(j, i), 0)),      # q
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),   # v
            pl.BlockSpec((1, bq, d),
                         lambda bh, j, i: (bh, q_of(j, i), 0)),      # do
            pl.BlockSpec((1, 1, bq),
                         lambda bh, j, i: (bh, 0, q_of(j, i))),      # lse
            pl.BlockSpec((1, 1, bq),
                         lambda bh, j, i: (bh, 0, q_of(j, i))),      # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[sds((b * h, sk, d), k.dtype),
                   sds((b * h, sk, d), v.dtype)],
        scratch_shapes=[scratch((bk, d)), scratch((bk, d))],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse3, delta3)

    unflat = lambda t, s: t.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


# ---------------------------------------------------------------------------
# public functional API
# ---------------------------------------------------------------------------


_DEFAULT_BLOCK = 512


def _resolve(block_q, block_k, seq_q, seq_k, head_dim, dtype, causal,
             window=0):
    """((forward block_q, block_k), (backward block_q, block_k)) of a call.
    Explicit ints are respected as given by all three kernels.  What is
    left None takes the measured defaults: 512 for the backward kernels,
    and for the forward the most rows that keep an operand block within
    ``_FWD_BLOCK_BYTES`` (it works a fetched block through in ``_FWD_TILE``
    tiles, so its block is how much one grid step holds, not how much one
    product covers), under a band (``window`` > 0) one tile: a larger block
    fetches keys the band leaves out.  Each is clamped by ``_pick_block``."""
    import jax.numpy as jnp

    cap = _FWD_TILE if window else _FWD_BLOCK_MAX
    while cap > _DEFAULT_BLOCK and \
            cap * head_dim * jnp.dtype(dtype).itemsize > _FWD_BLOCK_BYTES:
        cap //= 2

    def wide(seq):
        """Whole tiles only: a length no big block divides runs the
        forward at the backward's default."""
        b = cap
        while b > _DEFAULT_BLOCK and seq % b:
            b //= 2
        return b

    def pick(default_q, default_k):
        return (_pick_block(int(block_q or default_q), seq_q),
                _pick_block(int(block_k or default_k), seq_k))

    return (pick(wide(seq_q), wide(seq_k)),
            pick(_DEFAULT_BLOCK, _DEFAULT_BLOCK))


def resolve_blocks(block_q, block_k, seq_q, seq_k, head_dim=128,
                   dtype="bfloat16", causal=False):
    """The EFFECTIVE (block_q, block_k) a call's backward kernels run with
    (and its forward, wherever a block size was given): explicit ints are
    respected as-is, None takes the measured default (512/512 — PERF.md's
    v5e-validated config); either way the result is clamped by
    ``_pick_block``."""
    return _resolve(block_q, block_k, seq_q, seq_k, head_dim, dtype,
                    causal)[1]


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q=None, block_k=None, interpret=None):
    """Exact fused attention, Pallas fwd+bwd. q, k, v: [b, seq, heads, d].

    Blocks are clamped to the sequence length for short inputs.  Passing
    None (the default) takes the measured defaults; explicit block sizes
    are always respected, by all three kernels.  The defaults (PERF.md
    section 6, PR 40, has the readings): 512 x 512 for the two backward
    kernels (v5e, d=128, s=8k, measured before PR 24:
    512-wide tiles ran ~3x faster than 128; v5e, causal, bfloat16,
    4 x 2048 x 16 x 128, PR 40: dQ 0.975 and dK/dV 1.265 ms a call, at
    1024 x 1024 0.99 and 1.26 by the host's clock where 512 reads 1.14
    and 1.35); for the forward a block of up to 2048 rows worked through
    in 512 x 512 tiles (the same shape, PR 40: 0.620 ms where one
    512 x 512 tile a grid step takes 0.868 and the kernel before PR 40
    1.995).
    ``interpret`` None picks compiled-vs-interpreted from the device the
    operands live on (ops/interpret.py)."""
    import jax

    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    fwd_blocks, bwd_blocks = _resolve(block_q, block_k, q.shape[1],
                                      k.shape[1], q.shape[-1], q.dtype,
                                      causal)
    interpret = interpret_for("flash_attention", (q, k, v), interpret)

    @jax.custom_vjp
    def run(q, k, v):
        o, _ = _flash_forward(q, k, v, causal, scale, *fwd_blocks, interpret)
        return o

    def fwd(q, k, v):
        o, lse = _flash_forward(q, k, v, causal, scale, *fwd_blocks,
                                interpret)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        return _flash_backward(q, k, v, o, lse, g, causal, scale,
                               *bwd_blocks, interpret)

    run.defvjp(fwd, bwd)
    return run(q, k, v)


# ---------------------------------------------------------------------------
# registry op — first user of the public mx.register_pallas_op mechanism
# ---------------------------------------------------------------------------


def _attrs_config(attrs, q, k):
    """(causal, scale, forward blocks, backward blocks) for the registered
    op.  Attrs without pinned block sizes take the measured defaults — the
    fwd and bwd kernels see the same deterministic resolution for one
    (attrs, shapes) pair."""
    d = q.shape[-1]
    scale = attrs.get("scale")
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    causal = bool(attrs.get("causal", False))
    fwd_blocks, bwd_blocks = _resolve(
        attrs.get("block_q"), attrs.get("block_k"), q.shape[1], k.shape[1],
        d, q.dtype, causal)
    return causal, float(scale), fwd_blocks, bwd_blocks


def _fa_fn(attrs, query, key, value):
    causal, scale, blocks, _ = _attrs_config(attrs, query, key)
    interpret = interpret_for("_contrib_FlashAttention", (query, key, value))

    def kernel(q, k, v):
        return _flash_forward(q, k, v, causal, scale, *blocks, interpret)[0]

    return over_batch_shards(kernel)(query, key, value)


def _fa_fwd(attrs, query, key, value):
    causal, scale, blocks, _ = _attrs_config(attrs, query, key)
    interpret = interpret_for("_contrib_FlashAttention", (query, key, value))

    def kernel(q, k, v):
        return _flash_forward(q, k, v, causal, scale, *blocks, interpret)

    o, lse = over_batch_shards(kernel)(query, key, value)
    return o, (query, key, value, o, lse)


def _fa_bwd(attrs, res, ct):
    q, k, v, o, lse = res
    causal, scale, _, blocks = _attrs_config(attrs, q, k)
    interpret = interpret_for("_contrib_FlashAttention", (q, k, v))

    def kernel(q, k, v, o, lse, ct):
        return _flash_backward(q, k, v, o, lse, ct, causal, scale, *blocks,
                               interpret)

    return over_batch_shards(kernel)(q, k, v, o, lse, ct)


def _register():
    from .pallas_op import register_pallas_op
    from .param import Param

    # dogfooding the public user-kernel API — mx.register_pallas_op IS how
    # this framework's own flash attention becomes an op (MXRtc parity,
    # mxrtc.cc:117-135)
    register_pallas_op(
        "_contrib_FlashAttention", _fa_fn, bwd=_fa_bwd, fwd=_fa_fwd,
        inputs=("query", "key", "value"),
        params={"causal": Param(bool, False),
                "scale": Param("float-or-none", None),
                # None = the measured defaults
                "block_q": Param("int-or-none", None),
                "block_k": Param("int-or-none", None)},
        infer_shape=lambda attrs, s: (s, [s[0]], []),
        hint="flashattention")


_register()
