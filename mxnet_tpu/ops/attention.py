"""Fused attention — Pallas TPU kernels, forward AND backward (new
capability; the reference predates attention, SURVEY.md §5.7).

Forward: the standard flash-attention schedule — Q tiles on the grid, K/V
STREAMED block-by-block through VMEM via the grid's innermost dimension
(BlockSpec index maps; nothing is staged whole), online-softmax (m, l, acc)
carried in VMEM scratch across K steps, logsumexp written out for the
backward.

Backward: two Pallas kernels in the flash-v2 style, recomputing P per block
from (Q, K, logsumexp):
  * dQ kernel — grid over Q tiles, K/V streamed innermost,
    dQ += (P ∘ (dO·Vᵀ − Δ))·K with Δ = rowsum(dO ∘ O);
  * dK/dV kernel — grid over K tiles, Q/dO streamed innermost,
    dV += Pᵀ·dO,  dK += (P ∘ (dO·Vᵀ − Δ))ᵀ·Q.
Both run O(s²) time in O(s) memory — sequence length is bounded by HBM,
not VMEM, so ≥16k-token training steps fit on one chip.

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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, bq, bk, nk, scale, causal):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: blocks strictly above the diagonal are fully masked — skip
    # their MXU work entirely (the old fori_loop bounded the loop at the
    # diagonal; on a grid the block body is guarded instead)
    live = (j * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        # dots stay in the input dtype (bf16 on TPU -> MXU) with f32
        # accumulation; only the softmax state is f32. Scores live in the
        # base-2 domain (scale folded with log2e — see _LOG2E note).
        q = q_ref[0]
        kblk = k_ref[0]
        vblk = v_ref[0]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m = m_scr[...]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        corr = jnp.exp2(m - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        lsafe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / lsafe[:, None]).astype(o_ref.dtype)
        # back to natural log at the boundary (ring/backward contract)
        lse_ref[0, 0] = (m_scr[...] + jnp.log2(lsafe)) * _LN2


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    """Returns (o, lse) with o: [b, s, h, d], lse: [b*h, s] (f32)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = _pick_block(block_q, sq)
    bk = _pick_block(block_k, sk)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    nk = sk // bk
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk,
                               scale=scale, causal=causal)
    # lse carries a singleton middle dim so its block's trailing dims
    # (1, bq) satisfy the Mosaic tiling rule (second-to-last equals the
    # array dim, last divisible by 128); squeezed before returning
    vma = jax.typeof(qt).vma
    out_shape = [jax.ShapeDtypeStruct((b * h, sq, d), q.dtype, vma=vma),
                 jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32, vma=vma)]
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace
        name="flash_fwd",
    )(qt, kt, vt)
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

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                          causal=causal),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),   # v
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
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),   # do
            pl.BlockSpec((1, 1, bq), lambda bh, j, i: (bh, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda bh, j, i: (bh, 0, i)),   # delta
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


def _autotune_blocks(seq_q, seq_k, head_dim, dtype, causal):
    """Tuning-DB winner for this shape family, or None.  The record-mode
    tuning loop lowers the forward kernel per candidate at one head /
    batch 1 (the grid scales linearly in b*h, so the per-candidate
    RANKING is shape-family-wide) and scores by the XLA-cost-analysis
    roofline — CPU-runnable, no chip needed."""
    from .. import autotune

    if not autotune.enabled():
        return None
    key = {"seq_q": int(seq_q), "seq_k": int(seq_k),
           "head_dim": int(head_dim), "dtype": str(dtype),
           "causal": bool(causal)}

    def build(cand):
        import jax

        interpret = interpret_for("flash_attention")
        scale = 1.0 / np.sqrt(head_dim)

        def fwd(q, k, v):
            return _flash_forward(q, k, v, causal, scale,
                                  cand["block_q"], cand["block_k"],
                                  interpret)[0]

        sds = jax.ShapeDtypeStruct
        abstract = (sds((1, seq_q, 1, head_dim), dtype),
                    sds((1, seq_k, 1, head_dim), dtype),
                    sds((1, seq_k, 1, head_dim), dtype))
        return jax.jit(fwd), abstract

    def measure(cand):
        import time

        import jax
        import jax.numpy as jnp

        fn, abstract = build(cand)
        args = [jnp.zeros(a.shape, a.dtype) for a in abstract]
        compiled = fn.lower(*args).compile()
        jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        for _ in range(3):
            out = compiled(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 3 * 1e3

    return autotune.get_or_tune(
        "flash_attention", key,
        candidates=autotune.spaces.flash_blocks(seq_q, seq_k),
        build_fn=build, measure_fn=measure, default=None)


def resolve_blocks(block_q, block_k, seq_q, seq_k, head_dim=128,
                   dtype="bfloat16", causal=False):
    """The EFFECTIVE (block_q, block_k) a call runs with: explicit ints
    are respected as-is, None consults the autotuner (winner for this
    shape family when enabled) and falls back to the measured default
    (512/512 — PERF.md's v5e-validated config); either way the result
    is clamped by ``_pick_block``."""
    if block_q is None or block_k is None:
        tuned = None
        try:
            tuned = _autotune_blocks(seq_q, seq_k, head_dim, dtype, causal)
        except Exception:
            tuned = None
        if block_q is None:
            block_q = (tuned or {}).get("block_q", _DEFAULT_BLOCK)
        if block_k is None:
            block_k = (tuned or {}).get("block_k", _DEFAULT_BLOCK)
    return _pick_block(int(block_q), seq_q), _pick_block(int(block_k), seq_k)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q=None, block_k=None, interpret=None):
    """Exact fused attention, Pallas fwd+bwd. q, k, v: [b, seq, heads, d].

    Default 512 blocks: measured on v5e (d=128, s=8k), 512-wide tiles run
    ~3x faster than 128 (the MXU is fed longer contractions and the VPU
    softmax amortizes); blocks are clamped to the sequence length for
    short inputs.  Passing None (the default) consults the autotuner
    (``MXNET_AUTOTUNE``) for this shape family's winner before falling
    back to 512; explicit block sizes are always respected.
    ``interpret`` None picks compiled-vs-interpreted from the device the
    operands live on (ops/interpret.py)."""
    import jax

    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    block_q, block_k = resolve_blocks(block_q, block_k, q.shape[1],
                                      k.shape[1], q.shape[-1], q.dtype,
                                      causal)
    interpret = interpret_for("flash_attention", (q, k, v), interpret)

    @jax.custom_vjp
    def run(q, k, v):
        o, _ = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              interpret)
        return o

    def fwd(q, k, v):
        o, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                                interpret)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        q, k, v, o, lse = res
        return _flash_backward(q, k, v, o, lse, g, causal, scale, block_q,
                               block_k, interpret)

    run.defvjp(fwd, bwd)
    return run(q, k, v)


# ---------------------------------------------------------------------------
# registry op — first user of the public mx.register_pallas_op mechanism
# ---------------------------------------------------------------------------


def _attrs_config(attrs, q, k):
    """(causal, scale, block_q, block_k) for the registered op.  Attrs
    without pinned block sizes resolve through the autotuner (falling
    back to the measured 512 default) — the fwd and bwd kernels see the
    same deterministic resolution for one (attrs, shapes) pair."""
    d = q.shape[-1]
    scale = attrs.get("scale")
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    causal = bool(attrs.get("causal", False))
    bq, bk = resolve_blocks(attrs.get("block_q"), attrs.get("block_k"),
                            q.shape[1], k.shape[1], d, q.dtype, causal)
    return causal, float(scale), bq, bk


def _fa_fn(attrs, query, key, value):
    causal, scale, bq, bk = _attrs_config(attrs, query, key)
    interpret = interpret_for("_contrib_FlashAttention", (query, key, value))

    def kernel(q, k, v):
        return _flash_forward(q, k, v, causal, scale, bq, bk, interpret)[0]

    return over_batch_shards(kernel)(query, key, value)


def _fa_fwd(attrs, query, key, value):
    causal, scale, bq, bk = _attrs_config(attrs, query, key)
    interpret = interpret_for("_contrib_FlashAttention", (query, key, value))

    def kernel(q, k, v):
        return _flash_forward(q, k, v, causal, scale, bq, bk, interpret)

    o, lse = over_batch_shards(kernel)(query, key, value)
    return o, (query, key, value, o, lse)


def _fa_bwd(attrs, res, ct):
    q, k, v, o, lse = res
    causal, scale, bq, bk = _attrs_config(attrs, q, k)
    interpret = interpret_for("_contrib_FlashAttention", (q, k, v))

    def kernel(q, k, v, o, lse, ct):
        return _flash_backward(q, k, v, o, lse, ct, causal, scale, bq, bk,
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
                # None = autotuner winner, else the measured 512 default
                "block_q": Param("int-or-none", None),
                "block_k": Param("int-or-none", None)},
        infer_shape=lambda attrs, s: (s, [s[0]], []),
        hint="flashattention")


_register()
