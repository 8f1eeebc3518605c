"""Sequence-parallel attention over the device mesh — the long-context
engine (new capability vs the reference, which only had bucketing for long
sequences; SURVEY.md §5.7).

Three schemes, all exact (not approximations of softmax attention):

* ``ring_attention`` — K/V blocks rotate around the mesh ring with
  ``lax.ppermute`` while each device's Q block accumulates the softmax
  online (the numerically-stable m/l running max/denominator recurrence).
  Communication overlaps compute; memory per device is O(seq/n).
* ``ring_flash_attention`` — same ring, but the per-block compute is the
  Pallas flash kernel (ops/attention.py) forward AND backward, with a
  custom ring-level vjp (dk/dv ride the ring with their blocks). The
  end-to-end long-context training path: VMEM-streamed blocks locally,
  O(seq/n) HBM per device globally.
* ``ulysses_attention`` — ``lax.all_to_all`` reshards from sequence-sharded
  to head-sharded, runs dense local attention, then reshards back. Cheaper
  at moderate sequence lengths when heads >= mesh axis size.

Tensor convention: [batch, seq, heads, head_dim], sequence sharded on
``axis`` (default 'seq').
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ..ops.interpret import interpret_for

_NEG = -1e30


def local_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Dense single-device softmax attention — the oracle and the inner
    kernel for ulysses. [b, s, h, d] in/out."""
    import jax.numpy as jnp

    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def _ring_inner(q, k, v, *, axis, vary_axes, n_shards, causal, scale):
    import jax.numpy as jnp
    from jax import lax

    idx = lax.axis_index(axis)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q_pos = idx * sq + jnp.arange(sq)
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    # initial accumulators must carry the same varying-axis type as the
    # loop outputs (shard_map VMA typing)
    def _vary(x):
        return lax.pcast(x, vary_axes, to="varying")

    o0 = _vary(jnp.zeros((b, sq, h, d), jnp.float32))
    m0 = _vary(jnp.full((b, h, sq), _NEG, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, sq), jnp.float32))

    def step(carry, t):
        o, m, l, k_blk, v_blk = carry
        # after t right-rotations this device holds block (idx - t) mod n
        k_idx = jnp.mod(idx - t, n_shards)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32)
        s = s * scale
        if causal:
            k_pos = k_idx * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)  # [b, h, q]
        l = l * corr + p.sum(-1)
        o = (o * corr.transpose(0, 2, 1)[..., None] +
             jnp.einsum("bhqk,bkhd->bqhd", p,
                        v_blk.astype(jnp.float32)))
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (o, m_new, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(n_shards))
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis: str = "seq",
                   batch_axis: Optional[str] = None, causal: bool = False,
                   scale: Optional[float] = None):
    """Exact attention with the sequence dimension sharded over ``axis`` of
    ``mesh``; K/V ride the ring via ppermute (ICI neighbours on TPU).

    q, k, v: [batch, seq, heads, head_dim] global arrays (sequence may be
    sharded on ``axis``; batch optionally on ``batch_axis``)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    n_shards = mesh.shape[axis]
    spec = P(batch_axis, axis, None, None)
    vary_axes = (axis,) + ((batch_axis,) if batch_axis else ())
    inner = functools.partial(_ring_inner, axis=axis, vary_axes=vary_axes,
                              n_shards=n_shards, causal=causal, scale=scale)
    fn = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)


def _merge_blocks(o_a, lse_a, o_b, lse_b):
    """Numerically-stable merge of two flash partial results.
    o: [b, sq, h, d] f32 (normalized), lse: [b*h, sq] f32."""
    import jax.numpy as jnp

    lse_new = jnp.logaddexp(lse_a, lse_b)
    b, sq, h, d = o_a.shape

    def w(lse):
        return jnp.exp(lse - lse_new).reshape(b, h, sq) \
            .transpose(0, 2, 1)[..., None]

    return o_a * w(lse_a) + o_b * w(lse_b), lse_new


def _ring_flash_fwd(q, k, v, *, axis, vary_axes, n_shards, causal, scale,
                    block_q, block_k, interpret):
    import jax.numpy as jnp
    from jax import lax

    from ..ops.attention import _flash_forward

    idx = lax.axis_index(axis)
    b, sq, h, d = q.shape
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def _vary(x):
        return lax.pcast(x, vary_axes, to="varying")

    o0 = _vary(jnp.zeros((b, sq, h, d), jnp.float32))
    lse0 = _vary(jnp.full((b * h, sq), _NEG, jnp.float32))

    def step(carry, t):
        o, lse, k_blk, v_blk = carry
        k_idx = jnp.mod(idx - t, n_shards)

        def blk_diag(_):
            return _flash_forward(q, k_blk, v_blk, True, scale, block_q,
                                  block_k, interpret)

        def blk_full(_):
            return _flash_forward(q, k_blk, v_blk, False, scale, block_q,
                                  block_k, interpret)

        def blk_skip(_):
            # constants must carry the same varying-axis type as the other
            # switch branches (check_vma on TPU rejects a mismatch)
            return (_vary(jnp.zeros((b, sq, h, d), q.dtype)),
                    _vary(jnp.full((b * h, sq), _NEG, jnp.float32)))

        if causal:
            branch = jnp.where(k_idx == idx, 0,
                               jnp.where(k_idx < idx, 1, 2))
            o_b, lse_b = lax.switch(branch, [blk_diag, blk_full, blk_skip],
                                    None)
        else:
            o_b, lse_b = blk_full(None)
        o, lse = _merge_blocks(o, lse, o_b.astype(jnp.float32), lse_b)
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (o, lse, k_blk, v_blk), None

    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v),
                                 jnp.arange(n_shards))
    return o.astype(q.dtype), lse


def _ring_flash_bwd(q, k, v, o, lse, do, *, axis, vary_axes, n_shards,
                    causal, scale, block_q, block_k, interpret):
    import jax.numpy as jnp
    from jax import lax

    from ..ops.attention import _flash_backward, _flash_bwd_precompute

    idx = lax.axis_index(axis)
    b, sq, h, d = q.shape
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def _vary(x):
        return lax.pcast(x, vary_axes, to="varying")

    dq0 = _vary(jnp.zeros((b, sq, h, d), jnp.float32))
    dkv0 = _vary(jnp.zeros((b, sq, h, d), jnp.float32))
    # q/dO layouts, lse and delta do not change across ring steps —
    # compute once, not per rotated block
    pre = _flash_bwd_precompute(q, o, lse, do)

    def step(carry, t):
        dq, k_blk, v_blk, dk_blk, dv_blk = carry
        k_idx = jnp.mod(idx - t, n_shards)

        def go_diag(_):
            return _flash_backward(q, k_blk, v_blk, o, lse, do, True,
                                   scale, block_q, block_k, interpret,
                                   pre=pre)

        def go_full(_):
            return _flash_backward(q, k_blk, v_blk, o, lse, do, False,
                                   scale, block_q, block_k, interpret,
                                   pre=pre)

        def go_skip(_):
            # zeros_like tracks the compute branches' shape AND dtype
            # (dq/dk/dv come back in q/k/v dtype; lax.switch requires
            # identical branch signatures for mixed-precision q vs k/v).
            # No _vary: zeros_like inherits the operand's varying type,
            # and pcast varying->varying is rejected.
            return (jnp.zeros_like(q), jnp.zeros_like(k),
                    jnp.zeros_like(v))

        if causal:
            branch = jnp.where(k_idx == idx, 0,
                               jnp.where(k_idx < idx, 1, 2))
            dq_c, dk_c, dv_c = lax.switch(
                branch, [go_diag, go_full, go_skip], None)
        else:
            dq_c, dk_c, dv_c = go_full(None)
        dq = dq + dq_c.astype(jnp.float32)
        dk_blk = dk_blk + dk_c.astype(jnp.float32)
        dv_blk = dv_blk + dv_c.astype(jnp.float32)
        # dk/dv travel WITH their k/v block: after the full cycle each
        # block's gradient is home with every device's contribution
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        dk_blk = lax.ppermute(dk_blk, axis, perm)
        dv_blk = lax.ppermute(dv_blk, axis, perm)
        return (dq, k_blk, v_blk, dk_blk, dv_blk), None

    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dkv0, dkv0), jnp.arange(n_shards))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def ring_flash_attention(q, k, v, mesh, axis: str = "seq",
                         batch_axis: Optional[str] = None,
                         causal: bool = False, scale: Optional[float] = None,
                         block_q: int = 512, block_k: int = 512):
    """Ring attention whose per-block compute is the Pallas flash kernel
    (fwd AND bwd): sequence sharded over ``axis``, K/V (and their
    gradients, on the backward ring) rotating via ppermute, per-block
    partials merged by logsumexp. Exact; O(seq/n) memory per device with
    VMEM-streamed blocks — the long-context training path end to end."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    n_shards = mesh.shape[axis]
    # the mesh names the devices the kernels run on
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    interpret = interpret_for("ring_flash_attention", interpret=not on_tpu)
    spec = P(batch_axis, axis, None, None)
    vary_axes = (axis,) + ((batch_axis,) if batch_axis else ())
    kw = dict(axis=axis, vary_axes=vary_axes, n_shards=n_shards,
              causal=causal, scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)

    @jax.custom_vjp
    def rf(q, k, v):
        o, _ = _ring_flash_fwd(q, k, v, **kw)
        return o

    def fwd(q, k, v):
        o, lse = _ring_flash_fwd(q, k, v, **kw)
        return o, (q, k, v, o, lse)

    def bwd(res, g):
        return _ring_flash_bwd(*res, g, **kw)

    rf.defvjp(fwd, bwd)
    # interpret-mode Pallas trips the varying-axis checker (see
    # ulysses_attention); compiled kernels keep it on
    fn = shard_map(rf, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=on_tpu)
    return fn(q, k, v)


def _ulysses_inner(q, k, v, *, axis, n_shards, causal, scale, attn_fn):
    from jax import lax

    # [b, s/n, h, d] -> [b, s, h/n, d]: gather sequence, scatter heads
    def fwd(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def bwd(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    out = attn_fn(fwd(q), fwd(k), fwd(v), causal=causal, scale=scale)
    return bwd(out)


def ulysses_attention(q, k, v, mesh, axis: str = "seq",
                      batch_axis: Optional[str] = None, causal: bool = False,
                      scale: Optional[float] = None, attn_fn=None):
    """All-to-all sequence parallelism: heads are sharded during attention,
    sequence is sharded elsewhere. Requires heads % mesh.shape[axis] == 0."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n_shards = mesh.shape[axis]
    if q.shape[2] % n_shards:
        raise ValueError(
            "ulysses needs heads (%d) divisible by mesh axis %r size %d"
            % (q.shape[2], axis, n_shards))
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if attn_fn is None:
        attn_fn = local_attention
    spec = P(batch_axis, axis, None, None)
    inner = functools.partial(_ulysses_inner, axis=axis, n_shards=n_shards,
                              causal=causal, scale=scale, attn_fn=attn_fn)
    # pallas interpret-mode (non-TPU) dynamic_slice inside shard_map trips
    # the varying-axis checker (jax 0.9); keep the checker on for TPU
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    fn = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=on_tpu)
    return fn(q, k, v)
