"""Paged-KV attention ops — the decode-step kernels behind
``mxnet_tpu.generation`` (continuous batching + paged KV-cache).

Two ops:

* ``_contrib_DenseAttention`` — plain dense softmax attention over
  ``[b, s, h, d]`` (the ``parallel.ring.local_attention`` oracle as a
  symbol op).  The generation prefill path uses it instead of the Pallas
  flash kernels because interpret-mode Pallas is orders of magnitude too
  slow on CPU, and prefill happens once per sequence; on TPU the flash
  kernels remain the training/high-MFU choice (models/transformer.py).

* ``_contrib_PagedAttention`` — one autoregressive decode step over a
  paged KV pool (the vLLM PagedAttention layout): each decode *lane*
  holds one live sequence whose K/V history lives in fixed-size pages of
  a shared pool, indirected through a per-lane page table.  The op
  WRITES the lane's new K/V at ``positions[lane]`` into the pool, then
  attends the lane's query against its own gathered history.  Because
  pools, page tables, and lane vectors are all fixed-shape, the whole
  decode step is ONE static XLA program per lane-count bucket — no
  per-sequence-length recompiles, which is the entire point
  (ISSUE 12 / Operator Fusion in XLA, arxiv 2301.13062).

Page 0 of the pool is reserved as a scratch page: inactive lanes carry
an all-zero page-table row and position 0, so their (masked-out) writes
land harmlessly in the scratch page and never corrupt a live sequence.
"""
from __future__ import annotations

import jax
import numpy as np

from .param import Param
from .registry import register

_NEG = -1e30


def _dense_infer(attrs, shapes):
    return shapes, [shapes[0]], []


@register("_contrib_DenseAttention",
          inputs=("query", "key", "value"),
          params={"causal": Param(bool, True),
                  "scale": Param("float-or-none", None)},
          infer_shape=_dense_infer, hint="denseattention")
def _dense_attention(opctx, attrs, query, key, value):
    from ..parallel.ring import local_attention

    scale = attrs.get("scale")
    return local_attention(query, key, value,
                           causal=bool(attrs.get("causal", True)),
                           scale=None if scale is None else float(scale))


def _paged_infer(attrs, shapes):
    q, k_new, v_new, k_pool, v_pool, page_table, positions = shapes
    if q is None or k_pool is None:
        return shapes, [None, None, None], []
    return shapes, [q, k_pool, v_pool], []


@register("_contrib_PagedAttentionWindow",
          inputs=("query", "key", "value", "k_pool", "v_pool",
                  "page_table", "positions"),
          params={"page_size": Param(int, required=True),
                  "scale": Param("float-or-none", None)},
          num_outputs=3, infer_shape=_paged_infer,
          no_grad_inputs=("page_table", "positions"),
          output_names=lambda attrs: ["out", "k_pool_out", "v_pool_out"],
          hint="pagedattentionwindow")
@jax.named_scope("paged_attention_window")
def _paged_attention_window(opctx, attrs, q, k_new, v_new, k_pool, v_pool,
                            page_table, positions):
    """``width`` KNOWN tokens per lane in ONE causal pass over paged KV.

    The sequential decode chain is only necessary when each token must
    be *discovered* from the previous logits.  When the whole window is
    known up front — a prefix-cache catch-up walking a prompt suffix, a
    re-admitted preemptee re-materializing its transcript — teacher
    forcing applies: write all ``width`` new K/V slots, gather each
    lane's history ONCE, and attend all ``width`` queries under a
    per-query causal mask.  Same numerics family as the chained
    construction at a fraction of the gathers (2 per layer instead of
    2 per layer per token) and with every projection batched over
    ``lanes * width`` rows instead of ``lanes``.

    Shapes (all static):
      q, k_new, v_new : (lanes * width, heads, head_dim)
      k_pool, v_pool  : (num_pages, page_size, heads, head_dim)
      page_table      : (lanes, max_pages)
      positions       : (lanes, width) absolute position per window slot
                        (pad slots point at the scratch page, as decode)
    Returns (att_out (lanes * width, heads, head_dim), k_pool_out,
    v_pool_out).
    """
    import jax.numpy as jnp

    ps = int(attrs["page_size"])
    lanes, width = positions.shape
    heads, hd = q.shape[-2], q.shape[-1]
    num_pages = k_pool.shape[0]
    max_pages = page_table.shape[1]
    scale = attrs.get("scale")
    scale = (1.0 / np.sqrt(hd)) if scale is None else float(scale)

    pt = page_table.astype(jnp.int32)
    pos = positions.astype(jnp.int32)  # (lanes, width)

    flat_k = k_pool.reshape(num_pages * ps, heads, hd)
    flat_v = v_pool.reshape(num_pages * ps, heads, hd)
    k_new = k_new.astype(flat_k.dtype)
    v_new = v_new.astype(flat_v.dtype)

    # -- gather ONCE: each lane's full history, in token order, from the
    # pool as it came; the window's own K/V goes into the gathered copy
    # at its positions.  The pool's update below is then a write of
    # ``lanes * width`` rows that nothing in this step reads back.
    ctx_idx = (pt[:, :, None] * ps
               + jnp.arange(ps, dtype=jnp.int32)[None, None, :])
    ctx_idx = ctx_idx.reshape(lanes, max_pages * ps)
    lane = jnp.arange(lanes, dtype=jnp.int32)[:, None]
    keys = flat_k[ctx_idx].at[lane, pos].set(   # (lanes, T, heads, hd)
        k_new.reshape(lanes, width, heads, hd))
    vals = flat_v[ctx_idx].at[lane, pos].set(
        v_new.reshape(lanes, width, heads, hd))

    # -- write: the whole window's K/V into each lane's slots ------------
    page_idx = jnp.take_along_axis(pt, pos // ps, axis=1)  # (lanes, width)
    slot = (page_idx * ps + pos % ps).reshape(-1)
    flat_k = flat_k.at[slot].set(k_new)
    flat_v = flat_v.at[slot].set(v_new)

    # -- causal masked attention, all width queries at once --------------
    qw = q.reshape(lanes, width, heads, hd)
    s = jnp.einsum("lwhd,lthd->lwht", qw, keys).astype(jnp.float32) * scale
    valid = (jnp.arange(max_pages * ps, dtype=jnp.int32)[None, None, :]
             <= pos[:, :, None])  # (lanes, width, T)
    s = jnp.where(valid[:, :, None, :], s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("lwht,lthd->lwhd", p, vals).astype(q.dtype)
    return (out.reshape(lanes * width, heads, hd),
            flat_k.reshape(num_pages, ps, heads, hd),
            flat_v.reshape(num_pages, ps, heads, hd))


@register("_contrib_PagedAttention",
          inputs=("query", "key", "value", "k_pool", "v_pool",
                  "page_table", "positions"),
          params={"page_size": Param(int, required=True),
                  "scale": Param("float-or-none", None)},
          num_outputs=3, infer_shape=_paged_infer,
          no_grad_inputs=("page_table", "positions"),
          output_names=lambda attrs: ["out", "k_pool_out", "v_pool_out"],
          hint="pagedattention")
@jax.named_scope("paged_attention")
def _paged_attention(opctx, attrs, q, k_new, v_new, k_pool, v_pool,
                     page_table, positions):
    """One decode step for ``lanes`` sequences at once.

    Shapes (all static):
      q, k_new, v_new : (lanes, heads, head_dim) — this step's projections
      k_pool, v_pool  : (num_pages, page_size, heads, head_dim)
      page_table      : (lanes, max_pages) pool-page ids per lane, in
                        sequence order (float carrier, cast to int32 —
                        Predictor feeds every input as its bind dtype)
      positions       : (lanes,) this token's absolute position per lane
    Returns (att_out, k_pool_out, v_pool_out).  The engine carries the
    pools through the step donated (``Executor.set_carried``), so the
    ``.at[slot].set`` below updates them in place; undonated it copies
    each pool once.
    """
    import jax.numpy as jnp

    ps = int(attrs["page_size"])
    lanes, heads, hd = q.shape
    num_pages = k_pool.shape[0]
    max_pages = page_table.shape[1]
    scale = attrs.get("scale")
    scale = (1.0 / np.sqrt(hd)) if scale is None else float(scale)

    pt = page_table.astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    flat_k = k_pool.reshape(num_pages * ps, heads, hd)
    flat_v = v_pool.reshape(num_pages * ps, heads, hd)
    k_new = k_new.astype(flat_k.dtype)
    v_new = v_new.astype(flat_v.dtype)

    # -- gather: each lane's full history, in token order ----------------
    # token t of a lane lives at page_table[lane, t // ps], offset t % ps,
    # so gathering the lane's pages in table order yields exactly tokens
    # 0..max_pages*ps-1 at their flattened indices.  The gather reads the
    # pool as it came and this step's own K/V goes into the gathered copy
    # at its position: the pool's update below is then a write of
    # ``lanes`` rows that nothing in this step reads back.
    ctx_idx = (pt[:, :, None] * ps
               + jnp.arange(ps, dtype=jnp.int32)[None, None, :])
    ctx_idx = ctx_idx.reshape(lanes, max_pages * ps)
    lane = jnp.arange(lanes, dtype=jnp.int32)
    keys = flat_k[ctx_idx].at[lane, pos].set(k_new)  # (lanes, T, heads, hd)
    vals = flat_v[ctx_idx].at[lane, pos].set(v_new)

    # -- write: this step's K/V into each lane's current slot ------------
    cur_page = jnp.take_along_axis(pt, (pos // ps)[:, None], axis=1)[:, 0]
    slot = cur_page * ps + pos % ps  # (lanes,) — inactive lanes hit page 0
    flat_k = flat_k.at[slot].set(k_new)
    flat_v = flat_v.at[slot].set(v_new)

    # -- masked softmax attention (local_attention numerics) -------------
    s = jnp.einsum("lhd,lthd->lht", q, keys).astype(jnp.float32) * scale
    valid = (jnp.arange(max_pages * ps, dtype=jnp.int32)[None, :]
             <= pos[:, None])  # causal: history up to and incl. this token
    s = jnp.where(valid[:, None, :], s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("lht,lthd->lhd", p, vals).astype(q.dtype)
    return (out,
            flat_k.reshape(num_pages, ps, heads, hd),
            flat_v.reshape(num_pages, ps, heads, hd))
