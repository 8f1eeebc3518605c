"""Paged-KV attention ops — the decode-step kernels behind
``mxnet_tpu.generation`` (continuous batching + paged KV-cache).

Three ops:

* ``_contrib_DenseAttention`` — plain dense softmax attention over
  ``[b, s, h, d]`` (the ``parallel.ring.local_attention`` oracle as a
  symbol op); grouped-query when ``key`` / ``value`` hold fewer heads than
  ``query`` (query head ``i`` reads K/V head ``i // (h / kv_heads)``), and
  then, over more than one block of queries, in one of two formulations
  (:func:`sequence_formulation` picks by where the operands live and what
  they are): on a TPU, for bfloat16 heads of whole lane tiles, the flash
  forward kernel of ``ops/attention.py`` (a K/V head read by its group of
  query heads through the index map, online softmax in VMEM: no score
  reaches the HBM); anywhere else in query blocks
  (:func:`blocked_attention`: a block's float32 scores against the keys up
  to its last row only, so ``heads x s x s`` scores never exist at once),
  which is also the kernel's oracle.  A sequence of one block, and heads
  with a K/V head each, keep the plain softmax: interpret-mode Pallas is
  orders of magnitude too slow on CPU, and the training graphs have
  ``_contrib_FlashAttention`` (models/transformer.py).

* ``_contrib_PagedAttention`` — one autoregressive decode step over a
  paged KV pool (the vLLM PagedAttention layout): each decode *lane*
  holds one live sequence whose K/V history lives in fixed-size pages of
  a shared pool, indirected through a per-lane page table.  The op
  attends the lane's query against its history as the pool holds it plus
  this step's own K/V (taken from the projections, never read back), and
  writes that K/V at ``positions[lane]`` into the pool: ``lanes`` rows.
  Two formulations, one op (:func:`decode_formulation` picks by where the
  operands live and what they are): on a TPU one Pallas kernel
  (``paged_decode``) walks each lane's live pages where they lie in the
  plane — ``positions[lane] // page_size`` page reads a lane, not the
  table's whole width — in either of two forms of plane: float32 tokens of
  ``(heads, head_dim)`` whole tiles, a K/V head a query head (the
  transformer family), and bfloat16 tokens held as ONE row of ``kv_heads *
  head_dim`` lanes, whose query heads may share a K/V head (grouped-query:
  a page is fetched once for its group; the hybrid family's attention
  layers).  Either way a page is one contiguous block of whole tiles, the
  planes go through the call in place, and the products' sums, the running
  maximum, sum and weighted values are float32.  Anywhere else (every CPU
  test, ``mx.cpu()`` serving, tokens that are no whole tiles) XLA gathers
  the whole table and masks, which is also the kernel's oracle
  (tests/test_paged_kernel.py).  Because
  pools, page tables, and lane vectors are all fixed-shape, the whole
  decode step is ONE static XLA program per lane-count bucket — no
  per-sequence-length recompiles, which is the entire point
  (ISSUE 12 / Operator Fusion in XLA, arxiv 2301.13062).

* ``_contrib_PagedAttentionWindow`` — ``width`` known tokens a lane in
  one causal pass (prefix catch-up, re-admission, speculative verify).
  It keeps the gather on every platform: ``width`` queries a lane want a
  kernel of their own, and no benchmark cell runs it to judge one.

Latent attention (one cached row a token and layer, ``[c | k_r]``: the
compressed K/V after its norm and the one rotated key all heads share) has
the same two places in a model, under two ops of its own:

* ``_contrib_LatentAttention`` — a whole sequence, *expanded*: every head's
  ``[k_n | v] = W_kvb c`` is made from the latent rows, the scores are ``q_n
  . k_n + q_r . k_r``, a block of heads at a time so that ``heads x L x L``
  scores never exist at once.

* ``_contrib_PagedLatentAttention`` — one decode step over ONE paged plane
  of latent rows, *absorbed*: ``W_kvb``'s key part goes into the query
  (``q_c = q_n W_k``, ``rank`` wide), every head reads the same rows
  (``score = q_c . c + q_r . k_r``), the weighted sum of latents leaves
  through ``W_kvb``'s value part.  The same mathematics as the expanded
  form (tests/test_latent_lm.py holds them together), at ``rank +
  rope`` values a token instead of ``heads x (nope + v)``.  Two
  formulations, one op (:func:`latent_formulation` picks, as
  :func:`decode_formulation` does): on a TPU, over bfloat16 rows, the
  kernel ``paged_latent_decode`` walks each lane's live pages where they
  lie in the plane (multi-query attention with one shared key row a token,
  whose values are the key's first ``rank`` columns: one plane, one fetch a
  page, products of whole MXU tiles); anywhere else XLA gathers the whole
  table, which is also the kernel's oracle (tests/test_paged_kernel.py).
  The plane holds a row in whole lane tiles (``HybridLM.latent_row``:
  openPangu's 512 + 64 values in 640 columns, zeros after them), because a
  chip lays a ``(pages, 16, 576)`` plane out with the PAGES on the lanes,
  where no page is one piece of memory.

Sliding-window attention (a layer whose token ``t`` attends to ``t - window
< u <= t``) caches a *ring* a lane and not pages: ``window`` rows of K and of
V, token ``t`` at ``t % window``, a slot plane of the pool like a recurrent
state (models/hybrid_lm.py, kind ``window``).  Two ops, both under the scope
``window_attention``:

* ``_contrib_WindowAttention`` — a whole sequence, banded, in the same two
  formulations (the kernel's grid walks only the blocks the band touches;
  :func:`blocked_attention` with the band: a block reads the keys from
  ``window - 1`` before its first row); beside the output it returns each
  prompt's rings ``(b, window, kv_heads * head_dim)`` as they stand after its
  LAST REAL token, at the ring's own indices (entry ``j`` the latest token
  ``t < length`` with ``t % window == j``; zeros where there is none).

* ``_contrib_WindowAttentionStep`` — one token a lane over its slot of the
  ring planes: this step's K/V go to ``positions % window`` and the query
  attends to the live entries (``j <= position``: after ``window`` tokens all
  of them; an entry that is not live reaches nothing, whatever it holds).
  XLA gathers the live lanes' slots (:data:`WINDOW_STEP`).

Page 0 of the pool is reserved as a scratch page: inactive lanes carry
an all-zero page-table row and position 0, so their (masked-out) writes
land harmlessly in the scratch page and never corrupt a live sequence.
"""
from __future__ import annotations

import functools

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
    import jax.numpy as jnp

    from ..parallel.ring import local_attention

    scale = attrs.get("scale")
    scale = None if scale is None else float(scale)
    causal = bool(attrs.get("causal", True))
    b, s, heads, hd = query.shape
    kv_heads = key.shape[2]
    if kv_heads == heads:
        return local_attention(query, key, value, causal=causal, scale=scale)
    # grouped-query: the group is an axis of the query, K/V are read as
    # they are (never repeated); local_attention's numerics
    group = _group(heads, kv_heads)
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    if causal and s > _QUERY_BLOCK:
        return _sequence_attention(opctx, query, key, value, scale=scale)
    q = query.reshape(b, s, kv_heads, group, hd)
    sc = jnp.einsum("bqkgd,btkd->bkgqt", q, key).astype(jnp.float32) * scale
    if causal:
        mask = jnp.arange(s)[:, None] >= jnp.arange(key.shape[1])[None, :]
        sc = jnp.where(mask, sc, _NEG)
    p = jnp.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p, value).astype(query.dtype)
    return out.reshape(b, s, heads, hd)


# queries whose scores exist at once in the sequence forms: at 64 heads x 512
# a block's float32 scores over a band of 1,023 keys are 134 MB, over 4,096
# keys 537 MB, where 64 x 4,096 x 4,096 would be 4.3 GB
_QUERY_BLOCK = 512


def blocked_attention(q, k, v, *, scale, window=0):
    """Causal grouped-query attention in query blocks: ``q`` (b, s, heads,
    d), ``k`` / ``v`` (b, s, kv_heads, d), query head ``i`` over K/V head ``i
    // group``.  A block of ``_QUERY_BLOCK`` queries against the keys only
    as far as the mask lets them matter: up to the block's last row, and
    with ``window`` (token ``t`` attends to ``t - window < u <= t``) from
    ``window - 1`` before its first.  Scores, softmax and the products' sums
    float32, the products' operands in the rows' dtype."""
    import jax.numpy as jnp

    b, s, heads, hd = q.shape
    kv_heads = k.shape[2]
    qg = q.reshape(b, s, kv_heads, _group(heads, kv_heads), hd)
    f32 = jnp.float32
    out = []
    for start in range(0, s, _QUERY_BLOCK):
        end = min(start + _QUERY_BLOCK, s)
        first = max(0, start - window + 1) if window else 0
        sc = jnp.einsum("bqkgd,btkd->bkgqt", qg[:, start:end],
                        k[:, first:end], preferred_element_type=f32) * scale
        ahead = (jnp.arange(start, end)[:, None]
                 - jnp.arange(first, end)[None, :])
        mask = ahead >= 0
        if window:
            mask = mask & (ahead < window)
        p = _softmax(jnp.where(mask, sc, _NEG)).astype(v.dtype)
        out.append(jnp.einsum("bkgqt,btkd->bqkgd", p, v[:, first:end],
                              preferred_element_type=f32))
    return jnp.concatenate(out, axis=1).astype(q.dtype).reshape(q.shape)


def sequence_formulation(platform, L, heads, kv_heads, head_dim, dtype,
                         is_train):
    """Which formulation a grouped-query sequence of more than one query
    block runs (``_contrib_DenseAttention``'s causal path, and
    ``_contrib_WindowAttention``): ``"pallas"`` -- the flash forward kernel
    of ``ops/attention.py`` (online softmax in VMEM: no score reaches the
    HBM), a K/V head read by its group through the index map and, under a
    window, only the blocks the band touches walked -- where the operands
    live on a TPU in bfloat16, a head is whole lane tiles, ``L`` whole tiles
    of the kernel and the op is not being differentiated (the call is the
    forward alone: a training graph keeps the XLA form and its gradient);
    ``"xla"`` -- :func:`blocked_attention`, also the kernel's oracle --
    anywhere else.  An observation of the operands, as
    :func:`decode_formulation` is."""
    from .attention import _FWD_TILE

    tiled = (np.dtype(dtype) == np.dtype("bfloat16") and head_dim % 128 == 0
             and heads % kv_heads == 0 and L > _QUERY_BLOCK
             and L % _FWD_TILE == 0)
    return ("pallas" if platform == "tpu" and tiled and not is_train
            else "xla")


def _kernel_sequence(q, k, v, *, scale, window=0, interpret=None):
    """:func:`blocked_attention`'s operands and result through the flash
    forward kernel: the band in blocks of one tile, a causal sequence in the
    forward's own default block.  The band's operands go in as they are, a
    token one row of its heads (on the v5e, in the sliding-window cell's
    4,096-token prefill: 125.9 ms where by heads reads 133.2: XLA's copy of
    64 heads' output into its consumer's layout and the transposing write of
    the rotation before it, 0.5 ms a layer, against 0.13 in the kernel's
    strided fetches; my chip runs, PR 52); a causal sequence's by heads,
    whose 2,048-row blocks by rows pass the kernel's fast memory (16.9 MB
    of 16)."""
    from . import attention
    from .interpret import interpret_for

    s, hd = q.shape[1], q.shape[3]
    blocks, _ = attention._resolve(None, None, s, s, hd, q.dtype, True,
                                   window)
    return attention._flash_forward(
        q, k, v, True, scale, *blocks,
        interpret_for("sequence_attention", (q, k, v), interpret),
        window=window, rows=bool(window))[0]


def _sequence_attention(opctx, q, k, v, *, scale, window=0):
    """A grouped-query causal (or banded) sequence of more than one query
    block, in the formulation its operands allow."""
    import jax.numpy as jnp

    from .interpret import platform_of

    attend = {"pallas": _kernel_sequence, "xla": blocked_attention}[
        sequence_formulation(platform_of(q, k, v), q.shape[1], q.shape[2],
                             k.shape[2], q.shape[3],
                             jnp.result_type(q.dtype, k.dtype, v.dtype),
                             getattr(opctx, "is_train", False))]
    return attend(q, k, v, scale=scale, window=window)


def _group(heads, kv_heads):
    """Query heads a K/V head: head ``i`` reads K/V head ``i // group``."""
    if heads % kv_heads:
        raise ValueError("%d query heads over %d K/V heads" % (heads,
                                                                kv_heads))
    return heads // kv_heads


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

    This op gathers the table's whole width on a TPU too, where the
    single-token op runs a kernel (:func:`decode_formulation`): a window
    kernel is the follow-up once a benchmark cell with prefix reuse or
    speculation exists to judge it.

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


def decode_formulation(platform, heads, head_dim, dtype, kv_heads=None,
                       rows=False, page_size=16):
    """Which formulation ``_contrib_PagedAttention`` runs: ``"pallas"`` —
    the kernel that reads the live pages where they lie — where the
    operands live on a TPU and a page is one contiguous block of whole tiles
    of the plane (the DMA's unit), in one of the two forms the kernel takes:

    * a token of ``(kv_heads, head_dim)`` float32 values that are whole
      tiles themselves, every query head with a K/V head of its own
      (``kv_heads`` None or ``heads``);
    * ``rows``: a token held as ONE row of ``kv_heads * head_dim`` bfloat16
      lanes (``kv_heads`` may divide ``heads``: the query heads of a group
      share the K/V head's pages), the row whole lane tiles and the page's
      ``page_size`` rows whole sublane tiles.

    ``"xla"`` — the gather over the whole table — anywhere else.  ``dtype``
    is what the products' operands are held in (the query's and the planes'
    common type).  An observation of the operands, as
    ``interpret.interpret_for`` is for the flash kernels: no attribute or
    environment variable chooses."""
    kv_heads = heads if kv_heads is None else kv_heads
    if rows:
        tiled = (np.dtype(dtype) == np.dtype("bfloat16")
                 and heads % kv_heads == 0 and heads % 8 == 0
                 and (kv_heads * head_dim) % 128 == 0 and page_size % 16 == 0)
    else:
        tiled = (np.dtype(dtype) == np.float32 and head_dim % 128 == 0
                 and heads % 8 == 0 and kv_heads == heads)
    return "pallas" if platform == "tpu" and tiled else "xla"


def _by_heads(plane, kv_heads):
    """A plane whose token is one row of ``kv_heads * head_dim`` values, by
    K/V heads; one that is by heads already, as it is."""
    if plane.ndim == 4:
        return plane
    return plane.reshape(plane.shape[:2] + (kv_heads, -1))


def _gather_decode(q, k_new, v_new, k_pool, v_pool, pt, pos, scale):
    """The XLA formulation, and the kernel's oracle: gather every lane's
    whole table (``max_pages * page_size`` slots), put this step's own K/V
    into the gathered copy at its position, masked softmax; the pool's
    update is a scatter of ``lanes`` rows.  Grouped-query where ``q`` holds
    more heads than the planes: the planes are by K/V heads (a token
    ``(kv_heads, head_dim)``, or one row of their product), and query head
    ``i`` reads K/V head ``i // group``."""
    import jax.numpy as jnp

    heads = k_new.shape[1]
    pools = k_pool.shape, v_pool.shape
    k_pool, v_pool = _by_heads(k_pool, heads), _by_heads(v_pool, heads)
    num_pages, ps, heads, hd = k_pool.shape
    lanes, max_pages = pt.shape
    group = _group(q.shape[1], heads)
    flat_k = k_pool.reshape(num_pages * ps, heads, hd)
    flat_v = v_pool.reshape(num_pages * ps, heads, hd)

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
    valid = (jnp.arange(max_pages * ps, dtype=jnp.int32)[None, :]
             <= pos[:, None])  # causal: history up to and incl. this token
    if group == 1:
        s = jnp.einsum("lhd,lthd->lht", q, keys).astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, :], s, _NEG)
    else:
        s = jnp.einsum("lhgd,lthd->lhgt", q.reshape(lanes, heads, group, hd),
                       keys).astype(jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    if group == 1:
        out = jnp.einsum("lht,lthd->lhd", p, vals)
    else:
        out = jnp.einsum("lhgt,lthd->lhgd", p, vals).reshape(q.shape)
    return (out.astype(q.dtype), flat_k.reshape(pools[0]),
            flat_v.reshape(pools[1]))


# Bytes of each plane in flight towards the ring of VMEM slots, and the
# tokens a slot holds at the least.  A slot is one fetch's worth of a lane's
# pages: one float32 page of the cgpt13b cell's (16 tokens of 16 x 128: 128
# KiB, 7 in flight cover the DMA's latency at the HBM's rate: PR 29), or as
# many bfloat16 pages of 16-32 KiB as hold 128 tokens (the scores' lane tile).
# The ring's depth follows from the slot's bytes.
_IN_FLIGHT_BYTES = 7 * (128 << 10)
_SLOT_TOKENS = 128


def _ring(page_shape, dtype, tokens=_SLOT_TOKENS):
    """(pages a slot, slots) of the kernel's ring for pages of this shape
    (``(page_size,) + token``) and dtype, a slot of ``tokens`` at the
    least."""
    if len(page_shape) == 2:  # a token is a row: pages are fetched together
        chunk = max(1, tokens // page_shape[0])
    else:
        chunk = 1
    slot = chunk * int(np.prod(page_shape)) * np.dtype(dtype).itemsize
    return chunk, 1 + max(1, -(-_IN_FLIGHT_BYTES // slot))


def _decode_kernel(pt_ref, pos_ref, q_ref, kn_ref, vn_ref, k_in, v_in,
                   o_ref, k_out, v_out, work, m_ref, l_ref, acc, kbuf, vbuf,
                   sems, row_sems, aside, *, scale, max_pages, chunk):
    """One call, all lanes.  Every lane's live pages, in lane then table
    order, stream from the planes where they lie through a ring of VMEM
    slots (``chunk`` pages of one lane a slot) while an online softmax folds
    each slot into its lane's running max, sum and weighted values, all
    float32.  The planes come in and go out as the same buffers (``k_out``
    aliases ``k_in``): the kernel's only write to them is this step's
    ``lanes`` rows.  Two forms of plane (:func:`decode_formulation`):

    * float32 tokens of ``(heads, head_dim)``: the products on the VPU
      (exact); a token is whole tiles, so this step's rows go to their slots
      as they are (``aside``: the query times the scale);
    * ``rows``, bfloat16 tokens of one row: a slot's scores are ONE product
      on the MXU of the lane's query heads, each laid out over its K/V
      head's lanes of the row (zeros elsewhere: ``q_ref``), with the slot's
      keys; the weighted values one product with the probabilities in two
      bfloat16 halves (float32 sums of exact products: nothing is rounded
      that the gather rounds not).  A row is no DMA's unit (two rows share a
      sublane): each lane's current page comes into ``aside``, takes the
      row and goes back whole."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows_form = len(k_in.shape) == 3
    qs = held = aside  # one scratch, by the planes' form (docstring)
    lanes = q_ref.shape[0]
    ring, ps = kbuf.shape[0], k_in.shape[1]
    chunks = -(-max_pages // chunk)  # slots a lane's whole table makes
    f32 = jnp.float32

    def live_pages(lane):
        return (pos_ref[lane] + ps - 1) // ps

    # this step's K/V rows into each lane's current slot (inactive lanes
    # hit the scratch page).  No page read below uses that slot — it holds
    # the token AT the lane's position, the reads stop before it — so the
    # rows fly while the pages stream and are waited for at the end.  (A
    # page that goes back whole is the page as it was but for that slot.)
    def rows(to_plane):
        def copies(lane):
            at = pos_ref[lane]
            page = pt_ref[lane * max_pages + at // ps]
            if rows_form:  # (in the plane, here): the page that holds it
                ends = [(k_out.at[page], held.at[0, lane]),
                        (v_out.at[page], held.at[1, lane])]
            else:          # the token's row itself
                ends = [(k_out.at[page, at % ps], kn_ref.at[lane]),
                        (v_out.at[page, at % ps], vn_ref.at[lane])]
            return [pltpu.make_async_copy(*(pair[::-1] if to_plane else pair),
                                          row_sems.at[i, lane])
                    for i, pair in enumerate(ends)]
        return copies

    def each(copies, method):
        def run(n, carry):
            for copy in copies(n):
                getattr(copy, method)()
            return carry
        return run

    lax.fori_loop(0, lanes, each(rows(not rows_form), "start"), None)

    # the work list: lane * chunks + the slot's number in the lane's table,
    # once per ``chunk`` pages that hold a token before the lane's position.
    # A lane at position 0 (an inactive lane among them) has none: it
    # attends its own token alone.
    def lane_slots(lane, n):
        def one(c, n):
            work[n] = lane * chunks + c
            return n + 1
        return lax.fori_loop(0, (live_pages(lane) + chunk - 1) // chunk, one,
                             n)

    total = lax.fori_loop(0, lanes, lane_slots, 0)

    def fetch(method):
        """Start, or wait for, the pages of work item ``n``: the lane's
        live ones among the slot's ``chunk``."""
        def run(n, carry):
            slot = n % ring
            lane, c = work[n] // chunks, work[n] % chunks

            def pages(j):
                page = pt_ref[lane * max_pages + c * chunk + j]
                dst = (slot, j) if rows_form else (slot,)
                return (pltpu.make_async_copy(k_in.at[page], kbuf.at[dst],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_in.at[page], vbuf.at[dst],
                                              sems.at[1, slot]))

            if chunk == 1:
                return each(pages, method)(0, carry)
            return lax.fori_loop(
                0, jnp.minimum(chunk, live_pages(lane) - c * chunk),
                each(pages, method), carry)
        return run

    if rows_form:
        # a slot's rows past the lane's live pages are an earlier fetch's,
        # or nobody's: masked below, but 0 x NaN is NaN in the values' product
        vbuf[...] = jnp.zeros_like(vbuf)
    lax.fori_loop(0, jnp.minimum(ring - 1, total), fetch("start"), None)

    # this step's own token opens every lane's softmax: its K/V come from
    # the projections, not from the pool
    if rows_form:
        m_ref[...] = jnp.sum(q_ref[...].astype(f32) * kn_ref[...], axis=-1,
                             keepdims=True) * scale
        acc[...] = jnp.broadcast_to(vn_ref[...], acc.shape)

        def put(lane, carry):
            each(rows(False), "wait")(lane, None)
            here = lax.broadcasted_iota(jnp.int32, held.shape[2:], 0) \
                == pos_ref[lane] % ps
            for i, new in enumerate((kn_ref, vn_ref)):
                held[i, lane] = jnp.where(here, new[lane],
                                          held[i, lane].astype(f32)
                                          ).astype(held.dtype)
            return each(rows(True), "start")(lane, carry)

        lax.fori_loop(0, lanes, put, None)
    else:
        qs[...] = q_ref[...] * scale
        m_ref[...] = jnp.sum(qs[...] * kn_ref[...], axis=-1, keepdims=True)
        acc[...] = vn_ref[...]
    l_ref[...] = jnp.ones_like(l_ref)

    def fold(n, carry):
        @pl.when(n + ring - 1 < total)
        def _():
            fetch("start")(n + ring - 1, None)

        lane, c = work[n] // chunks, work[n] % chunks
        slot = n % ring
        fetch("wait")(n, None)
        m_old = m_ref[lane]
        if rows_form:
            width = kbuf.shape[-1]
            keys = kbuf[slot].reshape(chunk * ps, width)
            s = lax.dot_general(q_ref[lane], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
            token = c * chunk * ps + lax.broadcasted_iota(jnp.int32, s.shape,
                                                          1)
            s = jnp.where(token < pos_ref[lane], s, _NEG)  # (heads, tokens)
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)  # a masked slot underflows to 0.0
            high = p.astype(keys.dtype)
            low = (p - high.astype(f32)).astype(keys.dtype)
            pv = jnp.dot(jnp.concatenate([high, low], axis=0),
                         vbuf[slot].reshape(chunk * ps, width),
                         preferred_element_type=f32)
            p_sum = jnp.sum(p, axis=1, keepdims=True)
            pv = pv[:p.shape[0]] + pv[p.shape[0]:]
        else:
            s = jnp.sum(kbuf[slot] * qs[lane][None], axis=-1,
                        keepdims=True)                    # (ps, heads, 1)
            token = c * ps + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(token < pos_ref[lane], s, _NEG)
            m_new = jnp.maximum(m_old, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])  # a masked slot underflows to 0.0
            p_sum, pv = jnp.sum(p, axis=0), jnp.sum(p * vbuf[slot], axis=0)
        alpha = jnp.exp(m_old - m_new)
        m_ref[lane] = m_new
        l_ref[lane] = alpha * l_ref[lane] + p_sum
        acc[lane] = alpha * acc[lane] + pv
        return carry

    lax.fori_loop(0, total, fold, None)
    o_ref[...] = acc[...] / l_ref[...]
    lax.fori_loop(0, lanes, each(rows(True), "wait"), None)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                              "in_place"))
def _kernel_decode(q, k_new, v_new, k_pool, v_pool, pt, pos, scale,
                   interpret=False, in_place=False):
    """The kernel's call: page table and positions as scalar-prefetch
    operands (a page id is a DMA's source index), the planes left where
    they lie, in the layout they have, and aliased to their outputs — a
    donated plane then goes through the call in place, uncopied and
    unstaged (an undonated one is copied once, as for the scatter) — and
    everything else whole in VMEM.  ``in_place`` says the planes are
    donated (``interpret.carried_in_place``): planes of row tokens are then
    held to the HBM (``pltpu.HBM``).  Left free, XLA stages a plane small
    enough for its fast memory there and back around the call, the whole
    plane each way (the 704-token cells' 11.5 MB planes); held, a plane that
    XLA must copy first (an undonated one) aborts the TPU compiler's
    memory-space assignment.  The float32 planes stay free as they were
    (PR 29): XLA leaves the transformer cell's 23 MB planes where they lie,
    and held, the chip's compiler refuses them ("Different aliasing
    shapes": my chip run, PR 44).  Jitted on its own so that a lane
    program's 24 call sites trace and lower the kernel once, not 24 times,
    at every start (the persistent cache's key needs the lowered
    program)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, hd = q.shape
    ps, token = k_pool.shape[1], k_pool.shape[2:]
    max_pages = pt.shape[1]
    f32 = jnp.float32
    chunk, ring = _ring(k_pool.shape[1:], k_pool.dtype)
    if len(token) == 2:
        width, slot = hd, (ps,) + token
        operands = (q, k_new, v_new)
        aside = pltpu.VMEM((lanes, heads, hd), f32)  # the scaled query
    else:
        # a token is one row of ``kv_heads`` heads: query head ``i`` lies
        # over the lanes of K/V head ``i // group`` and is zero over the
        # others', so ONE product of a lane's (heads, width) with a slot's
        # (tokens, width) is every head's scores, and the product of the
        # probabilities with the slot's values holds every head's weighted
        # values over its own lanes
        kv_heads, (width,) = k_new.shape[1], token
        slot = (chunk, ps, width)
        mine = (jnp.arange(heads)[:, None] // _group(heads, kv_heads)
                == jnp.arange(kv_heads)[None, :])[None, :, :, None]
        operands = (
            jnp.where(mine, q[:, :, None, :], 0).reshape(lanes, heads, width),
            k_new.astype(f32).reshape(lanes, 1, width),
            v_new.astype(f32).reshape(lanes, 1, width))
        # each lane's current page of K and of V
        aside = pltpu.VMEM((2, lanes, ps, width), k_pool.dtype)
    whole = [pl.BlockSpec(x.shape, lambda i, *_: (0, 0, 0)) for x in operands]
    plane = pltpu.HBM if in_place and len(token) == 1 else \
        jax.ShapeDtypeStruct
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, max_pages=max_pages,
                          chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=whole + [where_it_lies, where_it_lies],
            out_specs=[whole[0], where_it_lies, where_it_lies],
            scratch_shapes=[
                pltpu.SMEM((lanes * -(-max_pages // chunk),),
                           jnp.int32),                      # work list
                pltpu.VMEM((lanes, heads, 1), f32),         # running max
                pltpu.VMEM((lanes, heads, 1), f32),         # running sum
                pltpu.VMEM((lanes, heads, width), f32),     # weighted V
                pltpu.VMEM((ring,) + slot, k_pool.dtype),
                pltpu.VMEM((ring,) + slot, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, ring)),
                pltpu.SemaphoreType.DMA((2, lanes)), aside]),
        out_shape=[jax.ShapeDtypeStruct(operands[0].shape,
                                        q.dtype if len(token) == 2 else f32),
                   plane(k_pool.shape, k_pool.dtype),
                   plane(v_pool.shape, v_pool.dtype)],
        # operands count the two scalar-prefetch ones: the planes are 5, 6
        input_output_aliases={5: 1, 6: 2},
        name="paged_decode", interpret=interpret,
    )(pt.reshape(-1), pos, *operands, k_pool, v_pool)
    if len(token) == 1:
        out = jnp.where(mine, out.reshape(lanes, heads, kv_heads, hd),
                        0).sum(2).astype(q.dtype)
    return out, k_pool, v_pool


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
      q               : (lanes, heads, head_dim) — this step's projections
      k_new, v_new    : (lanes, kv_heads, head_dim); ``kv_heads`` divides
                        ``heads`` (grouped-query), or is ``heads``
      k_pool, v_pool  : (num_pages, page_size, kv_heads, head_dim), or with
                        a token held as one row (num_pages, page_size,
                        kv_heads * head_dim)
      page_table      : (lanes, max_pages) pool-page ids per lane, in
                        sequence order (float carrier, cast to int32 —
                        Predictor feeds every input as its bind dtype)
      positions       : (lanes,) this token's absolute position per lane
    Returns (att_out, k_pool_out, v_pool_out).  The attention reads the
    pool as it came and takes this step's own K/V from the projections; on
    a TPU through the kernel where it takes the planes' form (float32
    tokens of whole tiles a head, or bfloat16 tokens of one row, grouped or
    not), elsewhere through the gather (:func:`decode_formulation`).  The
    engine carries the pools through the step donated
    (``Executor.set_carried``), so the write of ``lanes`` rows updates them
    in place; undonated it copies each pool once.
    """
    import jax.numpy as jnp

    from .interpret import carried_in_place, platform_of

    heads, hd = q.shape[-2:]
    kv_heads = k_new.shape[-2]
    rows = k_pool.ndim == 3
    if k_pool.shape[2:] != ((kv_heads * hd,) if rows else (kv_heads, hd)):
        raise ValueError("this step's K holds %d heads of %d, the pool's "
                         "planes a token of %s"
                         % (kv_heads, hd, k_pool.shape[2:]))
    if int(attrs["page_size"]) != k_pool.shape[1]:
        raise ValueError("page_size %s, but the pool's pages hold %d slots"
                         % (attrs["page_size"], k_pool.shape[1]))
    scale = attrs.get("scale")
    scale = (1.0 / np.sqrt(hd)) if scale is None else float(scale)
    decode = {"pallas": functools.partial(_kernel_decode,
                                          in_place=carried_in_place()),
              "xla": _gather_decode}[
        decode_formulation(platform_of(q, k_pool, v_pool), heads, hd,
                           jnp.result_type(q.dtype, k_pool.dtype)
                           if rows else k_pool.dtype,
                           kv_heads=kv_heads, rows=rows,
                           page_size=k_pool.shape[1])]
    return decode(q, k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype),
                  k_pool, v_pool, page_table.astype(jnp.int32),
                  positions.astype(jnp.int32), scale)


# ---------------------------------------------------------------------------
# sliding-window attention over a ring a lane
# ---------------------------------------------------------------------------

# what ``_contrib_WindowAttentionStep`` runs wherever the operands live: XLA
# gathers the live lanes' ring slots, puts this step's row into the copy and
# scatters it to the plane.  (A kernel that reads each live lane's ring once
# where it lies is PERF.md section 7's; ``window_attn_roofline_pct_laguna``
# records where this form stands.)
WINDOW_STEP = "xla"


def window_rings(k, v, length, window):
    """Each prompt's rings after its last real token: ``k`` / ``v`` (b, s,
    kv_heads, d), ``length`` (b,) int32 -> two (b, window, kv_heads * d):
    entry ``j`` the latest token ``t < length`` with ``t % window == j``,
    zeros where no token has landed."""
    import jax.numpy as jnp

    b, s = k.shape[:2]
    j = jnp.arange(window, dtype=jnp.int32)[None, :]
    last = length.astype(jnp.int32)[:, None] - 1
    t = (last - j) // window * window + j     # (b, window); < 0: none yet
    live = (t >= 0)[..., None]
    t = jnp.clip(t, 0, s - 1)[..., None]

    def ring(x):
        rows = x.reshape(b, s, -1)
        return jnp.where(live, jnp.take_along_axis(rows, t, axis=1), 0)

    return ring(k), ring(v)


@register("_contrib_WindowAttention",
          inputs=lambda attrs: ["query", "key", "value"] + (
              ["length"] if attrs.get("use_length") else []),
          params={"window": Param(int, required=True),
                  "scale": Param("float-or-none", None),
                  "use_length": Param(bool, False)},
          num_outputs=3, no_grad_inputs=("length",),
          output_names=lambda attrs: ["out", "k_ring", "v_ring"],
          hint="windowattention")
@jax.named_scope("window_attention")
def _window_attention(opctx, attrs, q, k, v, length=None):
    """A whole sequence under the band ``t - window < u <= t``
    (:func:`blocked_attention`), and the rings it leaves
    (:func:`window_rings`; ``length`` (b,) the prompts' true lengths, the
    whole sequence without it).

    Shapes: q (b, s, heads, d); k, v (b, s, kv_heads, d); returns (out as q,
    k_ring, v_ring (b, window, kv_heads * d))."""
    import jax.numpy as jnp

    window = int(attrs["window"])
    scale = attrs.get("scale")
    scale = (1.0 / np.sqrt(q.shape[-1])) if scale is None else float(scale)
    if length is None:
        length = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    return (_sequence_attention(opctx, q, k, v, scale=scale, window=window),
            *window_rings(k, v, length, window))


def window_step(q, k_new, v_new, k_ring, v_ring, slot, pos, scale):
    """The lane form: ``q`` (lanes, heads, d), ``k_new`` / ``v_new`` (lanes,
    kv_heads, d) in the rings' dtype, ``k_ring`` / ``v_ring`` (num_slots,
    window, kv_heads * d), ``slot`` / ``pos`` (lanes,) int32.  Gathers the
    lanes' slots, puts this step's rows into the copy at ``pos % window``,
    attends to the live entries (``j <= pos``), and writes ``lanes`` rows to
    the planes.  Returns (lanes, heads, d) and the planes."""
    import jax.numpy as jnp

    lanes, heads, hd = q.shape
    kv_heads = k_new.shape[1]
    window = k_ring.shape[1]
    group = _group(heads, kv_heads)
    f32 = jnp.float32
    at = pos % window
    lane = jnp.arange(lanes, dtype=jnp.int32)
    k_row, v_row = k_new.reshape(lanes, -1), v_new.reshape(lanes, -1)
    live = jnp.arange(window, dtype=jnp.int32)[None, :] <= pos[:, None]
    keys = k_ring[slot].at[lane, at].set(k_row)      # (lanes, window, row)
    # a dead entry may hold anything: zero times it must still be zero
    vals = jnp.where(live[..., None],
                     v_ring[slot].at[lane, at].set(v_row), 0)
    s = jnp.einsum("lkgd,ltkd->lkgt", q.reshape(lanes, kv_heads, group, hd),
                   keys.reshape(lanes, window, kv_heads, hd),
                   preferred_element_type=f32) * scale
    p = _softmax(jnp.where(live[:, None, None, :], s, _NEG)).astype(
        vals.dtype)
    out = jnp.einsum("lkgt,ltkd->lkgd", p,
                     vals.reshape(lanes, window, kv_heads, hd),
                     preferred_element_type=f32)
    # padded lanes all land on the scratch slot 0
    return (out.reshape(q.shape).astype(q.dtype),
            k_ring.at[slot, at].set(k_row), v_ring.at[slot, at].set(v_row))


@register("_contrib_WindowAttentionStep",
          inputs=("query", "key", "value", "k_ring", "v_ring", "state_slot",
                  "positions"),
          params={"scale": Param("float-or-none", None)},
          num_outputs=3, no_grad_inputs=("state_slot", "positions"),
          output_names=lambda attrs: ["out", "k_ring_out", "v_ring_out"],
          hint="windowattentionstep")
@jax.named_scope("window_attention")
def _window_attention_step(opctx, attrs, q, k_new, v_new, k_ring, v_ring,
                           state_slot, positions):
    """One decode step of a sliding-window layer for ``lanes`` sequences
    (:func:`window_step`): q (lanes, heads, d), k_new / v_new (lanes,
    kv_heads, d), the ring planes (num_slots, window, kv_heads * d), a lane's
    slot and its token's absolute position (float carriers, cast to int32).
    The engine carries the planes through the step donated, so the write of
    ``lanes`` rows updates them in place."""
    import jax.numpy as jnp

    hd = q.shape[-1]
    if k_ring.shape[2] != k_new.shape[-2] * hd or k_ring.shape != v_ring.shape:
        raise ValueError("this step's K holds %d heads of %d, the rings rows "
                         "of %s and %s" % (k_new.shape[-2], hd,
                                           k_ring.shape[2:], v_ring.shape[2:]))
    scale = attrs.get("scale")
    scale = (1.0 / np.sqrt(hd)) if scale is None else float(scale)
    return window_step(q, k_new.astype(k_ring.dtype),
                       v_new.astype(v_ring.dtype), k_ring, v_ring,
                       state_slot.astype(jnp.int32),
                       positions.astype(jnp.int32), scale)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

# heads, and queries, whose scores exist at once in the expanded form: at 8 x
# 512 a block's float32 scores over 2,048 keys are 33 MB, which XLA works
# through in one pass; 8 x 2,048 x 2,048 (134 MB) took sixteen times as long
# for four times the work (PERF.md section 6, PR 41)
_LATENT_HEAD_BLOCK, _LATENT_QUERY_BLOCK = 8, 512


# what ``_contrib_LatentAttention`` runs wherever the operands live: the
# expanded form over blocks of heads and queries.  (What the decode step's op
# runs is an observation of its operands: :func:`latent_formulation`.)
LATENT_PREFILL = "xla-expanded-head-blocks"


def _softmax(s):
    import jax.numpy as jnp

    p = jnp.exp(s - s.max(-1, keepdims=True))
    return p / p.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("scale",))
def latent_attention(q_n, q_r, latent, weight, *, scale):
    """The expanded form: ``q_n`` (b, s, heads, nope), ``q_r`` (b, s, heads,
    rope) rotated, ``latent`` (b, s, rank + rope) ``[c | k_r]``, ``weight``
    ``W_kvb`` (heads * (nope + v), rank).  Causal; returns (b, s, heads, v).
    Scores and softmax float32, the products' operands in the rows' dtype."""
    import jax.numpy as jnp
    from jax import lax

    b, s, heads, nope = q_n.shape
    rank = weight.shape[-1]
    c, k_r = latent[..., :rank], latent[..., rank:]
    block = max(d for d in range(1, _LATENT_HEAD_BLOCK + 1) if heads % d == 0)
    f32 = jnp.float32

    def blocks(x):  # (b, s, heads, d) -> (heads / block, b, s, block, d)
        return jnp.moveaxis(
            x.reshape(b, s, heads // block, block, x.shape[-1]), 2, 0)

    def attend(operands):
        qn, qr, w = operands  # w (block, nope + v, rank)
        kv = jnp.einsum("btr,hmr->bthm", c, w,
                        preferred_element_type=f32).astype(latent.dtype)
        k_n, val = kv[..., :nope], kv[..., nope:]
        out = []
        # a block of queries against the keys up to its last row: nothing
        # above the diagonal block is computed, and a block's scores
        # (heads x queries x keys, float32) stay small enough to be one pass
        for start in range(0, s, _LATENT_QUERY_BLOCK):
            end = min(start + _LATENT_QUERY_BLOCK, s)
            sc = (jnp.einsum("bqhd,bthd->bhqt", qn[:, start:end],
                             k_n[:, :end], preferred_element_type=f32)
                  + jnp.einsum("bqhr,btr->bhqt", qr[:, start:end],
                               k_r[:, :end], preferred_element_type=f32)
                  ) * scale
            causal = (jnp.arange(start, end)[:, None]
                      >= jnp.arange(end)[None, :])
            p = _softmax(jnp.where(causal, sc, _NEG)).astype(val.dtype)
            out.append(jnp.einsum("bhqt,bthd->bqhd", p, val[:, :end],
                                  preferred_element_type=f32))
        return jnp.concatenate(out, axis=1).astype(q_n.dtype)

    out = lax.map(attend, (blocks(q_n), blocks(q_r),
                           weight.reshape(heads // block, block, -1, rank)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, heads, -1)


def _latent_infer(attrs, shapes):
    q_n, weight = shapes[0], shapes[3]
    if q_n is None or weight is None:
        return shapes, [None], []
    return shapes, [q_n[:-1] + (weight[0] // q_n[-2] - q_n[-1],)], []


@register("_contrib_LatentAttention",
          inputs=("q_nope", "q_rope", "latent", "kv_b_weight"),
          params={"scale": Param(float, required=True)},
          infer_shape=_latent_infer, hint="latentattention")
@jax.named_scope("latent_attention")
def _latent_attention(opctx, attrs, q_n, q_r, latent, weight):
    """:func:`latent_attention` as an op (a whole sequence, causal,
    expanded)."""
    return latent_attention(q_n, q_r, latent, weight,
                            scale=float(attrs["scale"]))


def _absorbed(q_n, q_r, new, weight, pool):
    """The absorbed form's operands.  ``W_kvb``'s key part goes into the
    query: ``[q_c | q_r]`` (lanes, heads, width), the cached row's own
    layout, and this step's row ``new`` (lanes, width), both in the pool's
    dtype and as wide as its rows (zeros past ``rank + rope`` where the
    plane holds whole lane tiles: ``HybridLM.latent_row``); the value part
    ``w_v`` (heads, v, rank) is for the way out.  A head's rows of ``W_kvb``
    are ``[k_n | v]``."""
    import jax.numpy as jnp

    heads, nope = q_n.shape[1:]
    w = weight.reshape(heads, -1, weight.shape[-1])
    # (head-major operands: the host's XLA has no bfloat16 product with the
    # batch axis in the middle)
    q_c = jnp.einsum("hln,hnr->hlr", q_n.swapaxes(0, 1), w[:, :nope],
                     preferred_element_type=jnp.float32).swapaxes(0, 1)
    q = jnp.concatenate([q_c.astype(pool.dtype), q_r.astype(pool.dtype)],
                        axis=-1)
    new = new.astype(pool.dtype)
    zeros = pool.shape[-1] - new.shape[-1]
    if zeros:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, zeros)))
        new = jnp.pad(new, ((0, 0), (0, zeros)))
    return q, new, w[:, nope:]


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_latent_attention(q_n, q_r, new, weight, pool, pt, pos, *, scale):
    """The absorbed form, one token a lane: ``q_n`` (lanes, heads, nope),
    ``q_r`` (lanes, heads, rope) rotated, ``new`` (lanes, rank + rope) this
    step's own ``[c | k_r]``, ``weight`` ``W_kvb``, ``pool`` (num_pages,
    page_size, row) whose rows hold those values and zeros after them, ``pt``
    (lanes, max_pages) and ``pos`` (lanes,) int32.  The XLA formulation, and the kernel's oracle: gathers every
    lane's table (as :func:`_gather_decode`), puts this step's row into the
    copy at its position and writes it to the pool: ``lanes`` rows.  Returns
    (lanes, heads, v) and the pool."""
    import jax.numpy as jnp

    lanes = q_n.shape[0]
    num_pages, ps, width = pool.shape
    rank = weight.shape[-1]
    f32 = jnp.float32
    q, new, w_v = _absorbed(q_n, q_r, new, weight, pool)
    flat = pool.reshape(num_pages * ps, width)
    idx = (pt[:, :, None] * ps
           + jnp.arange(ps, dtype=jnp.int32)[None, None, :]).reshape(lanes, -1)
    lane = jnp.arange(lanes, dtype=jnp.int32)
    rows = flat[idx].at[lane, pos].set(new)          # (lanes, T, width)
    cur = jnp.take_along_axis(pt, (pos // ps)[:, None], axis=1)[:, 0]
    flat = flat.at[cur * ps + pos % ps].set(new)     # inactive: page 0

    s = jnp.einsum("lhw,ltw->lht", q, rows,
                   preferred_element_type=f32) * scale
    valid = jnp.arange(idx.shape[1], dtype=jnp.int32)[None, :] <= pos[:, None]
    p = _softmax(jnp.where(valid[:, None, :], s, _NEG)).astype(pool.dtype)
    o_c = jnp.einsum("lht,ltr->lhr", p, rows[..., :rank],
                     preferred_element_type=f32).astype(pool.dtype)
    out = jnp.einsum("lhr,hvr->lhv", o_c, w_v, preferred_element_type=f32)
    return out.astype(q_n.dtype), flat.reshape(pool.shape)


def latent_formulation(platform, heads, rank, row, dtype, page_size=16):
    """Which formulation ``_contrib_PagedLatentAttention`` runs:
    ``"pallas-absorbed-live-pages"`` (the kernel ``paged_latent_decode``,
    which reads each lane's live pages where they lie in the plane) where
    the operands live on a TPU, query and plane are bfloat16 (``dtype``
    their common type) and a page is one block of whole tiles: its
    ``page_size`` rows whole sublane tiles, the ``row`` the plane holds a
    token in whole lane tiles, and so its first ``rank`` columns, the values
    (the rotated key after them may be any width: 64 at openPangu's, in a
    row of 640); ``"xla-absorbed-gather"`` over the whole table anywhere
    else.  An observation of the operands, as :func:`decode_formulation`
    is."""
    tiled = (np.dtype(dtype) == np.dtype("bfloat16") and heads % 8 == 0
             and rank % 128 == 0 and row % 128 == 0 and page_size % 16 == 0)
    return "pallas-absorbed-live-pages" if platform == "tpu" and tiled \
        else "xla-absorbed-gather"


# Tokens a slot of the latent kernel's ring holds at the least: 16 pages of
# 20 KiB.  A slot costs a product, a softmax and a product that wait on each
# other whatever it holds: at 128 tokens a call took 237 us, at 256 it takes
# 138, at 512 no less (PERF.md section 6, PR 49).
_LATENT_SLOT_TOKENS = 256


def _latent_decode_kernel(pt_ref, pos_ref, q_ref, new_ref, plane_in, o_ref,
                          plane_out, work, m_ref, l_ref, acc, buf, sems,
                          row_sems, aside, *, scale, max_pages, chunk):
    """One call, all lanes, ONE plane.  As :func:`_decode_kernel`'s ``rows``
    form walks them, every lane's live pages stream in lane then table order
    through a ring of VMEM slots (``chunk`` pages of one lane a slot) and an
    online softmax folds each slot into the lane's running max, sum and
    weighted rows, all float32.  What differs: every head scores the SAME
    row (``[q_c | q_r] . [c | k_r]``: one product of the lane's (heads,
    width) query with the slot's (tokens, width) rows, no head owns lanes of
    the row), the values are that row's first ``rank`` columns (the buffer
    that held the keys, no second plane), and the state is ONE lane's: a
    lane's slots are folded one after another, so its output leaves when
    its last slot has been folded.  Scores, state and the products' sums are
    float32, the probabilities go into their product in two bfloat16 halves
    and the weighted rows leave in float32: the kernel rounds nothing (one
    that rounded the probabilities once, as the gather does, read the
    benchmark's gap to its reference higher on eight seeds of eight:
    PERF.md section 6, PR 49).  This step's own row opens each lane's
    softmax from the projections; its page comes into ``aside``, takes the
    row and goes back whole (a bfloat16 row is no DMA's unit)."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads, width = q_ref.shape
    ring, ps = buf.shape[0], plane_in.shape[1]
    rank = acc.shape[-1]
    chunks = -(-max_pages // chunk)  # slots a lane's whole table makes
    f32 = jnp.float32

    def live_pages(lane):
        return (pos_ref[lane] + ps - 1) // ps

    def slots(lane):
        return (live_pages(lane) + chunk - 1) // chunk

    def own_page(lane, to_plane):
        """The copy of the page that holds the lane's position (an inactive
        lane's: the scratch page) into ``aside``, or back."""
        page = pt_ref[lane * max_pages + pos_ref[lane] // ps]
        ends = (plane_out.at[page], aside.at[lane])
        return pltpu.make_async_copy(*(ends[::-1] if to_plane else ends),
                                     row_sems.at[lane])

    def each_lane(fn):
        lax.fori_loop(0, lanes, lambda lane, carry: fn(lane), None)

    each_lane(lambda lane: own_page(lane, False).start())

    # the work list: lane * chunks + the slot's number in the lane's table,
    # once per ``chunk`` pages that hold a token before the lane's position
    # (none for a lane at position 0: it attends its own token alone)
    def lane_slots(lane, n):
        def one(c, n):
            work[n] = lane * chunks + c
            return n + 1
        return lax.fori_loop(0, slots(lane), one, n)

    total = lax.fori_loop(0, lanes, lane_slots, 0)

    def fetch(method):
        """Start, or wait for, the pages of work item ``n``: the lane's
        live ones among the slot's ``chunk`` (all of them, but in a lane's
        last slot: those without a loop)."""
        def run(n):
            lane, c = work[n] // chunks, work[n] % chunks
            slot, first = n % ring, lane * max_pages + c * chunk
            live = jnp.minimum(chunk, live_pages(lane) - c * chunk)

            def page(j, carry=None):
                getattr(pltpu.make_async_copy(
                    plane_in.at[pt_ref[first + j]], buf.at[slot, j],
                    sems.at[slot]), method)()
                return carry

            @pl.when(live == chunk)
            def _():
                for j in range(chunk):
                    page(j)

            @pl.when(live < chunk)
            def _():
                lax.fori_loop(0, live, page, None)
        return run

    # a slot's rows past the lane's live pages are an earlier fetch's, or
    # nobody's: masked below, but 0 x NaN is NaN in the weighted rows
    buf[...] = jnp.zeros_like(buf)
    lax.fori_loop(0, jnp.minimum(ring - 1, total),
                  lambda n, carry: fetch("start")(n), None)

    def put(lane):
        own_page(lane, False).wait()
        here = lax.broadcasted_iota(jnp.int32, aside.shape[1:], 0) \
            == pos_ref[lane] % ps
        aside[lane] = jnp.where(here, new_ref[lane],
                                aside[lane].astype(f32)).astype(aside.dtype)
        own_page(lane, True).start()

    each_lane(put)

    def attend(lane, n):
        q = q_ref[lane]
        # this step's own token opens the lane's softmax: its row comes
        # from the projections, not from the pool
        m_ref[...] = jnp.sum(q.astype(f32) * new_ref[lane], axis=-1,
                             keepdims=True) * scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc[...] = jnp.broadcast_to(new_ref[lane][:, :rank], acc.shape)

        def fold(c, n):
            @pl.when(n + ring - 1 < total)
            def _():
                fetch("start")(n + ring - 1)

            fetch("wait")(n)
            rows = buf[n % ring].reshape(chunk * ps, width)
            s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
            token = c * chunk * ps + lax.broadcasted_iota(jnp.int32, s.shape,
                                                          1)
            s = jnp.where(token < pos_ref[lane], s, _NEG)  # (heads, tokens)
            m_old = m_ref[...]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)  # a masked slot underflows to 0.0
            alpha = jnp.exp(m_old - m_new)
            m_ref[...] = m_new
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            # the probabilities in two bfloat16 halves, as _decode_kernel's
            high = p.astype(rows.dtype)
            low = (p - high.astype(f32)).astype(rows.dtype)
            pv = jnp.dot(jnp.concatenate([high, low], axis=0),
                         rows[:, :rank], preferred_element_type=f32)
            acc[...] = alpha * acc[...] + pv[:heads] + pv[heads:]
            return n + 1

        n = lax.fori_loop(0, slots(lane), fold, n)
        o_ref[lane] = acc[...] / l_ref[...]
        return n

    lax.fori_loop(0, lanes, attend, 0)
    each_lane(lambda lane: own_page(lane, True).wait())


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                              "in_place"))
def _kernel_latent_decode(q_n, q_r, new, weight, pool, pt, pos, *, scale,
                          interpret=False, in_place=False):
    """:func:`paged_latent_attention` with the kernel ``paged_latent_decode``
    in the gather's place: ``q_c = q_n W_k`` before the call and ``o_c W_v``
    after it stay XLA's, the plane goes through the call where it lies and
    aliased to its output, held to the HBM where the program donates it
    (``in_place``: :func:`_kernel_decode` has the rule and its reasons), the
    page table and positions are scalar-prefetch operands.  Jitted on its
    own so that a lane program's latent layers trace and lower it once."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, heads = q_n.shape[:2]
    ps, width = pool.shape[1:]
    rank, max_pages = weight.shape[-1], pt.shape[1]
    f32 = jnp.float32
    chunk, ring = _ring(pool.shape[1:], pool.dtype, _LATENT_SLOT_TOKENS)
    q, new, w_v = _absorbed(q_n, q_r, new, weight, pool)
    operands = (q, new.astype(f32).reshape(lanes, 1, width))
    whole = [pl.BlockSpec(x.shape, lambda i, *_: (0, 0, 0)) for x in operands]
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    o_c, pool = pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale,
                          max_pages=max_pages, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=whole + [where_it_lies],
            out_specs=[pl.BlockSpec((lanes, heads, rank),
                                    lambda i, *_: (0, 0, 0)), where_it_lies],
            scratch_shapes=[
                pltpu.SMEM((lanes * -(-max_pages // chunk),),
                           jnp.int32),                      # work list
                pltpu.VMEM((heads, 1), f32),                # running max
                pltpu.VMEM((heads, 1), f32),                # running sum
                pltpu.VMEM((heads, rank), f32),             # weighted rows
                pltpu.VMEM((ring, chunk, ps, width), pool.dtype),
                pltpu.SemaphoreType.DMA((ring,)),
                pltpu.SemaphoreType.DMA((lanes,)),
                # each lane's current page
                pltpu.VMEM((lanes, ps, width), pool.dtype)]),
        out_shape=[jax.ShapeDtypeStruct((lanes, heads, rank), f32),
                   (pltpu.HBM if in_place else jax.ShapeDtypeStruct)(
                       pool.shape, pool.dtype)],
        # operands count the two scalar-prefetch ones: the plane is 4
        input_output_aliases={4: 1},
        name="paged_latent_decode", interpret=interpret,
    )(pt.reshape(-1), pos, *operands, pool)
    out = jnp.einsum("lhr,hvr->lhv", o_c.astype(pool.dtype), w_v,
                     preferred_element_type=f32)
    return out.astype(q_n.dtype), pool


def _paged_latent_infer(attrs, shapes):
    q_n, weight, pool = shapes[0], shapes[3], shapes[4]
    if q_n is None or weight is None or pool is None:
        return shapes, [None, None], []
    return shapes, [q_n[:-1] + (weight[0] // q_n[-2] - q_n[-1],), pool], []


@register("_contrib_PagedLatentAttention",
          inputs=("q_nope", "q_rope", "latent", "kv_b_weight", "latent_pool",
                  "page_table", "positions"),
          params={"page_size": Param(int, required=True),
                  "scale": Param(float, required=True)},
          num_outputs=2, infer_shape=_paged_latent_infer,
          no_grad_inputs=("page_table", "positions"),
          output_names=lambda attrs: ["out", "latent_pool_out"],
          hint="pagedlatentattention")
@jax.named_scope("paged_attention")
@jax.named_scope("paged_attention_latent")
def _paged_latent_attention(opctx, attrs, q_n, q_r, latent, weight, pool,
                            page_table, positions):
    """One decode step of latent attention, absorbed, as an op: through the
    kernel where the operands live on a TPU and are what it takes, elsewhere
    through the gather (:func:`latent_formulation`).  Two scopes, one inside
    the other: ``paged_attention`` for whoever reads the decode step's
    attention whatever its kind, ``paged_attention_latent`` for this one."""
    import jax.numpy as jnp

    from .interpret import carried_in_place, platform_of

    if int(attrs["page_size"]) != pool.shape[1]:
        raise ValueError("page_size %s, but the pool's pages hold %d slots"
                         % (attrs["page_size"], pool.shape[1]))
    if latent.shape[-1] > pool.shape[2]:
        raise ValueError("this step's latent row is %d wide, the pool's %d"
                         % (latent.shape[-1], pool.shape[2]))
    decode = {"pallas-absorbed-live-pages": functools.partial(
                  _kernel_latent_decode, in_place=carried_in_place()),
              "xla-absorbed-gather": paged_latent_attention}[
        latent_formulation(platform_of(q_n, pool), q_n.shape[1],
                           weight.shape[-1], pool.shape[2],
                           jnp.result_type(q_n.dtype, pool.dtype),
                           page_size=pool.shape[1])]
    return decode(q_n, q_r, latent, weight, pool,
                  page_table.astype(jnp.int32), positions.astype(jnp.int32),
                  scale=float(attrs["scale"]))
