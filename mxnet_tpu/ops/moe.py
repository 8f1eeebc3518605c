"""Routed expert layer (mixture of experts) and rotary positions: what the
block of ``models/hybrid_lm.py`` needs for a sparse feed-forward and for
rotated queries and keys.

The expert layer, for rows ``g`` (tokens or lanes) of width ``H``, ``E``
experts of inner width ``F``, ``k`` picks a row::

    s   = sigmoid(W_r g)                      float32, (rows, E)
    I   = top_k(s + bias)                     the selection alone sees bias
                                              (a router may have none)
    w_i = scale * s_i / (sum_{j in I} s_j + 1e-6)   (``normalize``), i in I
    y   = sum_{i in I} w_i * W2_i (silu(W1_i g) * W3_i g)

A second scoring rule (``score="softmax"``; IBM Granite 4.0-H's routed
layers): the selection is over the float32 LOGITS and the weights are the
softmax over the picked ones, which sum to one by construction (no bias, no
eps; equal to the softmax over all ``E`` renormalised over the picks)::

    z   = W_r g                               float32, (rows, E)
    I   = top_k(z)
    w_i = scale * exp(z_i - max_I z) / sum_{j in I} exp(z_j - max_I z)

Two ops, so that a chip that holds a share of the experts routes over all of
them and computes its own part (``first_expert`` and the held count, read
from the stacked weights' leading axis): the parts of every share add up to
the whole layer (tests/test_moe_ops.py).  On one chip there is no exchange,
and nothing stands in for absent chips.

``_contrib_RoutedExperts`` computes every (row, pick) pair once and no
other: the pairs are sorted by expert, each held expert's group goes through
its two products and the gate between them (no capacity, no dropped token,
no padding to a capacity), the results go back to their rows weighted and
summed.  A pick outside the held range adds nothing, and so does a row the
router was told is not live (a padded lane of a bucket, a position past a
prompt's length): it picks expert ``E``, which no share holds.  The grouped
products have two formulations, one op (:func:`experts_formulation` picks by
where the operands live and what they are, as ``ops/paged.py`` and
``ops/ssm.py`` do): on a TPU, for bfloat16 leaves of tiled widths, one
Pallas kernel a layer (``moe_grouped``: it streams each expert with a group
once a row tile of the sorted pairs, where it lies in the stacked leaves, the
next tile in flight while this one multiplies; an expert no row picked costs
no fetch); for other operands ``jax.lax.ragged_dot`` twice, which on a TPU is
XLA's own grouped kernel and anywhere else XLA's dense expansion, the oracle
of both (tests/test_moe_kernel.py).

What is moved around the products, and for which pairs, is a second
observation (:func:`experts_path`, from the formulation, the held count
against the router's width, and the pair count).  The index work -- the
sort key, the stable sort, the groups' sizes: ``n x k`` int32 values -- is
over all pairs on either path; the rows (``H`` features each) are not:

``"held"``  where the kernel runs, the chip holds a SHARE of the experts and
    the pairs fill more than one row tile (a prefill's thousands): the held
    pairs are the sorted order's prefix, and only the row tiles they fill
    are gathered from the layer's rows, once, in the layout the kernel reads
    (a loop whose trip count is the live tiles, into a buffer nobody
    filled); the kernel's float32 output is neither sliced nor gathered: the
    kernel ``moe_combine`` streams its live tiles, where they lie, and adds
    each held pair's row, weighted, into its row's float32 sum, all the
    layer's sums resident in VMEM a block of columns at a time.  A pair of
    another share costs an int32 in the sort and nothing else.  Nothing is
    bounded: with every pick held every tile is live, and the answer is the
    same, only slower.
``"all"``   a lane step's one row tile, a layer that holds every expert and
    the XLA formulations: every pair's row is gathered into sorted order,
    the products' output is gathered back to (row, pick) order and summed
    over the picks.  Also what a gradient goes through, on either path
    (over ``ragged_dot``: the kernels are forward passes, and a loop with a
    dynamic trip count has no reverse mode).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .param import Param
from .registry import register

_F32 = jnp.float32
ROUTER_EPS = 1e-6
SCORES = ("sigmoid", "softmax")


@functools.partial(jax.jit, static_argnames=("top_k", "normalize", "scale",
                                             "score"))
def route(rows, weight, bias=None, live=None, *, top_k, normalize=True,
          scale=1.0, score="sigmoid"):
    """``rows`` (n, H), ``weight`` (E, H), ``bias`` (E,) or None (the top k
    of the scores alone), ``live`` (n,) or None.  Returns ids (n, k) int32,
    weights (n, k) float32 and the load (E,) int32: the live rows' picks by
    expert.  Scores are float32
    whatever the rows' dtype (a near-tie must not be a tie of rounded
    scores); a row that is not live picks expert ``E`` with weight 0.
    ``score`` is the rule (module docstring): ``"sigmoid"``, or
    ``"softmax"`` -- the top k of the logits, weighted by the softmax over
    the picked ones: it takes no ``bias``, and its weights sum to one
    whatever ``normalize`` says.
    Jitted on its own, as :func:`routed_experts` is: a program's 14 expert
    layers then trace and lower the layer once, not 14 times, at every
    start (a third of a second a program on the chip's host)."""
    if score not in SCORES:
        raise ValueError("route: score is one of %s; got %r"
                         % (SCORES, score))
    if score == "softmax" and bias is not None:
        raise ValueError("route: the softmax rule takes no selection bias")
    experts = weight.shape[0]
    logits = lax.dot_general(rows, weight, (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32)
    if score == "softmax":
        # top_k returns the picked logits largest first: [:, :1] is max_I z
        picked, ids = lax.top_k(logits, int(top_k))
        picked = jnp.exp(picked - picked[:, :1])
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(logits)
        _, ids = lax.top_k(
            scores if bias is None else scores + bias.astype(_F32),
            int(top_k))
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        if normalize:
            picked = picked / (jnp.sum(picked, -1, keepdims=True)
                               + ROUTER_EPS)
    picked = picked * float(scale)
    ids = ids.astype(jnp.int32)
    if live is not None:
        on = live.astype(bool)[:, None]
        ids = jnp.where(on, ids, experts)
        picked = jnp.where(on, picked, 0.0)
    load = jnp.sum(ids.reshape(-1)[:, None] == jnp.arange(experts)[None, :],
                   axis=0, dtype=jnp.int32)
    return ids, picked, load


def _ragged_grouped(x, sizes, w13, w2):
    """The XLA formulation, and the kernel's oracle: ``jax.lax.ragged_dot``
    twice over the sorted rows ``x`` (pairs, H), the gate between them.
    On a TPU XLA's own grouped kernel (an expert's weights are fetched once
    for each row tile its group touches), anywhere else its dense expansion
    (every expert over every pair).  Returns (pairs, H) float32; what it
    leaves in a row of no group is not to be looked at."""
    h = lax.ragged_dot(x, w13, sizes, preferred_element_type=_F32)
    g, u = jnp.split(h, 2, axis=-1)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return lax.ragged_dot(a, w2, sizes, preferred_element_type=_F32)


# bytes of one weight tile in VMEM: a contiguous piece of a leaf (whole rows
# of ``w13[e]`` or ``w2[e]``), long enough a DMA to run at the HBM's rate and
# short enough that the first one of a call, which hides behind nothing, is
# a small part of an expert's 22 MB; the pipeline holds two of each leaf
_TILE_BYTES = 4 << 20
# sorted pairs a row tile (what stays in VMEM while the experts of its
# groups stream by) and a chunk (what one product multiplies: a group's rows
# are kept by a mask, so a chunk is the waste a group of a few rows pays)
_ROW_TILE, _CHUNK = 512, 64


def _depth_tile(depth, width, itemsize, budget=_TILE_BYTES):
    """Rows of a weight tile ``(rows, width)``: the most that divide
    ``depth``, are whole lane tiles (they are the last axis of the rows'
    block) and fit ``budget``; ``depth`` where none does."""
    fits = [t for t in range(128, depth + 1, 128)
            if depth % t == 0 and t * width * itemsize <= budget]
    return max(fits, default=depth)


def _row_tiles(pairs):
    """(row tile, chunk) for that many sorted pairs: one tile and one chunk
    up to ``_CHUNK`` pairs (a lane step's), else chunks of ``_CHUNK`` in
    tiles of at most ``_ROW_TILE`` (a prefill's)."""
    if pairs <= _CHUNK:
        tile = -(-pairs // 16) * 16
        return tile, tile
    return min(_ROW_TILE, -(-pairs // _CHUNK) * _CHUNK), _CHUNK


def _visits(sizes, tiles, row_tile):
    """The kernel's work list, as ``jax.experimental.pallas.ops.tpu.
    megablox`` makes its group metadata: one visit for every (held expert
    with a group, row tile that group touches), in the sorted rows' order,
    so both the expert and the tile only ever step forward.  Returns the
    groups' offsets (held + 1,), each visit's expert and row tile (visits,)
    and the count of real visits (1,), all int32; ``visits = tiles + held -
    1`` is the most there can be, and the ones past the count repeat the
    last real one (no block index changes: nothing is fetched)."""
    held = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offs[:-1] // row_tile
    count = jnp.where(sizes > 0, (ends - 1) // row_tile - first + 1, 0)
    done = jnp.cumsum(count)  # visits up to and with expert e
    v = jnp.minimum(jnp.arange(tiles + held - 1, dtype=jnp.int32),
                    jnp.maximum(done[-1] - 1, 0))
    ve = jnp.minimum(jnp.sum(v[:, None] >= done[None, :], axis=1,
                             dtype=jnp.int32), held - 1)
    vt = jnp.take(first, ve) + v - (jnp.take(done, ve) - jnp.take(count, ve))
    return offs, ve, vt, done[-1:]


def _grouped_kernel(offs, ve, vt, nv, x_ref, w13_ref, w2_ref, o_ref, h_ref,
                    a_ref, *, chunk):
    """One visit (an expert with a group in this row tile), one weight
    tile: steps ``0 .. k13-1`` add ``x[:, tile] W13[tile]`` into ``h_ref``,
    the last of them gates it into ``a_ref`` (the rows of other groups
    zeroed by selection: an inf or NaN in a row that is not this expert's
    stays out), steps ``k13 ..`` add ``a[:, tile] W2[tile]`` into the
    output's block, which stays in VMEM while the visits of its row tile
    pass and holds the sum over their experts (each row has one).  Only
    the chunks of the tile that the group touches multiply (a loop, not
    unrolled: a program's start traces and lowers this body once a
    shape)."""
    from jax.experimental import pallas as pl

    v, t = pl.program_id(0), pl.program_id(1)
    k13, tile = x_ref.shape[0], x_ref.shape[1]
    k2, d2 = a_ref.shape[0], a_ref.shape[2]
    width = k2 * d2
    e, base = ve[v], vt[v] * tile
    # the group's rows in the tile's own numbering; none in a visit past
    # the count
    lo = offs[e] - base
    hi = jnp.where(v < nv[0], offs[e + 1], offs[e]) - base

    @pl.when((t == 0) & ((v == 0) | (vt[v] != vt[jnp.maximum(v - 1, 0)])))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def multiply(c, carry):
        start = pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(start, chunk)

        @pl.when(t < k13)
        def _():
            part = jnp.dot(x_ref[t, rows, :], w13_ref[...],
                           preferred_element_type=_F32)
            h_ref[rows, :] = jnp.where(t == 0, part, h_ref[rows, :] + part)

        @pl.when(t == k13 - 1)
        def _():
            at = start + lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            mine = (at >= lo) & (at < hi)
            for j in range(k2):
                g = h_ref[rows, j * d2:(j + 1) * d2]
                u = h_ref[rows, width + j * d2:width + (j + 1) * d2]
                a = (jax.nn.silu(g) * u).astype(a_ref.dtype)
                a_ref[j, rows, :] = jnp.where(mine, a, jnp.zeros_like(a))

        @pl.when(t >= k13)
        def _():
            o_ref[rows, :] += jnp.dot(a_ref[t - k13, rows, :], w2_ref[...],
                                      preferred_element_type=_F32)

        return carry

    # the chunks the group's rows lie in: none where it has none
    chunks = tile // chunk
    first = jnp.clip(lo // chunk, 0, chunks)
    last = jnp.where(hi > lo, jnp.clip((hi + chunk - 1) // chunk, 0, chunks),
                     first)
    lax.fori_loop(first, last, multiply, None)


def _grouped_call(pairs, held, hidden, width, dtype, rows, depths, interpret):
    """The ``pallas_call`` of ``moe_grouped`` for that many sorted pairs and
    what it was cut to: ``(call, row tile, row tiles, k13, d13)``.  ``call``
    takes :func:`_visits`' four lists, the pairs' rows as ``(k13, tiles x row
    tile, d13)`` (weight tile, row, feature of the tile: a step indexes the
    leading axis) and both leaves, and returns ``(tiles x row tile, hidden)``
    float32 of which only the row tiles a visit names are written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row_tile, chunk = rows or _row_tiles(pairs)
    item = jnp.dtype(dtype).itemsize
    d13, d2 = depths or (_depth_tile(hidden, 2 * width, item),
                         _depth_tile(width, hidden, item))
    k13, k2 = hidden // d13, width // d2
    tiles = -(-pairs // row_tile)

    def x_map(v, t, offs, ve, vt, nv):
        return 0, vt[v], 0

    def w13_map(v, t, offs, ve, vt, nv):
        return ve[v], jnp.where(v < nv[0], jnp.minimum(t, k13 - 1),
                                k13 - 1), 0

    def w2_map(v, t, offs, ve, vt, nv):
        now = (v < nv[0]) & (t >= k13)
        return (jnp.where(now, ve[v], ve[jnp.maximum(v - 1, 0)]),
                jnp.where(now, t - k13, jnp.where(v == 0, 0, k2 - 1)), 0)

    def o_map(v, t, offs, ve, vt, nv):
        return vt[v], 0

    vmem = (2 * (d13 * 2 * width + d2 * hidden + k13 * row_tile * d13) * item
            + row_tile * (2 * hidden * 4 + 2 * width * 4 + width * item))
    call = pl.pallas_call(
        functools.partial(_grouped_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(tiles + held - 1, k13 + k2),
            in_specs=[pl.BlockSpec((k13, row_tile, d13), x_map),
                      pl.BlockSpec((None, d13, 2 * width), w13_map),
                      pl.BlockSpec((None, d2, hidden), w2_map)],
            out_specs=pl.BlockSpec((row_tile, hidden), o_map),
            scratch_shapes=[pltpu.VMEM((row_tile, 2 * width), _F32),
                            pltpu.VMEM((k2, row_tile, d2), dtype)]),
        out_shape=jax.ShapeDtypeStruct((tiles * row_tile, hidden), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the blocks above and as much again for the products' values
            vmem_limit_bytes=min(2 * vmem + (8 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=6 * pairs * hidden * width,
            transcendentals=pairs * width,
            bytes_accessed=(min(held, pairs) * 3 * hidden * width * item
                            + pairs * hidden * (item + 4))),
        name="moe_grouped", interpret=interpret)
    return call, row_tile, tiles, k13, d13


@functools.partial(jax.jit, static_argnames=("rows", "depths", "interpret"))
def _kernel_grouped(x, sizes, w13, w2, rows=None, depths=None,
                    interpret=False):
    """The Pallas formulation: one call a layer, both products and the gate
    (``h`` never leaves VMEM, and the second leaf's first tile is in flight
    while the first leaf's last one multiplies: two calls would drain and
    fill the pipeline between them, and write and read ``a``).  The grid is
    (visit, weight tile); :func:`_visits`' lists are the scalar-prefetch
    operands and every index map reads them, so the call's own pipeline
    fetches the next tile of this expert, or the first tile of the NEXT
    expert with a group, while this one multiplies, and an expert without a
    group is never named: no byte of it moves.  The leaves are read where
    they lie: a tile is ``depths[i]`` whole rows of ``w13[e]`` / ``w2[e]``,
    one contiguous piece.  ``w2``'s map stays on the previous visit's last
    tile until this visit's second product starts, so that every step
    starts exactly one tile's fetch.  Jitted on its own so that a program's
    14 call sites trace and lower it once (as ``ops/paged.py``
    ``_kernel_decode``); ``rows`` (row tile, chunk) and ``depths`` (of a
    tile of each leaf) are for tests (:func:`_row_tiles`,
    :func:`_depth_tile`)."""
    pairs, hidden = x.shape
    call, row_tile, tiles, k13, d13 = _grouped_call(
        pairs, w2.shape[0], hidden, w2.shape[1], x.dtype, rows, depths,
        interpret)

    @jax.custom_vjp
    def grouped(x, sizes, w13, w2):
        # rows past the pairs belong to no group
        x = jnp.pad(x, ((0, tiles * row_tile - pairs), (0, 0)))
        x = x.reshape(tiles * row_tile, k13, d13).swapaxes(0, 1)
        return call(*_visits(sizes, tiles, row_tile), x, w13, w2)[:pairs]

    # the kernel is the forward pass alone: a gradient goes through the XLA
    # formulation of the same products (training does not stop working where
    # the kernel engages; a backward kernel is ROADMAP B5's)
    def backward(operands, dy):
        x, sizes, w13, w2 = operands
        dx, dw13, dw2 = jax.vjp(
            lambda x, w13, w2: _ragged_grouped(x, sizes, w13, w2),
            x, w13, w2)[1](dy)
        return dx, None, dw13, dw2

    grouped.defvjp(lambda *operands: (grouped(*operands), operands),
                   backward)
    return grouped(x, sizes, w13, w2)


# bytes of the combine's float32 sums in VMEM (all rows of the layer, one
# block of columns)
_SUMS_BYTES = 16 << 20


def _combine_kernel(total, token, weight, y_ref, o_ref, acc):
    """One row tile of the grouped kernel's output (the sorted pairs'
    results, where they lie), one block of columns: each held pair's row,
    times its weight, is added to its token's row of ``acc``, the float32
    sums of ALL the layer's rows for this block of columns, which stay in
    VMEM while the live tiles stream by (a row of the sums is reached by a
    dynamic sublane offset; a DMA could not fetch one row of a tiled
    plane).  A pair past the held ones is not looked at, a tile past the
    live ones is never fetched (the index map stays on the last live one).
    The last tile's step casts the sums into the output's block."""
    from jax.experimental import pallas as pl

    t, tiles = pl.program_id(1), pl.num_programs(1)
    tile = y_ref.shape[0]

    @pl.when(t == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    def add(r, carry):
        at = t * tile + r
        row = pl.ds(token[at], 1)
        acc[row, :] += y_ref[pl.ds(r, 1), :] * weight[at]
        return carry

    lax.fori_loop(0, jnp.clip(total[0] - t * tile, 0, tile), add, None)

    @pl.when(t == tiles - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _combine(y, token, weight, total, n, dtype, row_tile, cols, interpret):
    """``y`` (tiles x row tile, H) float32 the grouped kernel's output,
    ``token`` / ``weight`` (tiles x row tile,) the row and the float32
    weight of each sorted pair, ``total`` (1,) how many of them are held
    (the first ones): the ``n`` rows' weighted sums (n, H) in ``dtype``,
    made from the live tiles of ``y`` alone (kernel ``moe_combine``: each
    read once, nothing of ``y`` copied, sliced or gathered)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hidden = y.shape[1]
    tiles = y.shape[0] // row_tile
    cols = cols or _depth_tile(hidden, n, 4, _SUMS_BYTES)

    def y_map(c, t, total, token, weight):
        live = (total[0] + row_tile - 1) // row_tile
        return jnp.minimum(t, jnp.maximum(live - 1, 0)), c

    vmem = n * cols * (4 + 2 * jnp.dtype(dtype).itemsize) \
        + 2 * row_tile * cols * 4
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(hidden // cols, tiles),
            in_specs=[pl.BlockSpec((row_tile, cols), y_map)],
            out_specs=pl.BlockSpec((n, cols), lambda c, t, *_: (0, c)),
            scratch_shapes=[pltpu.VMEM((n, cols), _F32)]),
        out_shape=jax.ShapeDtypeStruct((n, hidden), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(2 * vmem + (8 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * y.shape[0] * hidden, transcendentals=0,
            bytes_accessed=y.shape[0] * hidden * 4 + n * hidden * 2),
        name="moe_combine", interpret=interpret)(total, token, weight, y)


@functools.partial(jax.jit, static_argnames=("rows", "depths", "cols",
                                             "interpret"))
def _kernel_held(x, token, weight, sizes, w13, w2, rows=None, depths=None,
                 cols=None, interpret=False):
    """The layer's output for the pairs whose expert is held, moving no row
    of any other pair.  ``x`` (n, H) the layer's rows, ``token`` / ``weight``
    (n x k,) the row and the float32 weight of each pair in sorted order (the
    held pairs are its first ``sum(sizes)``), ``sizes`` (held,).

    Into the kernel: the row tiles that hold a held pair, and no other, are
    gathered from ``x`` straight into the layout ``moe_grouped`` reads (a
    loop whose trip count is the live tiles: what lies past them is never
    written, and no visit names it).  Out of it: ``moe_combine`` adds the
    live tiles of the kernel's output, where they lie, into the rows' sums
    (float32, in the sorted pairs' order); the output is neither sliced nor
    gathered, and its tiles past the live ones are never read.  Nothing is
    bounded: with every pick held every tile is live.  Jitted on its own,
    and the tiles a loop, for the start's sake (:func:`_kernel_grouped`);
    ``rows``, ``depths`` and ``cols`` (the combine's block of columns) are
    for tests."""
    from jax.experimental.layout import Layout, with_layout_constraint

    n, hidden = x.shape
    call, row_tile, tiles, k13, d13 = _grouped_call(
        token.shape[0], w2.shape[0], hidden, w2.shape[1], x.dtype, rows,
        depths, interpret)
    total = jnp.sum(sizes, dtype=jnp.int32).reshape(1)
    past = tiles * row_tile - token.shape[0]
    token, weight = jnp.pad(token, (0, past)), jnp.pad(weight, (0, past))
    # one copy of the rows by weight tile (n rows, not n x k)
    tiled = x.reshape(n, k13, d13).swapaxes(0, 1)

    def gather(t, into):
        rows_of = lax.dynamic_slice(token, (t * row_tile,), (row_tile,))
        return lax.dynamic_update_slice(
            into, jnp.take(tiled, rows_of, axis=1, mode="clip"),
            (0, t * row_tile, 0))

    # a buffer nobody wrote (on a TPU XLA's ``AllocateBuffer``: no pass over
    # all of it, as ``jnp.zeros`` would be), held to the order the kernel
    # reads (left free, XLA lays the loop's buffer out pair by pair, as the
    # gather makes it, and copies ALL of it for the kernel)
    into = lax.fori_loop(
        0, (total[0] + row_tile - 1) // row_tile, gather,
        with_layout_constraint(
            lax.empty((k13, tiles * row_tile, d13), x.dtype),
            Layout(major_to_minor=(0, 1, 2))))
    y = call(*_visits(sizes, tiles, row_tile), into, w13, w2)
    return _combine(y, token, weight, total, n, x.dtype, row_tile, cols,
                    interpret)


def experts_formulation(platform, dtype, hidden, width):
    """What the grouped products of ``_contrib_RoutedExperts`` are, read off
    where the operands live and what they are: ``"pallas"`` -- the kernel
    ``moe_grouped``, which streams each expert with a group once a row tile
    -- on a TPU for bfloat16 leaves whose widths (``hidden``, ``width`` and
    so ``2 x width``) are whole lane tiles; ``"ragged"`` -- XLA's own
    grouped kernel over the sorted pairs -- on a TPU for any other operands;
    ``"ragged-dense"`` -- the same ``ragged_dot`` expanded by XLA into a
    masked dense product, every expert over every pair: the oracle of both,
    fine at test sizes -- anywhere else.  An observation, as ``ops/paged.py``
    ``decode_formulation`` is: no attribute or environment variable
    chooses."""
    if platform != "tpu":
        return "ragged-dense"
    tiled = (jnp.dtype(dtype) == jnp.bfloat16 and hidden % 128 == 0
             and width % 128 == 0)
    return "pallas" if tiled else "ragged"


def experts_path(formulation, held, num_experts, pairs):
    """Which pairs ``_contrib_RoutedExperts`` moves at the width of a row,
    read off what the op can see (as :func:`experts_formulation`: no
    attribute, environment variable or model name chooses): ``"held"`` --
    only the pairs whose expert this chip holds (:func:`_kernel_held`) --
    where the kernel runs, the chip holds a share of the router's experts
    (``held`` of ``num_experts``, wherever the share starts) and the sorted
    pairs fill more than one row tile, so that there are tiles to leave out
    (a prefill's thousands of pairs); ``"all"`` -- every pair sorted,
    gathered and gathered back around the products -- for a lane step's one
    row tile, for a layer that holds every expert (every pair is its own)
    and for the XLA formulations."""
    share = 0 < held < num_experts
    return "held" if (formulation == "pallas" and share
                      and pairs > _ROW_TILE) else "all"


_GROUPED = {"pallas": _kernel_grouped, "ragged": _ragged_grouped,
            "ragged-dense": _ragged_grouped}


def _sorted_pairs(ids, first_expert, held):
    """The index work, over all ``n x k`` pairs (int32s, never rows):
    ``mine`` (n x k,) whether a pair's expert is held, ``order`` the pairs
    sorted by held expert (pairs of other shares, and of rows that are not
    live, sort behind every held group and belong to none) and ``sizes``
    (held,) the groups."""
    local = ids.reshape(-1) - int(first_expert)
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return mine, order, sizes


def _all_pairs(rows, ids, weights, w13, w2, first_expert, grouped):
    """Every pair's row gathered into sorted order, through ``grouped``, and
    gathered back (the ``"all"`` path, and what a gradient goes through)."""
    n, k = ids.shape
    mine, order, sizes = _sorted_pairs(ids, first_expert, w13.shape[0])
    y = grouped(jnp.take(rows, order // k, axis=0), sizes, w13, w2)
    # back to (row, pick) order; a select, not a product with a zero weight:
    # what the grouped product leaves in a row of no group is not looked at
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(n, k, -1)
    w = weights.astype(_F32)[:, :, None]
    y = jnp.where(mine.reshape(n, k, 1), y * w, 0.0)
    return jnp.sum(y, axis=1).astype(rows.dtype)


def _held_pairs(rows, ids, weights, w13, w2, first_expert, held):
    """The ``"held"`` path: the index work here, the rows in ``held``
    (:func:`_kernel_held`).  A loop with a dynamic trip count has no reverse
    mode, so a gradient goes through :func:`_all_pairs` over the XLA
    formulation, as the kernel's own does."""
    @jax.custom_vjp
    def forward(rows, ids, weights, w13, w2):
        _, order, sizes = _sorted_pairs(ids, first_expert, w13.shape[0])
        return held(rows, (order // ids.shape[1]).astype(jnp.int32),
                    jnp.take(weights.astype(_F32).reshape(-1), order),
                    sizes, w13, w2)

    def backward(operands, dy):
        rows, ids, weights, w13, w2 = operands
        drows, dweights, dw13, dw2 = jax.vjp(
            lambda rows, weights, w13, w2: _all_pairs(
                rows, ids, weights, w13, w2, first_expert, _ragged_grouped),
            rows, weights, w13, w2)[1](dy)
        return drows, None, dweights, dw13, dw2

    forward.defvjp(lambda *operands: (forward(*operands), operands),
                   backward)
    return forward(rows, ids, weights, w13, w2)


@functools.partial(jax.jit, static_argnames=("first_expert", "grouped",
                                             "held"))
def routed_experts(rows, ids, weights, w13, w2, *, first_expert=0,
                   grouped=_ragged_grouped, held=None):
    """``rows`` (n, H), ``ids`` / ``weights`` (n, k), ``w13`` (held, H, 2F)
    ``[W1 | W3]`` and ``w2`` (held, F, H), the experts ``first_expert ..
    first_expert + held - 1``.  ``grouped`` makes the two products of the
    pairs sorted by expert: :func:`_ragged_grouped` (here, and wherever
    :func:`experts_formulation` says so) or :func:`_kernel_grouped`.
    ``held`` is None where every pair's row is moved around ``grouped``,
    or :func:`_kernel_held`, which moves the held pairs' alone and runs the
    kernel itself (:func:`experts_path` says which).
    Returns (n, H) in ``rows``' dtype: the held experts' part of the
    layer's output."""
    if held is not None:
        return _held_pairs(rows, ids, weights, w13, w2, first_expert, held)
    return _all_pairs(rows, ids, weights, w13, w2, first_expert, grouped)


def _router_inputs(attrs):
    return (["data", "weight"]
            + (["bias"] if attrs.get("use_bias", True) else [])
            + (["live"] if attrs.get("use_live") else []))


@register("_contrib_MoERouter", inputs=_router_inputs,
          params={"top_k": Param(int, required=True),
                  "normalize": Param(bool, True),
                  "scale": Param(float, 1.0),
                  # None (not written into a graph): sigmoid
                  "score": Param(str, None, enum=SCORES),
                  "use_bias": Param(bool, True),
                  "use_live": Param(bool, False)},
          num_outputs=3, no_grad_inputs=("live",),
          output_names=lambda attrs: ["ids", "weights", "load"],
          hint="moerouter")
@jax.named_scope("moe_router")
def _moe_router(opctx, attrs, data, weight, *more):
    """:func:`route` as an op (``score``: ``sigmoid`` or ``softmax``, both
    under this one scope): reads ``data`` (rows, H),
    ``weight`` (E, H), unless ``use_bias`` is off ``bias`` (E,) and, with
    ``use_live``, ``live`` (rows,; nonzero: the row routes); writes ``ids``
    (rows, k) int32, ``weights`` (rows, k) float32 and ``load`` (E,)
    int32."""
    more = list(more)
    bias = more.pop(0) if attrs.get("use_bias", True) else None
    return route(data, weight, bias, more[0] if more else None,
                 top_k=int(attrs["top_k"]),
                 normalize=bool(attrs.get("normalize", True)),
                 scale=float(attrs.get("scale", 1.0)),
                 score=attrs.get("score") or "sigmoid")


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
    from .interpret import platform_of

    first, held = int(attrs.get("first_expert", 0)), w13.shape[0]
    if first < 0 or first + held > int(attrs["num_experts"]):
        raise ValueError("experts %d..%d are not among the router's %d"
                         % (first, first + held - 1,
                            int(attrs["num_experts"])))
    formulation = experts_formulation(
        platform_of(data, w13, w2), jnp.result_type(data, w13, w2),
        data.shape[1], w2.shape[1])
    path = experts_path(formulation, held, int(attrs["num_experts"]),
                        ids.shape[0] * ids.shape[1])
    return routed_experts(data, ids, weights, w13, w2, first_expert=first,
                          grouped=_GROUPED[formulation],
                          held=_kernel_held if path == "held" else None)


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def rotary_table(theta, d, scaling=None):
    """The ``d / 2`` frequencies of a rotation over ``d`` features and the
    factor on its cosines and sines: ``e_m = theta^(-2m / d)`` and 1, or
    with ``scaling`` (YaRN: ``factor``, ``original_max``, ``beta_fast``,
    ``beta_slow``, ``attention_factor``) the frequencies that turn more than
    ``beta_fast`` times over the ``original_max`` positions as they are,
    those that turn fewer than ``beta_slow`` times divided by ``factor``,
    and a ramp between::

        c(b) = d ln(original_max / (2 pi b)) / (2 ln theta)
        lo = max(floor(c(beta_fast)), 0),  hi = min(ceil(c(beta_slow)), d - 1)
        ramp_m = clip((m - lo) / (hi - lo), 0, 1)     (hi + 0.001 if hi = lo)
        f_m = (e_m / factor) ramp_m + e_m (1 - ramp_m)

    Computed here in float64 and held in float32: a constant of the graph."""
    half = d // 2
    m = np.arange(half, dtype=np.float64)
    freq = float(theta) ** (-2.0 * m / d)
    if not scaling:
        return freq.astype(np.float32), 1.0

    def turns(b):
        return d * math.log(scaling["original_max"] / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    lo = max(math.floor(turns(scaling["beta_fast"])), 0)
    hi = min(math.ceil(turns(scaling["beta_slow"])), d - 1)
    if hi == lo:
        hi += 0.001
    ramp = np.clip((m - lo) / (hi - lo), 0.0, 1.0)
    freq = freq / scaling["factor"] * ramp + freq * (1.0 - ramp)
    return freq.astype(np.float32), float(scaling["attention_factor"])


def rotary(x, positions, *, theta, rotary_dim=0, scaling=None):
    """``x`` (..., heads, head_dim) rotated by its position: the first
    ``rotary_dim`` features (0: all of them) in two halves, feature ``i``
    paired with ``i + rotary_dim / 2``, by the angle ``position *
    theta^(-2i / rotary_dim)`` (with ``scaling`` the table and the factor
    on cosine and sine of :func:`rotary_table`); the rest pass.
    ``positions`` is the
    trailing part of ``x``'s leading axes ((L,) for (b, L, heads, d),
    (lanes,) for (lanes, heads, d)).  Angles, sines and the rotation are
    float32; returned in ``x``'s dtype."""
    d = int(rotary_dim) or x.shape[-1]
    half = d // 2
    if scaling:
        inv, factor = rotary_table(theta, d, scaling)
    else:
        inv, factor = jnp.exp(jnp.arange(half, dtype=_F32) * (-2.0 / d)
                              * jnp.log(_F32(theta))), 1.0
    ang = positions.astype(_F32)[..., None, None] * inv  # (..., 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * _F32(factor), sin * _F32(factor)
    x32 = x.astype(_F32)
    a, b = x32[..., :half], x32[..., half:d]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                           x32[..., d:]], axis=-1)
    return out.astype(x.dtype)


@register("_contrib_Rotary", inputs=("data", "positions"),
          params={"theta": Param(float, 10000.0),
                  "rotary_dim": Param(int, 0),
                  # YaRN (:func:`rotary_table`): all five or none (None is
                  # not written into a graph: the graphs without stay as
                  # they were)
                  "factor": Param("float-or-none", None),
                  "original_max": Param("int-or-none", None),
                  "beta_fast": Param("float-or-none", None),
                  "beta_slow": Param("float-or-none", None),
                  "attention_factor": Param("float-or-none", None)},
          no_grad_inputs=("positions",), hint="rotary")
@jax.named_scope("rotary")
def _rotary(opctx, attrs, data, positions):
    """:func:`rotary` as an op: reads ``data`` (..., heads, head_dim) and
    ``positions`` (the sequence axis' or the lanes'; float carrier or
    int), writes ``data``'s shape and dtype."""
    keys = ("factor", "original_max", "beta_fast", "beta_slow",
            "attention_factor")
    scaling = {k: attrs.get(k) for k in keys}
    if all(v is None for v in scaling.values()):
        scaling = None
    elif any(v is None for v in scaling.values()):
        raise ValueError("a scaled rotation needs every one of %s; got %s"
                         % (keys, scaling))
    return rotary(data, positions, theta=float(attrs.get("theta", 10000.0)),
                  rotary_dim=int(attrs.get("rotary_dim", 0)),
                  scaling=scaling)
