"""``_contrib_PagedAttention``'s two formulations: the Pallas kernel that
walks each lane's live pages (interpret mode here) against the XLA gather
over the whole table, which stays as its oracle; which of the two the op
picks where; and what the engine says about it (``snapshot()``, the
``gen:step`` span's ``pages``).  Then the same for
``_contrib_PagedLatentAttention``: its kernel over ONE plane of latent rows
against its gather.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.generation import DecodeEngine
from mxnet_tpu.generation import engine as engine_mod
from mxnet_tpu.ops import paged
from mxnet_tpu.ops.interpret import bound_to

HEADS, HD = 2, 8

# the two forms of plane the kernel takes, name -> (query heads, K/V heads,
# head_dim, dtype, a token held as one row of kv_heads * head_dim): float32
# tokens by heads (toy widths), and the bfloat16 row tokens of the three
# hybrid cells' attention layers (their own head shapes) and of a family
# whose every query head has a K/V head of its own
FORMS = {
    "float32": (HEADS, HEADS, HD, jnp.float32, False),
    "bf16_32_over_8_of_64": (32, 8, 64, jnp.bfloat16, True),
    "bf16_32_over_8_of_128": (32, 8, 128, jnp.bfloat16, True),
    "bf16_16_of_128_group_1": (16, 16, 128, jnp.bfloat16, True),
    # the sliding-window cell's full layers: six query heads to a K/V head
    "bf16_48_over_8_of_128": (48, 8, 128, jnp.bfloat16, True),
}

# name -> (page_size, max_pages, positions; None is an inactive lane,
#          page ids handed out: "shuffled", "descending" or "ascending"
#          [, the planes' form: "float32" unless given])
CASES = {
    "page_size_4": (4, 8, [5, 31, 12, 18], "shuffled"),
    "page_size_16": (16, 4, [40, 7, 63, 22], "shuffled"),
    "first_slot_of_a_page": (4, 8, [8, 4, 16, 1], "shuffled"),
    "last_slot_of_a_page": (4, 8, [7, 3, 15, 27], "shuffled"),
    "first_and_last_slot_16": (16, 4, [16, 15, 48, 47], "shuffled"),
    "table_full": (4, 8, [31, 2], "shuffled"),
    "inactive_lane_beside_live": (4, 8, [9, None, 30, None], "shuffled"),
    "all_lanes_inactive": (4, 8, [None, None], "ascending"),
    "position_zero_live": (4, 8, [0, 6], "shuffled"),
    "pages_out_of_order": (4, 8, [29, 14, 21, 6], "descending"),
    "lane_bucket_1": (4, 8, [13], "shuffled"),
    "lane_bucket_4": (16, 4, [33, 1, 17, 60], "shuffled"),
    "lane_bucket_8": (4, 8, [3, 30, 11, None, 16, 23, 0, 8], "shuffled"),
    "more_pages_than_the_ring": (4, 16, [63, 50, 37], "shuffled"),
    # grouped bfloat16 pages: an inactive lane, a live lane at position 0,
    # lanes of one page and less, and tables of more slots than the ring
    "grouped_heads_of_64": (16, 12, [40, None, 0, 191, 130, 16, 7],
                            "shuffled", "bf16_32_over_8_of_64"),
    "grouped_heads_of_128": (16, 12, [40, None, 0, 191, 130, 16, 7],
                             "shuffled", "bf16_32_over_8_of_128"),
    "bfloat16_group_of_1": (16, 12, [40, None, 0, 191, 130], "shuffled",
                            "bf16_16_of_128_group_1"),
    "grouped_six_to_a_head": (16, 12, [40, None, 0, 191, 130, 16, 7],
                              "shuffled", "bf16_48_over_8_of_128"),
    "grouped_six_to_a_head_long": (16, 96, [1535, 700, None, 1100],
                                   "descending", "bf16_48_over_8_of_128"),
    "grouped_more_slots_than_the_ring": (16, 96, [1535, 700, None, 1100],
                                         "descending",
                                         "bf16_32_over_8_of_64"),
    "grouped_all_lanes_inactive": (16, 4, [None, None], "ascending",
                                   "bf16_32_over_8_of_64"),
    "grouped_page_size_4": (4, 8, [5, 31, 12, 0], "shuffled",
                            "bf16_32_over_8_of_64"),
}


def _table(rng, num_pages, page_size, max_pages, positions, order):
    """The page table that hands each live lane the pages its tokens need
    (ids ``order``ed), and the lanes' positions (an inactive lane's: 0)."""
    free = list(range(1, num_pages))  # popped from the end
    if order == "shuffled":
        rng.shuffle(free)
    elif order == "ascending":
        free.reverse()
    table = np.zeros((len(positions), max_pages), np.int32)
    for lane, at in enumerate(positions):
        if at is not None:
            held = at // page_size + 1
            table[lane, :held] = [free.pop() for _ in range(held)]
    return jnp.asarray(table), jnp.asarray([p or 0 for p in positions],
                                           jnp.int32)


def _operands(page_size, max_pages, positions, order, form="float32",
              seed=0):
    heads, kv_heads, hd, dtype, rows = FORMS[form]
    rng = np.random.RandomState(seed)
    lanes = len(positions)
    num_pages = 1 + lanes * max_pages  # page 0 is the scratch page
    q = jnp.asarray(rng.randn(lanes, heads, hd), dtype)
    k_new, v_new = (jnp.asarray(rng.randn(lanes, kv_heads, hd), dtype)
                    for _ in range(2))
    token = (kv_heads * hd,) if rows else (kv_heads, hd)
    k_pool, v_pool = (jnp.asarray(rng.randn(num_pages, page_size, *token),
                                  dtype) for _ in range(2))
    table, at = _table(rng, num_pages, page_size, max_pages, positions, order)
    return q, k_new, v_new, k_pool, v_pool, table, at


def _in_float32(ops):
    """The same values, held in float32: the gather over them rounds
    nothing, so it is the oracle of a kernel whose sums are float32."""
    return [x.astype(jnp.float32) for x in ops[:5]] + list(ops[5:])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_the_gather(name):
    page_size, max_pages, positions, order = CASES[name][:4]
    ops = _operands(*CASES[name])
    scale = 1.0 / np.sqrt(ops[0].shape[-1])
    want, want_k, want_v = paged._gather_decode(*ops, scale)
    got, got_k, got_v = paged._kernel_decode(*ops, scale, interpret=True)
    if got.dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    else:
        # bfloat16 pages: the kernel's products and sums are float32 (never
        # less exact than the gather's, which rounds its scores), so it is
        # held to the float32 gather over the same values, to the ONE
        # rounding of its output (8 bits of mantissa)
        exact = paged._gather_decode(*_in_float32(ops), scale)[0]
        np.testing.assert_allclose(got.astype(jnp.float32), exact,
                                   rtol=2.0 ** -8, atol=1e-4)
        assert got.dtype == want.dtype == ops[0].dtype
        assert got_k.dtype == got_v.dtype == ops[3].dtype
    # the pool: nothing but the lanes' rows changed, and those hold this
    # step's K/V.  (Every inactive lane writes the scratch page's first
    # slot: which of them lands last is nobody's business.)
    k_pool, v_pool, table, at = ops[3], ops[4], np.asarray(ops[5]), ops[6]
    for got_p, want_p, before, new in ((got_k, want_k, k_pool, ops[1]),
                                       (got_v, want_v, v_pool, ops[2])):
        np.testing.assert_array_equal(got_p[1:], want_p[1:])
        np.testing.assert_array_equal(got_p[0, 1:], before[0, 1:])
        for lane, pos in enumerate(positions):
            if pos is not None:
                page = table[lane, pos // page_size]
                np.testing.assert_array_equal(
                    got_p[page, pos % page_size],
                    new[lane].reshape(before.shape[2:]))
    if sum(p is None for p in positions) == 1:
        np.testing.assert_array_equal(got_k[0], want_k[0])


@pytest.mark.parametrize("form,page_size,positions", [
    ("float32", 4, [9, 14]),
    ("bf16_32_over_8_of_64", 16, [37, 58]),
    ("bf16_32_over_8_of_128", 16, [37, 58]),
    ("bf16_16_of_128_group_1", 16, [37, 58]),
])
def test_history_beyond_the_position_is_never_read(form, page_size,
                                                   positions):
    """Slots at and after a lane's position may hold anything (a retired
    sequence's tokens, the slot this step writes): neither formulation lets
    them into the softmax, and the kernel does not even fetch the pages
    after the position's (a slot of the ring holds several bfloat16 pages of
    a lane: the rows past its live pages are masked too)."""
    max_pages = 8
    ops = list(_operands(page_size, max_pages, positions, "shuffled", form))
    scale = 1.0 / np.sqrt(ops[0].shape[-1])
    want = paged._kernel_decode(*ops, scale, interpret=True)[0]
    table = np.asarray(ops[5])
    for plane in (3, 4):
        pool = np.asarray(ops[plane].astype(jnp.float32)).copy()
        for lane, pos in enumerate(positions):
            page = table[lane, pos // page_size]
            pool[page, pos % page_size:] = 1e4
        ops[plane] = jnp.asarray(pool, ops[plane].dtype)
    # a dead page id in the table's tail: must not be fetched (NaN poisons)
    poisoned = table.copy()
    poisoned[0, positions[0] // page_size + 1:] = table[1, 0]
    got = paged._kernel_decode(*ops[:5], jnp.asarray(poisoned), ops[6],
                               scale, interpret=True)[0]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("platform,heads,head_dim,dtype,want", [
    ("tpu", 16, 128, np.float32, "pallas"),
    ("cpu", 16, 128, np.float32, "xla"),
    ("gpu", 16, 128, np.float32, "xla"),
    ("tpu", 2, 8, np.float32, "xla"),        # a token's K is no whole tile
    ("tpu", 4, 128, np.float32, "xla"),
    # bfloat16 tokens by heads are no whole tiles: only as ONE row
    ("tpu", 16, 128, jnp.bfloat16, "xla"),
])
def test_formulation_follows_where_the_operands_live(platform, heads,
                                                     head_dim, dtype, want):
    assert paged.decode_formulation(platform, heads, head_dim, dtype) == want


@pytest.mark.parametrize("platform,heads,kv_heads,head_dim,dtype,more,want", [
    # the three hybrid cells' attention layers, and a group of 1
    ("tpu", 32, 8, 64, jnp.bfloat16, {}, "pallas"),
    ("tpu", 32, 8, 128, jnp.bfloat16, {}, "pallas"),
    ("tpu", 16, 16, 128, jnp.bfloat16, {}, "pallas"),
    ("cpu", 32, 8, 64, jnp.bfloat16, {}, "xla"),
    ("gpu", 32, 8, 128, jnp.bfloat16, {}, "xla"),
    # float32 rows, or a float32 query over bfloat16 rows: the gather
    ("tpu", 32, 8, 64, np.float32, {}, "xla"),
    ("tpu", 4, 2, 8, jnp.bfloat16, {}, "xla"),     # a row of 16 lanes
    ("tpu", 32, 8, 24, jnp.bfloat16, {}, "xla"),   # 192: off a lane tile
    ("tpu", 12, 5, 128, jnp.bfloat16, {}, "xla"),  # no whole groups
    ("tpu", 32, 8, 64, jnp.bfloat16, {"page_size": 4}, "xla"),
    ("tpu", 32, 8, 64, jnp.bfloat16, {"page_size": 32}, "pallas"),
])
def test_formulation_takes_a_token_held_as_one_row(platform, heads, kv_heads,
                                                   head_dim, dtype, more,
                                                   want):
    """``rows``: the plane holds a token as one row of ``kv_heads x
    head_dim`` lanes.  The kernel takes such pages in bfloat16 where the row
    is whole lane tiles and the page whole sublane tiles, grouped or not."""
    assert paged.decode_formulation(platform, heads, head_dim, dtype,
                                    kv_heads=kv_heads, rows=True,
                                    **more) == want


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _pallas_eqns(inner)


def _pallas_calls(jaxpr):
    return [eqn.params["name"] for eqn in _pallas_eqns(jaxpr)]


@pytest.mark.parametrize("platform,form,want", [
    ("cpu", "float32", []), ("tpu", "float32", ["paged_decode"]),
    ("cpu", "bfloat16 rows", []), ("tpu", "bfloat16 rows", ["paged_decode"]),
    ("tpu", "float32 query, bfloat16 rows", [])])
def test_op_picks_by_the_executors_scope(platform, form, want):
    """Traced under ``bound_to`` (what an Executor enters around every
    program it traces) the op runs the kernel on a tpu context only: no
    attribute, no environment variable.  Traced, not run."""
    from mxnet_tpu.ops.registry import get_op

    heads, hd, page_size = 8, 128, 8
    shapes = [(2, heads, hd)] * 3 + [(5, page_size, heads, hd)] * 2 + \
        [(2, 4), (2,)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    if form != "float32":  # 8 query heads over 2 K/V heads of 128, a row
        page_size, bf16 = 16, jnp.bfloat16
        args = [jax.ShapeDtypeStruct(
                    (2, heads, hd), jnp.float32 if "query" in form else bf16)
                ] + [jax.ShapeDtypeStruct((2, 2, hd), bf16)] * 2 + \
            [jax.ShapeDtypeStruct((5, page_size, 2 * hd), bf16)] * 2 + \
            args[5:]
    op = get_op("_contrib_PagedAttention")

    def step(*a):
        with bound_to(platform):
            return op.fn(None, {"page_size": page_size}, *a)

    assert _pallas_calls(jax.make_jaxpr(step)(*args).jaxpr) == want


@pytest.mark.parametrize("donated", [True, False])
def test_the_kernel_holds_only_donated_planes_to_the_hbm(donated):
    """Inside a program whose carried planes are donated (what an Executor
    says around it: ``interpret.carrying``) the kernel's aliased plane
    outputs are held to the HBM, so that XLA cannot stage a whole plane
    through its fast memory around the call; a plane XLA must copy first is
    left free, because held it aborts the TPU compiler (tests/
    test_chip_compile.py compiles both).  Traced, not run."""
    from mxnet_tpu.ops.interpret import carrying
    from mxnet_tpu.ops.registry import get_op

    bf16 = jnp.bfloat16
    args = [jax.ShapeDtypeStruct((2, 8, 128), bf16)] + \
        [jax.ShapeDtypeStruct((2, 2, 128), bf16)] * 2 + \
        [jax.ShapeDtypeStruct((5, 16, 256), bf16)] * 2 + \
        [jax.ShapeDtypeStruct((2, 4), jnp.float32),
         jax.ShapeDtypeStruct((2,), jnp.float32)]
    op = get_op("_contrib_PagedAttention")

    def step(*a):
        with bound_to("tpu"):
            return op.fn(None, {"page_size": 16}, *a)

    (eqn,) = _pallas_eqns(jax.make_jaxpr(carrying(step, donated))(*args).jaxpr)
    spaces = [str(getattr(aval, "memory_space", None))
              for aval in eqn.params["out_avals"]]
    assert [s == "hbm" for s in spaces] == [False, donated, donated], spaces


# -- latent attention: one plane of rows [c | k_r] ---------------------------

# name -> (heads, nope, rope, rank, v, the plane's row): the latent cell's
# widths (512 + 64 values in 640 columns), a narrow family, and rows that
# fill their lane tiles
LATENT_FORMS = {
    "cell": (128, 128, 64, 512, 128, 640),
    "narrow": (8, 16, 64, 128, 16, 256),
    "whole_tiles": (16, 32, 128, 128, 32, 256),
}

# name -> (max_pages, positions; None is an inactive lane, page ids handed
#          out, the form).  Pages of 16.
LATENT_CASES = {
    # an inactive lane on the scratch page, a live lane at position 0, at a
    # page's first and last slot, at the table's end
    "cell_widths": (12, [40, None, 0, 191, 176, 16, 15], "shuffled", "cell"),
    "narrow_rows": (12, [40, None, 0, 191, 130, 16, 7], "shuffled",
                    "narrow"),
    "rows_of_whole_tiles": (12, [40, None, 0, 191, 130], "shuffled",
                            "whole_tiles"),
    "more_slots_than_the_ring": (96, [1535, 700, None, 1100], "descending",
                                 "narrow"),
    "all_lanes_inactive": (4, [None, None], "ascending", "narrow"),
    "first_and_last_slot_of_a_page": (4, [16, 15, 48, 47], "shuffled",
                                      "narrow"),
    "one_lane": (8, [77], "shuffled", "narrow"),
    "table_full": (8, [127, 2], "shuffled", "narrow"),
}


def _latent_operands(max_pages, positions, order, form, seed=0,
                     dtype=jnp.bfloat16):
    heads, nope, rope, rank, v, row = LATENT_FORMS[form]
    rng = np.random.RandomState(seed)
    lanes, page_size = len(positions), 16
    num_pages = 1 + lanes * max_pages  # page 0 is the scratch page
    q_n, q_r, new = (jnp.asarray(rng.randn(*shape), dtype) for shape in (
        (lanes, heads, nope), (lanes, heads, rope), (lanes, rank + rope)))
    weight = jnp.asarray(rng.randn(heads * (nope + v), rank)
                         / np.sqrt(rank), dtype)
    pool = rng.randn(num_pages, page_size, row)
    pool[..., rank + rope:] = 0  # as the pool holds a row
    table, at = _table(rng, num_pages, page_size, max_pages, positions, order)
    return q_n, q_r, new, weight, jnp.asarray(pool, dtype), table, at


def _latent_scale(form):
    heads, nope, rope = LATENT_FORMS[form][:3]
    return float(1.0 / np.sqrt(nope + rope) / np.sqrt(nope))


@pytest.mark.parametrize("name", sorted(LATENT_CASES))
def test_latent_kernel_matches_the_gather(name):
    max_pages, positions, order, form = LATENT_CASES[name]
    ops = _latent_operands(*LATENT_CASES[name])
    scale = _latent_scale(form)
    want, want_pool = paged.paged_latent_attention(*ops, scale=scale)
    got, got_pool = paged._kernel_latent_decode(*ops, scale=scale,
                                                interpret=True)
    assert got.dtype == want.dtype == ops[0].dtype
    assert got_pool.dtype == ops[4].dtype and got_pool.shape == ops[4].shape
    # the gather rounds the probabilities, ``o_c`` and the output once each
    # to 8 bits of mantissa; the kernel's call rounds the last two (the
    # kernel itself nothing: float32 sums of exact products).  Both are held
    # to the float32 gather over the same values, to three such roundings of
    # the largest attended value
    exact = np.asarray(paged.paged_latent_attention(
        *[x.astype(jnp.float32) for x in ops[:5]], *ops[5:],
        scale=scale)[0])
    tol = 3 * 2.0 ** -8 * np.abs(exact).max()
    for out in (got, want):
        np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                                   exact, rtol=0, atol=tol)
    # the plane: nothing but the lanes' rows changed, and those hold this
    # step's row, zeros after its values.  (Every inactive lane writes the
    # scratch page's first slot: which of them lands last is nobody's
    # business.)
    new, pool, table = ops[2], ops[4], np.asarray(ops[5])
    np.testing.assert_array_equal(got_pool[1:], want_pool[1:])
    np.testing.assert_array_equal(got_pool[0, 1:], pool[0, 1:])
    for lane, pos in enumerate(positions):
        if pos is not None:
            row = got_pool[table[lane, pos // 16], pos % 16]
            np.testing.assert_array_equal(row[:new.shape[1]], new[lane])
            assert not np.asarray(row[new.shape[1]:]).any()


@pytest.mark.parametrize("form,positions", [("cell", [37, 58]),
                                            ("narrow", [37, 150])])
def test_latent_history_beyond_the_position_is_never_read(form, positions):
    """Slots at and after a lane's position may hold anything finite (a
    retired sequence's rows, the slot this step writes) and the pages after
    the position's anything at all: the kernel masks the first and never
    fetches the second (NaN would poison its weighted rows: 0 x NaN)."""
    max_pages = 12
    ops = list(_latent_operands(max_pages, positions, "shuffled", form))
    scale = _latent_scale(form)
    want = paged._kernel_latent_decode(*ops, scale=scale, interpret=True)[0]
    table = np.asarray(ops[5]).copy()
    pool = np.asarray(ops[4].astype(jnp.float32)).copy()
    poisoned = table.copy()
    for lane, pos in enumerate(positions):
        pool[table[lane, pos // 16], pos % 16:] = 1e4
    # lane 0's table names a page of NaN after its live ones
    spare = next(p for p in range(1, pool.shape[0]) if p not in table)
    pool[spare] = np.nan
    poisoned[0, positions[0] // 16 + 1:] = spare
    got = paged._kernel_latent_decode(
        *ops[:4], jnp.asarray(pool, ops[4].dtype), jnp.asarray(poisoned),
        ops[6], scale=scale, interpret=True)[0]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("platform,heads,rank,row,dtype,more,want", [
    ("tpu", 128, 512, 640, jnp.bfloat16, {}, "pallas-absorbed-live-pages"),
    ("cpu", 128, 512, 640, jnp.bfloat16, {}, "xla-absorbed-gather"),
    ("gpu", 128, 512, 640, jnp.bfloat16, {}, "xla-absorbed-gather"),
    ("tpu", 8, 128, 256, jnp.bfloat16, {}, "pallas-absorbed-live-pages"),
    # float32 rows, or a float32 query over bfloat16 rows: the gather
    ("tpu", 128, 512, 640, np.float32, {}, "xla-absorbed-gather"),
    # a row as wide as its values, off a lane tile: a page is no block of
    # whole tiles (and a chip lays such a plane out pages-minor)
    ("tpu", 128, 512, 576, jnp.bfloat16, {}, "xla-absorbed-gather"),
    ("tpu", 4, 12, 16, jnp.bfloat16, {}, "xla-absorbed-gather"),  # toy
    ("tpu", 128, 192, 256, jnp.bfloat16, {}, "xla-absorbed-gather"),
    ("tpu", 12, 512, 640, jnp.bfloat16, {}, "xla-absorbed-gather"),
    ("tpu", 128, 512, 640, jnp.bfloat16, {"page_size": 4},
     "xla-absorbed-gather"),
    ("tpu", 128, 512, 640, jnp.bfloat16, {"page_size": 32},
     "pallas-absorbed-live-pages"),
])
def test_latent_formulation_follows_where_the_operands_live(
        platform, heads, rank, row, dtype, more, want):
    """The kernel takes bfloat16 rows of whole lane tiles whose values (the
    first ``rank`` columns) are whole lane tiles too, in pages of whole
    sublane tiles, on a TPU; anything else the gather."""
    assert paged.latent_formulation(platform, heads, rank, row, dtype,
                                    **more) == want


def _latent_op_args(form, lanes, pages, max_pages, query=jnp.bfloat16,
                    rows=jnp.bfloat16):
    heads, nope, rope, rank, v, row = LATENT_FORMS[form]
    shapes = [((lanes, heads, nope), query), ((lanes, heads, rope), query),
              ((lanes, rank + rope), query),
              ((heads * (nope + v), rank), query), ((pages, 16, row), rows),
              ((lanes, max_pages), jnp.float32), ((lanes,), jnp.float32)]
    return [jax.ShapeDtypeStruct(*s) for s in shapes]


@pytest.mark.parametrize("platform,form,want", [
    ("cpu", "bfloat16", []), ("tpu", "bfloat16", ["paged_latent_decode"]),
    ("tpu", "float32", []), ("tpu", "float32 query, bfloat16 rows", [])])
def test_latent_op_picks_by_the_executors_scope(platform, form, want):
    """As ``_contrib_PagedAttention``: traced under ``bound_to`` the op runs
    its kernel on a tpu context only, for bfloat16 query and rows: no
    attribute, no environment variable.  Traced, not run."""
    from mxnet_tpu.ops.registry import get_op

    f32 = jnp.float32
    args = _latent_op_args(
        "narrow", 2, 5, 4, query=f32 if "float32" in form else jnp.bfloat16,
        rows=f32 if form == "float32" else jnp.bfloat16)
    op = get_op("_contrib_PagedLatentAttention")

    def step(*a):
        with bound_to(platform):
            return op.fn(None, {"page_size": 16, "scale": 0.1}, *a)

    assert _pallas_calls(jax.make_jaxpr(step)(*args).jaxpr) == want


@pytest.mark.parametrize("donated", [True, False])
def test_the_latent_kernel_holds_only_a_donated_plane_to_the_hbm(donated):
    """The K/V kernel's rule (above) for the one plane of this op."""
    from mxnet_tpu.ops.interpret import carrying
    from mxnet_tpu.ops.registry import get_op

    op = get_op("_contrib_PagedLatentAttention")

    def step(*a):
        with bound_to("tpu"):
            return op.fn(None, {"page_size": 16, "scale": 0.1}, *a)

    (eqn,) = _pallas_eqns(jax.make_jaxpr(carrying(step, donated))(
        *_latent_op_args("narrow", 2, 5, 4)).jaxpr)
    spaces = [str(getattr(aval, "memory_space", None))
              for aval in eqn.params["out_avals"]]
    assert [s == "hbm" for s in spaces] == [False, donated], spaces
    # the plane goes in and out as one buffer (operand 4 counts the two
    # scalar-prefetch ones)
    assert tuple(eqn.params["input_output_aliases"]) == ((4, 1),)


V, LAYERS, S, PAGE = 64, 2, 32, 4


def _lm_params():
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=LAYERS,
                                       num_heads=HEADS, hidden=HEADS * HD,
                                       seq_len=S)
    shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(0)
    return {name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
            for name, shp in zip(net.list_arguments(), shapes)
            if name not in ("data", "softmax_label")}


def test_engine_says_which_formulation_and_how_many_pages(monkeypatch):
    spans = []

    class Recorded(engine_mod._span):
        def __exit__(self, *exc):
            spans.append((self.name, dict(self.args or {})))
            return super().__exit__(*exc)

    monkeypatch.setattr(engine_mod, "_span", Recorded)
    eng = DecodeEngine(_lm_params(), vocab_size=V, num_layers=LAYERS,
                       num_heads=HEADS, hidden=HEADS * HD, max_seq_len=S,
                       lane_buckets=(1, 2), page_size=PAGE, num_pages=24,
                       prefill_len_buckets=(8, 16))
    try:
        # on the host platform the gather runs; what a chip would run is
        # the choice function's business (above)
        assert eng.snapshot()["paged_attention"] == "xla"
        assert "ssm_step" not in eng.snapshot()  # no state planes, no step
        assert "sequence_attention" not in eng.snapshot()  # no group
        prompt, new = [3, 1, 4, 1, 5, 9], 7
        assert len(eng.generate(prompt, new)) == new
    finally:
        eng.stop()
    steps = [args for name, args in spans if name == "gen:step"]
    # the prefill emits the first token; step k feeds position len + k
    assert [s["lanes"] for s in steps] == [1] * (new - 1)
    assert [s["pages"] for s in steps] == \
        [(len(prompt) + k) // PAGE + 1 for k in range(new - 1)]


def test_engine_says_pallas_for_grouped_bfloat16_pages_on_a_tpu(monkeypatch):
    """A family whose attention layers page grouped bfloat16 K/V (the hybrid
    one: 8 query heads over 2 K/V heads of 64, a token ONE row of 128 lanes,
    pages of 16) says ``pallas`` where its planes live on a TPU and ``xla`` on
    the host platform; a float32 pool of the same family, and pages that are
    no whole tiles, say ``xla`` on both."""
    import types

    from perfbench.builders import hybrid_lm as builder
    from perfbench.models import hybrid_lm as ref

    cfg = dict(vocab_size=V, hidden_size=512, layer_types=["mamba",
                                                           "attention"],
               num_attention_heads=8, num_key_value_heads=2,
               intermediate_size=64, shared_intermediate_size=64,
               mamba_n_heads=16, mamba_d_head=64, mamba_d_state=8,
               mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
               mamba_chunk_size=4, mamba_conv_bias=True, rms_norm_eps=1e-5,
               embedding_multiplier=1.0, residual_multiplier=1.0,
               attention_multiplier=0.125, logits_scaling=1.0,
               position_embedding_type="nope", tie_word_embeddings=True)
    said = {}
    for dtype, page_size in (("bfloat16", 16), ("float32", 16),
                             ("bfloat16", 4)):
        cfg["weights_dtype"] = dtype
        params = {k: mx.nd.NDArray(v, mx.cpu())
                  for k, v in ref.make_weights(cfg, 3).items()}
        eng = DecodeEngine(params, family=builder.family_spec(cfg),
                           ctx=mx.cpu(), max_seq_len=64, lane_buckets=(2,),
                           page_size=page_size, num_pages=9,
                           prefill_len_buckets=(16,), start=False,
                           warmup=False)
        plane = eng.pool.k_pools[0]
        assert plane.shape == (9, page_size, 2 * 64) and \
            str(plane.dtype) == dtype
        on_host = eng.snapshot()["paged_attention"]
        monkeypatch.setattr(eng, "_device",
                            types.SimpleNamespace(platform="tpu"))
        said[dtype, page_size] = (on_host, eng.snapshot()["paged_attention"])
    assert said == {("bfloat16", 16): ("xla", "pallas"),
                    ("float32", 16): ("xla", "xla"),
                    ("bfloat16", 4): ("xla", "xla")}


# a latent family whose row spans two lane tiles without filling them (128 +
# 64 values: 256 columns), at toy widths elsewhere
LATENT_CFG = dict(
    vocab_size=V, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=2, first_k_dense_replace=1,
    n_routed_experts=4, n_routed_experts_published=4, first_expert=0,
    n_shared_experts=1, num_attention_heads=8, num_key_value_heads=8,
    qk_nope_head_dim=8, qk_rope_head_dim=64, v_head_dim=8, q_lora_rank=16,
    kv_lora_rank=128, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, rope_theta=25600000, rms_norm_eps=1e-5,
    sandwich_norm=True, tie_word_embeddings=False, attention_bias=False,
    hidden_act="silu")


def _latent_engine(dtype, page_size):
    from perfbench.builders import latent_moe_lm as builder
    from perfbench.models import latent_moe_lm as ref

    cfg = dict(LATENT_CFG, weights_dtype=dtype)
    params = {k: mx.nd.NDArray(v, mx.cpu())
              for k, v in ref.make_weights(cfg, 3).items()}
    return DecodeEngine(params, family=builder.family_spec(cfg), ctx=mx.cpu(),
                        max_seq_len=64, lane_buckets=(2,),
                        page_size=page_size, num_pages=9,
                        prefill_len_buckets=(16,), start=False, warmup=False)


def test_engine_says_what_the_latent_family_runs_where(monkeypatch):
    """A family of latent layers (bfloat16 rows of 128 + 64 values, held in
    256 columns: whole lane tiles) says ``pallas-absorbed-live-pages`` for
    its decode step where its planes live on a TPU and
    ``xla-absorbed-gather`` on the host platform; float32 rows, and pages
    that are no whole sublane tiles, say the gather on both."""
    import types

    said = {}
    for dtype, page_size in (("bfloat16", 16), ("float32", 16),
                             ("bfloat16", 4)):
        eng = _latent_engine(dtype, page_size)
        (plane,) = eng.pool.planes()[:1]
        assert plane.shape == (9, page_size, 256) and str(plane.dtype) == dtype
        # the counter counts a token's values, not the zeros beside them
        assert eng._latent_token_bytes == 2 * 192 * plane.dtype.itemsize
        on_host = eng.snapshot()["latent_attention"]
        monkeypatch.setattr(eng, "_device",
                            types.SimpleNamespace(platform="tpu"))
        on_tpu = eng.snapshot()["latent_attention"]
        assert on_host["prefill"] == on_tpu["prefill"] == \
            "xla-expanded-head-blocks"
        assert eng.snapshot()["paged_attention"] == "xla"  # no K/V planes
        said[dtype, page_size] = (on_host["decode"], on_tpu["decode"])
    gather, kernel = "xla-absorbed-gather", "pallas-absorbed-live-pages"
    assert said == {("bfloat16", 16): (gather, kernel),
                    ("float32", 16): (gather, gather),
                    ("bfloat16", 4): (gather, gather)}


def test_zeros_beside_a_latent_rows_values_change_no_token(monkeypatch):
    """The plane holds a row of 128 + 64 values in 256 columns
    (``HybridLM.latent_row``): the prefill's slabs and the decode step's
    rows carry zeros there, and the transcript is the one of a plane as wide
    as the values."""
    from mxnet_tpu.models import HybridLM

    def transcript():
        eng = _latent_engine("float32", 16)
        eng.start()
        try:
            widths = {p.shape[-1] for p in eng.pool.planes()}
            return widths, eng.generate([3, 1, 4, 1, 5, 9, 2, 6], 12), [
                p.asnumpy() for p in eng.pool.planes()]
        finally:
            eng.stop()

    widths, tokens, planes = transcript()
    assert widths == {256}
    assert all(not p[..., 192:].any() and p[..., :192].any() for p in planes)
    monkeypatch.setattr(HybridLM, "latent_row",
                        lambda self: self.kv_rank + self.rope_dim)
    narrow_widths, narrow_tokens, _ = transcript()
    assert narrow_widths == {192} and narrow_tokens == tokens


# ---------------------------------------------------------------------------
# sliding-window attention: the sequence form and the lane form over a ring
# ---------------------------------------------------------------------------

def _banded_softmax(q, k, v, scale, window):
    """The oracle: a dense masked softmax over the whole square, float64."""
    b, s, heads, hd = q.shape
    group = heads // k.shape[2]
    k, v = (np.repeat(x.astype(np.float64), group, axis=2) for x in (k, v))
    sc = np.einsum("bqhd,bthd->bhqt", q.astype(np.float64), k) * scale
    ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
    mask = ahead >= 0
    if window:
        mask &= ahead < window
    sc = np.where(mask, sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqt,bthd->bqhd", p, v)


@pytest.mark.parametrize("heads,kv_heads", [(6, 2), (8, 2), (4, 4)])
@pytest.mark.parametrize("window,block", [(8, 4), (8, 16), (5, 8), (0, 8),
                                          (64, 8)])
def test_blocked_attention_is_the_dense_masked_softmax(monkeypatch, heads,
                                                       kv_heads, window,
                                                       block):
    """Query blocks against the keys the mask lets matter (a band from
    ``window - 1`` before a block's first row; with no window everything up
    to its last row) against the whole square: blocks smaller and larger
    than the window, a window wider than the sequence, 6 and 8 query heads
    over 2 K/V heads (48 and 64 over 8 in the cell)."""
    from mxnet_tpu.ops import paged

    monkeypatch.setattr(paged, "_QUERY_BLOCK", block)
    rng = np.random.default_rng(0)
    s, hd = 37, 8
    q = rng.standard_normal((2, s, heads, hd)).astype(np.float32)
    k = rng.standard_normal((2, s, kv_heads, hd)).astype(np.float32)
    v = rng.standard_normal((2, s, kv_heads, hd)).astype(np.float32)
    got = np.asarray(paged.blocked_attention(q, k, v, scale=0.3,
                                             window=window))
    np.testing.assert_allclose(got, _banded_softmax(q, k, v, 0.3, window),
                               atol=2e-5, rtol=0)


# (sequence, block): a sequence of 2 and of 5 blocks
@pytest.mark.parametrize("s,block", [(64, 32), (160, 32)])
# no window, one block, less than a block, more than the sequence
@pytest.mark.parametrize("window", [0, 32, 20, 500])
@pytest.mark.parametrize("heads,kv_heads", [(6, 1), (8, 1), (2, 2)])
def test_sequence_kernel_is_the_blocked_attention(monkeypatch, heads,
                                                  kv_heads, window, s, block):
    """The flash forward (interpret mode) with a group and a band against
    ``blocked_attention``, its oracle, and the whole square: groups of 6, 8
    and 1, with no window, one of a block, of less than a block and of more
    than the sequence, over 2 and 5 blocks.  Under a band the grid's
    innermost dimension is the most blocks a q tile's band touches, not the
    sequence's."""
    from mxnet_tpu.ops import attention as att

    monkeypatch.setattr(paged, "_QUERY_BLOCK", block)
    rng = np.random.default_rng(4)
    q = rng.standard_normal((2, s, heads, HD)).astype(np.float32)
    k = rng.standard_normal((2, s, kv_heads, HD)).astype(np.float32)
    v = rng.standard_normal((2, s, kv_heads, HD)).astype(np.float32)

    def kernel(q, k, v):
        return att._flash_forward(q, k, v, True, 0.3, block, block, True,
                                  window=window)[0]

    got = np.asarray(kernel(q, k, v))
    np.testing.assert_allclose(got, np.asarray(paged.blocked_attention(
        q, k, v, scale=0.3, window=window)), atol=2e-5, rtol=0)
    np.testing.assert_allclose(got, _banded_softmax(q, k, v, 0.3, window),
                               atol=2e-5, rtol=0)
    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    yield from pallas_calls(getattr(sub.jaxpr, "jaxpr",
                                                    sub.jaxpr))

    (call,) = pallas_calls(jax.make_jaxpr(kernel)(q, k, v).jaxpr)
    blocks = s // block
    steps = {0: blocks, 32: 2, 20: 2, 500: blocks}[window]
    assert call.params["grid_mapping"].grid == (2 * heads, blocks, steps)


@pytest.mark.parametrize("lengths", [(700, 1024), (1, 513)])
def test_window_op_through_the_kernel_with_padded_prompts(monkeypatch,
                                                          lengths):
    """``_contrib_WindowAttention`` over a right-padded batch of two, a
    bucket of 1,024 under a window of 512, with the kernel chosen (as on a
    TPU; interpret mode here): the real rows are the banded softmax's,
    whatever the padding holds, and the rings are bit-equal to the XLA
    form's (they come from ``window_rings`` by ``length``, untouched)."""
    rng = np.random.default_rng(5)
    s, window, kv, hd = 1024, 512, 1, 8
    q = rng.standard_normal((2, s, 6, hd)).astype(np.float32)
    k = rng.standard_normal((2, s, kv, hd)).astype(np.float32)
    v = rng.standard_normal((2, s, kv, hd)).astype(np.float32)
    args = [mx.nd.array(x) for x in (q, k, v, np.asarray(lengths,
                                                         np.float32))]

    def run():
        return [x.asnumpy() for x in mx.nd._contrib_WindowAttention(
            *args, window=window, scale=0.5, use_length=True)]

    want = run()
    seen = []
    real = paged._kernel_sequence
    monkeypatch.setattr(paged, "sequence_formulation",
                        lambda *a, **kw: "pallas")
    monkeypatch.setattr(paged, "_kernel_sequence",
                        lambda *a, **kw: seen.append(kw) or real(*a, **kw))
    out, k_ring, v_ring = run()
    assert seen == [dict(scale=0.5, window=window)]
    np.testing.assert_array_equal(k_ring, want[1])
    np.testing.assert_array_equal(v_ring, want[2])
    dense = _banded_softmax(q, k, v, 0.5, window)
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(out[b, :n], dense[b, :n], atol=2e-5,
                                   rtol=0)
        np.testing.assert_allclose(out[b, :n], want[0][b, :n], atol=2e-5,
                                   rtol=0)


@pytest.mark.parametrize("platform,L,heads,kv_heads,head_dim,dtype,train,"
                         "want", [
    ("tpu", 4096, 64, 8, 128, jnp.bfloat16, False, "pallas"),  # sliding
    ("tpu", 4096, 48, 8, 128, jnp.bfloat16, False, "pallas"),  # full
    ("tpu", 1024, 48, 8, 128, jnp.bfloat16, False, "pallas"),  # two blocks
    ("tpu", 2048, 32, 8, 128, jnp.bfloat16, False, "pallas"),  # g4hsmall
    ("tpu", 1024, 16, 16, 256, jnp.bfloat16, False, "pallas"),
    ("cpu", 4096, 64, 8, 128, jnp.bfloat16, False, "xla"),   # the platform
    ("gpu", 4096, 64, 8, 128, jnp.bfloat16, False, "xla"),
    ("tpu", 4096, 64, 8, 128, np.float32, False, "xla"),     # the dtype
    ("tpu", 4096, 64, 8, 64, jnp.bfloat16, False, "xla"),    # half a tile
    ("tpu", 704, 64, 8, 128, jnp.bfloat16, False, "xla"),    # no whole tiles
    ("tpu", 5120, 48, 8, 128, jnp.bfloat16, False, "pallas"),
    ("tpu", 512, 64, 8, 128, jnp.bfloat16, False, "xla"),    # one block
    ("tpu", 4096, 64, 8, 128, jnp.bfloat16, True, "xla"),    # a gradient
])
def test_sequence_formulation_is_read_off_the_operands(platform, L, heads,
                                                       kv_heads, head_dim,
                                                       dtype, train, want):
    assert paged.sequence_formulation(platform, L, heads, kv_heads, head_dim,
                                      dtype, train) == want


def test_the_sequence_ops_pick_their_formulation_where_the_operands_live():
    """``_contrib_DenseAttention`` (grouped, causal, two blocks) and
    ``_contrib_WindowAttention`` traced for a TPU lower ONE ``flash_fwd``
    kernel each; on this CPU, and for a graph that is being differentiated,
    they lower none, and that graph still gets its gradient (the XLA
    form's)."""
    from mxnet_tpu.ops.interpret import bind
    from mxnet_tpu.ops.registry import OpContext

    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 1024, 2, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 1024, 1, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 1024, 1, 128)), jnp.bfloat16)
    ops = {
        "dense": lambda opctx: lambda q, k, v: paged._dense_attention(
            opctx, dict(causal=True, scale=0.1), q, k, v),
        "window": lambda opctx: lambda q, k, v: paged._window_attention(
            opctx, dict(window=512, scale=0.1), q, k, v)[0]}
    for name, op in ops.items():
        here = str(jax.make_jaxpr(op(None))(q, k, v))
        there = str(jax.make_jaxpr(bind(op(OpContext(False)), "tpu"))(
            q, k, v))
        train = bind(op(OpContext(True)), "tpu")
        assert "pallas_call" not in here, name
        assert there.count("pallas_call") == 1 and "flash_fwd" in there, name
        assert "pallas_call" not in str(jax.make_jaxpr(train)(q, k, v)), name
        f32 = jnp.float32
        got = jax.grad(lambda q: train(q, k, v).astype(f32).sum())(q)
        want = jax.grad(lambda q: jnp.asarray(paged.blocked_attention(
            q, k, v, scale=0.1, window=512 if name == "window" else 0),
            f32).sum())(q)
        assert float(jnp.abs(got.astype(f32)).max()) > 0
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_engine_says_what_its_sequence_attention_runs_where(monkeypatch):
    """A hybrid family with grouped-query attention (4 query heads of 128
    over 2) says ``pallas`` for its prefills' sequence attention where it
    lives on a TPU, in bfloat16, with a longest bucket of two blocks, and
    ``xla`` on the host platform, for float32 weights and for a bucket of
    one block; a family whose every query head has a K/V head of its own
    says nothing."""
    import types

    from perfbench.builders import hybrid_lm as builder
    from perfbench.models import hybrid_lm as ref

    cfg = dict(vocab_size=V, hidden_size=512, layer_types=["mamba",
                                                           "attention"],
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=64, shared_intermediate_size=64,
               mamba_n_heads=16, mamba_d_head=64, mamba_d_state=8,
               mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
               mamba_chunk_size=4, mamba_conv_bias=True, rms_norm_eps=1e-5,
               embedding_multiplier=1.0, residual_multiplier=1.0,
               attention_multiplier=0.125, logits_scaling=1.0,
               position_embedding_type="nope", tie_word_embeddings=True)
    said = {}
    for dtype, bucket in (("bfloat16", 1024), ("float32", 1024),
                          ("bfloat16", 512)):
        cfg["weights_dtype"] = dtype
        params = {k: mx.nd.NDArray(v, mx.cpu())
                  for k, v in ref.make_weights(cfg, 3).items()}
        eng = DecodeEngine(params, family=builder.family_spec(cfg),
                           ctx=mx.cpu(), max_seq_len=1040, lane_buckets=(2,),
                           page_size=16, num_pages=9,
                           prefill_len_buckets=(bucket,),
                           prefill_batch_buckets=(1,), start=False,
                           warmup=False)
        on_host = eng.snapshot()["sequence_attention"]
        monkeypatch.setattr(eng, "_device",
                            types.SimpleNamespace(platform="tpu"))
        said[dtype, bucket] = (on_host, eng.snapshot()["sequence_attention"])
    assert said == {("bfloat16", 1024): ("xla", "pallas"),
                    ("float32", 1024): ("xla", "xla"),
                    ("bfloat16", 512): ("xla", "xla")}
    eng = DecodeEngine(_lm_params(), vocab_size=V, num_layers=LAYERS,
                       num_heads=HEADS, hidden=HEADS * HD, max_seq_len=S,
                       lane_buckets=(1,), page_size=PAGE, num_pages=24,
                       prefill_len_buckets=(8,), start=False, warmup=False)
    assert "sequence_attention" not in eng.snapshot()


def test_dense_attention_op_blocks_a_long_grouped_sequence(monkeypatch):
    """``_contrib_DenseAttention`` over more than one block of queries
    (grouped-query, causal) is the blocked form; a short one stays as it
    was."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import paged

    rng = np.random.default_rng(1)
    q = rng.standard_normal((1, 40, 6, 8)).astype(np.float32)
    k = rng.standard_normal((1, 40, 2, 8)).astype(np.float32)
    v = rng.standard_normal((1, 40, 2, 8)).astype(np.float32)
    want = _banded_softmax(q, k, v, 0.25, 0)
    seen = []
    real = paged.blocked_attention
    monkeypatch.setattr(paged, "blocked_attention",
                        lambda *a, **kw: seen.append(1) or real(*a, **kw))
    for block, blocked in ((512, False), (16, True)):
        monkeypatch.setattr(paged, "_QUERY_BLOCK", block)
        del seen[:]
        got = mx.nd._contrib_DenseAttention(
            mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), causal=True,
            scale=0.25).asnumpy()
        assert bool(seen) is blocked
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("lengths", [(3, 8), (9, 16), (17, 23), (24, 1)])
def test_window_op_returns_each_prompts_ring_after_its_last_real_token(
        lengths):
    """The sequence op over a bucket of 24 with two prompts' true lengths:
    entry ``j`` of a ring is the latest real token ``t`` with ``t % 8 == j``
    (zeros where none has landed), whatever the padding holds."""
    import mxnet_tpu as mx

    rng = np.random.default_rng(2)
    s, window, kv, hd = 24, 8, 2, 4
    q = rng.standard_normal((2, s, 6, hd)).astype(np.float32)
    k = rng.standard_normal((2, s, kv, hd)).astype(np.float32)
    v = rng.standard_normal((2, s, kv, hd)).astype(np.float32)
    out, k_ring, v_ring = (x.asnumpy() for x in mx.nd._contrib_WindowAttention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
        mx.nd.array(np.asarray(lengths, np.float32)), window=window,
        scale=0.5, use_length=True))
    np.testing.assert_allclose(out, _banded_softmax(q, k, v, 0.5, window),
                               atol=2e-5, rtol=0)
    assert k_ring.shape == v_ring.shape == (2, window, kv * hd)
    for b, n in enumerate(lengths):
        for j in range(window):
            live = [t for t in range(n) if t % window == j]
            for ring, rows in ((k_ring, k), (v_ring, v)):
                want = rows[b, live[-1]].reshape(-1) if live \
                    else np.zeros(kv * hd, np.float32)
                np.testing.assert_array_equal(ring[b, j], want)
    # without ``length`` the whole sequence is the prompt
    _, whole, _ = mx.nd._contrib_WindowAttention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), window=window,
        scale=0.5)
    np.testing.assert_array_equal(whole.asnumpy()[0],
                                  k[0, 16:24].reshape(window, -1))


@pytest.mark.parametrize("heads", [6, 8])
def test_window_step_over_a_ring_is_the_sequence_form_token_by_token(heads):
    """The lane form fed 27 tokens one at a time (a window of 8: the ring
    wraps three times) against ONE call of the sequence form; a second lane
    idles on the scratch slot, and the slots hold NaN before a token lands:
    an entry that is not live reaches nothing."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged

    rng = np.random.default_rng(3)
    n, window, kv, hd = 27, 8, 2, 4
    q = rng.standard_normal((1, n, heads, hd)).astype(np.float32)
    k = rng.standard_normal((1, n, kv, hd)).astype(np.float32)
    v = rng.standard_normal((1, n, kv, hd)).astype(np.float32)
    want = np.asarray(paged.blocked_attention(q, k, v, scale=0.5,
                                              window=window))[0]
    k_ring = jnp.full((3, window, kv * hd), np.nan, jnp.float32)
    v_ring = jnp.full((3, window, kv * hd), np.nan, jnp.float32)
    slot = np.array([2, 0], np.int32)   # lane 1 idles on the scratch slot
    for t in range(n):
        out, k_ring, v_ring = paged.window_step(
            np.stack([q[0, t], q[0, 0]]), np.stack([k[0, t], k[0, 0]]),
            np.stack([v[0, t], v[0, 0]]), k_ring, v_ring, slot,
            np.array([t, 0], np.int32), 0.5)
        np.testing.assert_allclose(np.asarray(out)[0], want[t], atol=2e-5,
                                   rtol=0)
        # the ring after token t is what the sequence form returns for a
        # prompt of t + 1 tokens
        rk, rv = paged.window_rings(k, v, np.array([t + 1], np.int32), window)
        live = np.arange(window) <= t
        np.testing.assert_array_equal(np.asarray(k_ring)[2][live],
                                      np.asarray(rk)[0][live])
        np.testing.assert_array_equal(np.asarray(v_ring)[2][live],
                                      np.asarray(rv)[0][live])
    assert np.isnan(np.asarray(k_ring)[1]).all()  # nobody's slot: untouched
