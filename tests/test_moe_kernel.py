"""The grouped-product kernel ``moe_grouped`` (ops/moe.py
``_kernel_grouped``) in Pallas interpret mode against its oracle, the
``"ragged-dense"`` formulation (``jax.lax.ragged_dot`` twice, expanded by XLA
on the host), through the whole of ``routed_experts`` (sort, products, gate,
combine) at small tiled widths: hidden 256 in two weight tiles of 128 rows,
expert width 256 in two (so every case crosses a tile of both leaves), 8
experts, 2 picks a row.  The row tiles are forced small (16 pairs, chunks of
16) so that a few dozen pairs need several tiles, as a prefill's 2,048 do at
the chip's 512.

Tolerances.  float32 operands: the kernel sums the same products tile by
tile, 2e-5 on outputs of order 1.  bfloat16 operands, float32 sums: the
gated product is rounded to bfloat16 once in both, from sums that differ in
their last float32 bits, so a few of its values land one bfloat16 step apart
(0.4 %) and the second product averages them: 2e-2 on outputs of order 1
(the runs read under 8e-3).
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe

H, F, E, K = 256, 256, 8, 2
TOL = {"float32": 2e-5, "bfloat16": 2e-2}
SMALL = dict(rows=(16, 16), depths=(128, 128))


def _kernel(**tiles):
    return functools.partial(moe._kernel_grouped, interpret=True, **tiles)


def _layer(seed, rows, dtype, experts=E):
    r = np.random.RandomState(seed)
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    return dict(g=cast(r.randn(rows, H)),
                w=jnp.asarray(r.rand(rows, K), jnp.float32),
                w13=cast(r.randn(experts, H, 2 * F) / np.sqrt(H)),
                w2=cast(r.randn(experts, F, H) / np.sqrt(F)))


def _picks(seed, rows, among):
    """``rows`` x K distinct experts a row out of ``among``."""
    r = np.random.RandomState(seed)
    return np.stack([r.permutation(among)[:K] for _ in range(rows)]) \
        .astype(np.int32)


def _held(**tiles):
    return functools.partial(moe._kernel_held, interpret=True, **tiles)


def _check(a, ids, dtype, first=0, held=False, **tiles):
    """``routed_experts`` through the kernel (``held``: on the path that
    moves the held pairs' rows alone, ``_kernel_held``) against the
    ``"ragged-dense"`` formulation."""
    ids = jnp.asarray(ids)
    want = moe.routed_experts(a["g"], ids, a["w"], a["w13"], a["w2"],
                              first_expert=first)
    path = dict(held=_held(**tiles)) if held else \
        dict(grouped=_kernel(**tiles))
    got = moe.routed_experts(a["g"], ids, a["w"], a["w13"], a["w2"],
                             first_expert=first, **path)
    assert got.dtype == want.dtype == a["g"].dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    return np.asarray(got, np.float32)


LOADS = {
    # 24 rows x 2 picks over experts 0-7 in turn: 6 pairs each
    "even": lambda: np.arange(48).reshape(24, 2) % E,
    "idle_first": lambda: 1 + _picks(1, 24, E - 1),
    "idle_last": lambda: _picks(2, 24, E - 1),
    "idle_middle": lambda: np.where(_picks(3, 24, E - 1) >= 3, 1, 0)
    + _picks(3, 24, E - 1),
    "idle_all_but_two": lambda: np.array([[2, 5]] * 24),
    # every pair but one row's second pick to expert 4: a group of 47 pairs
    # over three row tiles of 16
    "one_takes_all": lambda: np.array([[4, 4]] * 23 + [[4, 6]]),
    # groups of 10: each crosses an edge of the 16-pair row tiles
    "crosses_tile_edges": lambda: np.repeat(np.arange(4), 10)
    .reshape(20, 2),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_kernel_is_the_dense_formulation(load, dtype):
    ids = LOADS[load]().astype(np.int32)
    got = _check(_layer(20, ids.shape[0], dtype), ids, dtype, **SMALL)
    assert np.abs(got).max() > 0.1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,tiles", [
    (8, {}),                     # 16 pairs: one tile, one chunk, the default
    (8, SMALL),                  # the same as a single forced tile
    (11, {}),                    # 22 pairs: padded to a tile of 32
    (32, {}),                    # 64 pairs, a lane step's: one tile, a mask
    (100, dict(depths=(128, 128))),   # 200 pairs: four chunks of 64, a tile
    (100, dict(rows=(64, 32), depths=(128, 128))),  # four tiles
    (37, dict(rows=(32, 16), depths=(256, 128))),   # one w13 tile
])
def test_one_tile_and_several(rows, tiles, dtype):
    _check(_layer(21, rows, dtype), _picks(21, rows, E), dtype, **tiles)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_that_are_not_live_belong_to_no_group(dtype):
    """Rows the router was told are not live pick expert ``E`` (weight 0):
    their pairs sort behind every group.  Inf and NaN in them reach no
    other row, and they read exact zeros."""
    a = _layer(22, 24, dtype)
    live = np.arange(24) % 3 != 1
    g = np.array(a["g"], np.float32)
    g[~live] = np.where(np.arange(H) % 2, np.inf, np.nan)
    a["g"] = jnp.asarray(g, a["g"].dtype)
    ids = np.where(live[:, None], _picks(22, 24, E), E).astype(np.int32)
    a["w"] = jnp.where(live[:, None], a["w"], 0.0)
    got = _check(a, ids, dtype, **SMALL)
    assert (got[~live] == 0).all() and np.isfinite(got[live]).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("first,held", [(2, 3), (5, 3), (0, 1)])
def test_a_share_runs_the_kernel_over_its_own_leaves(first, held, dtype):
    """A share that holds experts ``first .. first + held - 1`` of the 8
    the rows were routed over: the others' pairs belong to no group."""
    a = _layer(23, 24, dtype)
    a["w13"], a["w2"] = (a[k][first:first + held] for k in ("w13", "w2"))
    got = _check(a, _picks(23, 24, E), dtype, first=first, **SMALL)
    assert np.abs(got).max() > 0.1


@pytest.mark.parametrize("idle", [[0], [7], [3], [0, 1, 6, 7], [2, 4, 5]])
def test_an_idle_experts_weights_reach_no_output(idle):
    """Both leaves of the experts no row picked are NaN throughout: were one
    of their bytes multiplied into an output (even by a zero) it would show.
    On the chip an expert without a group is in no visit of the kernel's
    work list, so no block index names it and nothing of it is fetched
    (tests/test_chip_compile.py holds the compiled form)."""
    a = _layer(24, 24, "float32")
    busy = np.array([e for e in range(E) if e not in idle])
    ids = busy[_picks(24, 24, len(busy))].astype(np.int32)
    clean = _check(a, ids, "float32", **SMALL)
    poison = jnp.asarray(np.isin(np.arange(E), idle))[:, None, None]
    a["w13"], a["w2"] = (jnp.where(poison, jnp.nan, a[k])
                         for k in ("w13", "w2"))
    got = np.asarray(moe.routed_experts(
        a["g"], jnp.asarray(ids), a["w"], a["w13"], a["w2"],
        grouped=_kernel(**SMALL)))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("first,held", [(0, 8), (2, 3), (5, 3), (0, 1)])
@pytest.mark.parametrize("load", ["even", "one_takes_all",
                                  "crosses_tile_edges", "idle_middle"])
def test_the_held_path_is_the_dense_formulation(load, first, held, dtype):
    """The path that moves the held pairs' rows alone (the gather loop into
    the kernel's layout, ``moe_grouped``, ``moe_combine``: all three in
    interpret mode) for the whole layer and for shares of it: the live row
    tiles are those the held pairs fill, from none of the three 16-pair
    tiles to all of them."""
    ids = LOADS[load]().astype(np.int32)
    a = _layer(26, ids.shape[0], dtype)
    a["w13"], a["w2"] = (a[k][first:first + held] for k in ("w13", "w2"))
    got = _check(a, ids, dtype, first=first, held=True, cols=128, **SMALL)
    here = (ids >= first) & (ids < first + held)
    assert (np.abs(got).max() > 0.1) == bool(here.any())
    assert (got[~here.any(1)] == 0).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_held_path_leaves_rows_that_are_not_live(dtype):
    """As ``test_rows_that_are_not_live_belong_to_no_group``, on the held
    path: the last live tile holds pairs of rows that are not live (they
    sort right behind the held ones) and their inf and NaN stay there."""
    a = _layer(22, 24, dtype)
    live = np.arange(24) % 3 != 1
    g = np.array(a["g"], np.float32)
    g[~live] = np.where(np.arange(H) % 2, np.inf, np.nan)
    a["g"] = jnp.asarray(g, a["g"].dtype)
    ids = np.where(live[:, None], _picks(22, 24, E), E).astype(np.int32)
    a["w"] = jnp.where(live[:, None], a["w"], 0.0)
    got = _check(a, ids, dtype, held=True, **SMALL)
    assert (got[~live] == 0).all() and np.isfinite(got[live]).all()


@pytest.mark.parametrize("total", [0, 1, 16, 23, 48])
def test_the_combine_reads_the_live_tiles_alone(total):
    """``moe_combine`` alone: 48 sorted pairs in three tiles of 16, the
    first ``total`` held; what lies past them in the kernel's output is NaN
    throughout (a tile past the live ones is memory nobody wrote; a pair
    past the held ones in the last live tile is not looked at either); the
    sums in two blocks of 128 columns."""
    r = np.random.RandomState(total)
    y = r.randn(48, H).astype(np.float32)
    token = r.randint(0, 10, 48).astype(np.int32)
    weight = r.rand(48).astype(np.float32)
    want = np.zeros((10, H), np.float32)
    for at in range(total):
        want[token[at]] += y[at] * weight[at]
    y[total:] = np.nan
    got = moe._combine(jnp.asarray(y), jnp.asarray(token),
                       jnp.asarray(weight),
                       jnp.asarray([total], jnp.int32), 10, jnp.float32, 16,
                       128, True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("formulation,held,experts,pairs,want", [
    ("pallas", 36, 72, 20480, "held"),   # the Granite-small cell's prefills
    ("pallas", 36, 72, 5120, "held"),
    ("pallas", 16, 256, 16384, "held"),  # the latent cell's
    ("pallas", 16, 256, 19456, "held"),  # and its scoring program
    ("pallas", 1, 2, 513, "held"),
    ("pallas", 36, 72, 512, "all"),      # one row tile: nothing to leave out
    ("pallas", 36, 72, 160, "all"),      # the three cells' lane steps
    ("pallas", 16, 256, 128, "all"),
    ("pallas", 32, 32, 64, "all"),
    ("pallas", 32, 32, 2048, "all"),     # every expert held: LFM2's prefill
    ("ragged", 36, 72, 20480, "all"),    # the XLA formulations
    ("ragged-dense", 16, 256, 16384, "all")])
def test_experts_path_is_read_off_the_share_and_the_pairs(
        formulation, held, experts, pairs, want):
    assert moe.experts_path(formulation, held, experts, pairs) == want


def test_a_gradient_goes_through_the_xla_formulation():
    """The kernel is a forward pass; differentiated, the op's gradients are
    those of the ``ragged_dot`` formulation of the same products (rows, both
    leaves and the router's weights), so a graph that trains keeps working
    where the kernel engages."""
    import jax

    a = _layer(25, 24, "float32")
    ids = jnp.asarray(_picks(25, 24, E - 1))  # expert 7 idle: zero gradient

    def loss(grouped):
        def f(g, w, w13, w2):
            y = moe.routed_experts(g, ids, w, w13, w2, grouped=grouped)
            return jnp.sum(jnp.sin(y))
        return jax.grad(f, argnums=(0, 1, 2, 3))(a["g"], a["w"], a["w13"],
                                                 a["w2"])

    want, got = loss(moe._ragged_grouped), loss(_kernel(**SMALL))
    for x, y in zip(got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5)
    assert float(jnp.abs(got[2][:7]).max()) > 1e-3
    assert float(jnp.abs(got[2][7]).max()) == 0.0

    # the held path's loop has no reverse mode: the same gradients
    def held(g, w, w13, w2):
        y = moe.routed_experts(g, ids, w, w13, w2, held=_held(**SMALL))
        return jnp.sum(jnp.sin(y))

    got = jax.grad(held, argnums=(0, 1, 2, 3))(a["g"], a["w"], a["w13"],
                                               a["w2"])
    for x, y in zip(got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5,
                                   rtol=2e-5)


def test_the_work_list_visits_each_group_once_a_row_tile():
    """Groups of 10, 0, 22, 0, 16 pairs over row tiles of 16: (expert, tile)
    visits (0, 0), (2, 0), (2, 1), (4, 2), the three left of the ``tiles +
    held - 1`` repeating the last one."""
    offs, ve, vt, nv = moe._visits(jnp.asarray([10, 0, 22, 0, 16], jnp.int32),
                                   3, 16)
    assert [int(x) for x in offs] == [0, 10, 10, 32, 32, 48]
    assert int(nv[0]) == 4
    assert [int(x) for x in ve] == [0, 2, 2, 4, 4, 4, 4]
    assert [int(x) for x in vt] == [0, 0, 1, 2, 2, 2, 2]
    # no pair at all: no real visit, every index in range
    offs, ve, vt, nv = moe._visits(jnp.zeros((5,), jnp.int32), 3, 16)
    assert int(nv[0]) == 0 and int(vt.max()) == 0 and int(ve.max()) <= 4


@pytest.mark.parametrize("pairs,want", [(16, (16, 16)), (22, (32, 32)),
                                        (64, (64, 64)), (100, (128, 64)),
                                        (256, (256, 64)), (1000, (512, 64)),
                                        (2048, (512, 64))])
def test_the_row_tile_follows_the_pairs(pairs, want):
    assert moe._row_tiles(pairs) == want


def test_weight_tiles_are_whole_rows_of_a_leaf():
    """The cell's widths in bfloat16: 512 rows of ``w13[e]`` (2048 x 3584)
    and 896 of ``w2[e]`` (1792 x 2048), 3.67 MB each, six a visit."""
    assert moe._depth_tile(2048, 3584, 2) == 512
    assert moe._depth_tile(1792, 2048, 2) == 896
    assert moe._depth_tile(256, 512, 4) == 256
    assert moe._depth_tile(32, 32, 4) == 32  # no lane tile divides: whole
