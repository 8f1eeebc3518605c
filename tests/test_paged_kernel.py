"""``_contrib_PagedAttention``'s two formulations: the Pallas kernel that
walks each lane's live pages (interpret mode here) against the XLA gather
over the whole table, which stays as its oracle; which of the two the op
picks where; and what the engine says about it (``snapshot()``, the
``gen:step`` span's ``pages``).
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

# name -> (page_size, max_pages, positions; None is an inactive lane,
#          page ids handed out: "shuffled", "descending" or "ascending")
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
}


def _operands(page_size, max_pages, positions, order, seed=0):
    rng = np.random.RandomState(seed)
    lanes = len(positions)
    num_pages = 1 + lanes * max_pages  # page 0 is the scratch page
    q, k_new, v_new = (jnp.asarray(rng.randn(lanes, HEADS, HD), jnp.float32)
                       for _ in range(3))
    k_pool, v_pool = (jnp.asarray(rng.randn(num_pages, page_size, HEADS, HD),
                                  jnp.float32) for _ in range(2))
    free = list(range(1, num_pages))  # popped from the end
    if order == "shuffled":
        rng.shuffle(free)
    elif order == "ascending":
        free.reverse()
    table = np.zeros((lanes, max_pages), np.int32)
    for lane, at in enumerate(positions):
        if at is not None:
            held = at // page_size + 1
            table[lane, :held] = [free.pop() for _ in range(held)]
    at = jnp.asarray([p or 0 for p in positions], jnp.int32)
    return q, k_new, v_new, k_pool, v_pool, jnp.asarray(table), at


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_the_gather(name):
    page_size, max_pages, positions, order = CASES[name]
    ops = _operands(page_size, max_pages, positions, order)
    scale = 1.0 / np.sqrt(HD)
    want, want_k, want_v = paged._gather_decode(*ops, scale)
    got, got_k, got_v = paged._kernel_decode(*ops, scale, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
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
                    got_p[page, pos % page_size], new[lane])
    if sum(p is None for p in positions) == 1:
        np.testing.assert_array_equal(got_k[0], want_k[0])


def test_history_beyond_the_position_is_never_read():
    """Slots at and after a lane's position may hold anything (a retired
    sequence's tokens, the slot this step writes): neither formulation lets
    them into the softmax, and the kernel does not even fetch the pages
    after the position's."""
    page_size, max_pages, positions = 4, 8, [9, 14]
    ops = list(_operands(page_size, max_pages, positions, "shuffled"))
    scale = 1.0 / np.sqrt(HD)
    want = paged._kernel_decode(*ops, scale, interpret=True)[0]
    table = np.asarray(ops[5])
    for plane in (3, 4):
        pool = np.asarray(ops[plane]).copy()
        for lane, pos in enumerate(positions):
            page = table[lane, pos // page_size]
            pool[page, pos % page_size:] = 1e4
        ops[plane] = jnp.asarray(pool)
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
    ("tpu", 16, 128, jnp.bfloat16, "xla"),   # the kernel is float32's
])
def test_formulation_follows_where_the_operands_live(platform, heads,
                                                     head_dim, dtype, want):
    assert paged.decode_formulation(platform, heads, head_dim, dtype) == want


def _pallas_calls(jaxpr):
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                names += _pallas_calls(inner)
    return names


@pytest.mark.parametrize("platform,want", [("cpu", []),
                                           ("tpu", ["paged_decode"])])
def test_op_picks_by_the_executors_scope(platform, want):
    """Traced under ``bound_to`` (what an Executor enters around every
    program it traces) the op runs the kernel on a tpu context only: no
    attribute, no environment variable.  Traced, not run."""
    from mxnet_tpu.ops.registry import get_op

    heads, hd, page_size = 8, 128, 8
    shapes = [(2, heads, hd)] * 3 + [(5, page_size, heads, hd)] * 2 + \
        [(2, 4), (2,)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    op = get_op("_contrib_PagedAttention")

    def step(*a):
        with bound_to(platform):
            return op.fn(None, {"page_size": page_size}, *a)

    assert _pallas_calls(jax.make_jaxpr(step)(*args).jaxpr) == want


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
        prompt, new = [3, 1, 4, 1, 5, 9], 7
        assert len(eng.generate(prompt, new)) == new
    finally:
        eng.stop()
    steps = [args for name, args in spans if name == "gen:step"]
    # the prefill emits the first token; step k feeds position len + k
    assert [s["lanes"] for s in steps] == [1] * (new - 1)
    assert [s["pages"] for s in steps] == \
        [(len(prompt) + k) // PAGE + 1 for k in range(new - 1)]
