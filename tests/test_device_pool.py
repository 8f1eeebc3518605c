"""The KV pool on the device: the planes are arrays of the engine's device
that the step's programs carry, a prefill's K/V is scattered into them by
one program, and nothing but ids, tables and logits crosses to the host.

CPU only, and counts only: what is bound where, which buffers die, which
tokens come out.  The plain reference is the dense recompute of the whole
prefix through the full-length prefill executable (as in
test_generation.py); the old host-side scatter is kept here as the
reference of the device one.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.generation import DecodeEngine, PagedKVPool
from mxnet_tpu.models.transformer import get_transformer_lm_prefill
from mxnet_tpu.serving.batcher import BucketedPredictor

V, LAYERS, HEADS, HID, S = 64, 2, 2, 32, 32
PAGE, PAGES = 4, 48
SPEC = dict(vocab_size=V, num_layers=LAYERS, num_heads=HEADS, hidden=HID,
            max_seq_len=S, lane_buckets=(1, 2, 4), page_size=PAGE,
            num_pages=PAGES, prefill_len_buckets=(8, 16, 32))
PLANE_BYTES = PAGES * PAGE * HEADS * (HID // HEADS) * 4


def _lm_params(seed=0, layers=LAYERS):
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=layers,
                                       num_heads=HEADS, hidden=HID,
                                       seq_len=S)
    shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(seed)
    return {name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
            for name, shp in zip(net.list_arguments(), shapes)
            if name not in ("data", "softmax_label")}


_PARAMS = _lm_params()


@pytest.fixture(scope="module")
def dense_decode():
    """Greedy decode by recomputing the whole prefix densely each token."""
    pred = mx.Predictor(get_transformer_lm_prefill(V, LAYERS, HEADS, HID,
                                                   seq_len=S, max_seq_len=S),
                        dict(_PARAMS), {"data": (1, S)})

    def decode(prompt, max_new):
        toks, buf = list(prompt), np.zeros((1, S), np.float32)
        for _ in range(max_new):
            buf[:] = 0
            buf[0, :len(toks)] = toks
            logits = pred.forward(data=buf)[0].asnumpy()
            toks.append(int(np.argmax(logits[0, len(toks) - 1])))
        return toks[len(prompt):]

    return decode


def _host_write_prefill(planes, pages, k, v, length, page_size):
    """The scatter as the host-side pool did it, one page at a time."""
    kp, vp = planes
    for start in range(0, length, page_size):
        n = min(page_size, length - start)
        kp[pages[start // page_size], :n] = k[start:start + n]
        vp[pages[start // page_size], :n] = v[start:start + n]


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lengths", [(3,), (4,), (5,), (7, 8), (9, 1, 12)],
                         ids=["before-boundary", "on-boundary",
                              "after-boundary", "batch-of-2", "batch-of-3"])
def test_device_scatter_equals_the_host_write(lengths):
    """Lengths that end before, on and after a page boundary, alone and in
    a padded batch: the planes hold what the host loop would have put
    there, and nothing outside the sequences' pages and scratch page 0
    changed."""
    rng = np.random.RandomState(sum(lengths))
    bucket, batch = 16, 4  # padded rows and padded batch entries
    pool = PagedKVPool(num_pages=16, page_size=PAGE, num_layers=2,
                       num_heads=2, head_dim=4)
    slabs = [rng.randn(batch, bucket, 2, 4).astype(np.float32)
             for _ in range(4)]  # k0, v0, k1, v1
    want = [np.zeros((16, PAGE, 2, 4), np.float32) for _ in range(4)]
    for b, n in enumerate(lengths):
        pages = pool.alloc(b, n)
        for layer in range(2):
            _host_write_prefill(want[2 * layer:2 * layer + 2], pages,
                                slabs[2 * layer][b], slabs[2 * layer + 1][b],
                                n, PAGE)
    pool.write_prefill(list(range(len(lengths))), slabs, list(lengths))
    for got, ref in zip(pool.planes(), want):
        # page 0 is scratch: the padding landed there
        assert np.array_equal(got.asnumpy()[1:], ref[1:])


def test_pool_planes_are_device_arrays_with_one_owner():
    pool = PagedKVPool(num_pages=8, page_size=PAGE, num_layers=2,
                       num_heads=2, head_dim=4, prefix_cache_pages=4)
    planes = pool.planes()
    assert [p is q for p, q in zip(planes, (pool.k_pools[0], pool.v_pools[0],
                                            pool.k_pools[1],
                                            pool.v_pools[1]))] == [True] * 4
    assert pool.device_bytes() == 4 * 8 * PAGE * 2 * 4 * 4
    assert pool.snapshot()["device_bytes"] == pool.device_bytes()
    before = [p._data for p in planes]
    pool.alloc("a", 4)
    pool.write_prefill(["a"], [np.ones((1, 8, 2, 4), np.float32)] * 4, [4])
    # donated: the old buffers are dead, the same NDArrays hold the new
    assert all(b.is_deleted() for b in before)
    assert pool.planes()[0] is planes[0]
    k, v = pool.read_page(1, pool._tables["a"][0])
    assert k.shape == (PAGE, 2, 4) and k.all() and v.all()
    before = [p._data for p in planes]
    pool.copy_page(pool._tables["a"][0], 7)
    assert all(b.is_deleted() for b in before)
    assert pool.read_page(0, 7)[0].all() and not pool.read_page(0, 6)[0].any()


def test_a_paged_plane_may_stand_alone():
    """A pool whose layers cache ONE row a token (a latent, no head axis)
    beside a layer with a K/V pair: alloc, extend, the prefill's scatter,
    copy-on-write and the prefix index go plane by plane."""
    planes = [("layer0_latent_pool", "paged", (6,), np.float32),
              ("layer1_k_pool", "paged", (2, 4), np.float32),
              ("layer1_v_pool", "paged", (2, 4), np.float32),
              ("layer2_latent_pool", "paged", (6,), np.float32)]
    pool = PagedKVPool(num_pages=8, page_size=PAGE, planes=planes,
                       prefix_cache_pages=4)
    assert pool.num_layers == 1 and pool.num_slots == 0
    assert len(pool.k_pools) == len(pool.v_pools) == 1
    assert pool.k_pools[0] is pool.planes()[1]
    assert len(pool.paged_planes()) == 4
    assert pool.device_bytes() == 8 * PAGE * (6 + 8 + 8 + 6) * 4
    pool.alloc("a", 6)
    assert len(pool.extend("a", 9)) == 3
    r = np.random.RandomState(0)
    slabs = [r.randn(1, 12, 6), r.randn(1, 12, 2, 4), r.randn(1, 12, 2, 4),
             r.randn(1, 12, 6)]
    slabs = [s.astype(np.float32) for s in slabs]
    pool.write_prefill(["a"], slabs, [9])
    pages = pool._tables["a"]
    for plane, slab in zip(pool.planes(), slabs):
        got = plane.asnumpy()[pages].reshape((3 * PAGE,) + slab.shape[2:])
        np.testing.assert_array_equal(got[:9], slab[0, :9])
    k, v = pool.read_page(0, pages[1])
    np.testing.assert_array_equal(k, slabs[1][0, PAGE:2 * PAGE])
    # the prefix index: "b" shares "a"'s two complete pages, and a write
    # into the second splits it in every plane, the lone ones too
    tokens = list(range(9))
    assert pool.register_prefix("a", tokens) == 2
    got, cached = pool.alloc_prefix("b", 9, tokens=tokens)
    assert cached == 8 and got[:2] == pages[:2]
    assert pool.ensure_writable("b", 7)
    own = pool._tables["b"][1]
    assert own != pages[1]
    for plane in pool.planes():
        host = plane.asnumpy()
        np.testing.assert_array_equal(host[own], host[pages[1]])
    pool.free("a")
    pool.free("b")
    assert pool.total_refcount() == 0


# ---------------------------------------------------------------------------
# the executor's carried arguments, through the engine's rigs
# ---------------------------------------------------------------------------

@pytest.fixture
def cold_engine():
    eng = DecodeEngine(_PARAMS, warmup=False, start=False, **SPEC)
    yield eng
    eng.stop()


def _step(eng, b):
    """One all-scratch step through lane bucket ``b``."""
    return eng._run_lanes(eng._decode[b], np.zeros((b,), np.float32),
                          np.zeros((b,), np.float32),
                          np.zeros((b, eng.max_pages), np.float32))


def test_every_lane_rig_binds_the_pools_own_planes(cold_engine):
    eng = cold_engine
    for b, pred in eng._decode.items():
        ex = pred._exec
        for i in range(LAYERS):
            assert ex.arg_dict["layer%d_k_pool" % i] is eng.pool.k_pools[i]
            assert ex.arg_dict["layer%d_v_pool" % i] is eng.pool.v_pools[i]
        assert ex._carried == {"layer0_k_pool": 1, "layer0_v_pool": 2,
                               "layer1_k_pool": 3, "layer1_v_pool": 4}
        assert ex._symbol.list_outputs()[1].endswith("k_pool_out")


def test_lane_buckets_alternate_over_one_donated_pool(cold_engine):
    """Several programs share the planes; each step kills the buffers it
    was given, and the next program — another bucket — finds live ones."""
    eng = cold_engine
    for b in (1, 2, 1, 4, 2):
        before = [p._data for p in eng.pool.planes()]
        logits = _step(eng, b)
        assert logits.shape == (b, V)
        assert all(x.is_deleted() for x in before)
        assert not any(p._data.is_deleted() for p in eng.pool.planes())
        # the outputs that are planes are the pool's NDArrays themselves
        outs = eng._decode[b].get_outputs()
        assert [o is p for o, p in zip(outs[1:], eng.pool.planes())] == \
            [True] * (2 * LAYERS)
        # every other rig still lists live arrays
        assert eng.devices()["decode"] == eng.devices()["pool"]
    assert eng.snapshot()["step_donated"] is True


def test_step_is_not_donated_where_executables_are_serialized(
        cold_engine, tmp_path, monkeypatch):
    """Under the framework's compile cache the program keeps its inputs
    (executor.py: a serialized executable must not alias them); the planes
    are carried all the same."""
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    eng = cold_engine
    before = [p._data for p in eng.pool.planes()]
    _step(eng, 2)
    assert eng.snapshot()["step_donated"] is False
    assert not any(x.is_deleted() for x in before)
    assert all(p._data is not x
               for p, x in zip(eng.pool.planes(), before))


def test_forward_without_carried_arguments_is_built_as_before():
    ex = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                               name="fc").simple_bind(mx.cpu(), data=(3, 4))
    ex.forward(is_train=False)
    assert ex.carry_donated is None and ex._carried == {}
    assert list(ex._jit_cache) == [("fwd", False, False)]
    assert len(ex._forward_args(None)) == 3
    with pytest.raises(mx.base.MXNetError):
        ex.set_carried({"nope": 0})
    with pytest.raises(mx.base.MXNetError):
        ex.set_carried({"data": 5})


def test_predictor_binds_a_given_input_and_keeps_it_over_reshape():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc") + mx.sym.Variable("state")
    state = mx.nd.ones((1, 2))
    params = {"fc_weight": mx.nd.ones((2, 4)), "fc_bias": mx.nd.zeros((2,)),
              "state": state}
    pred = mx.Predictor(net, params, {"data": (3, 4), "state": (1, 2)})
    assert pred._exec.arg_dict["state"] is state
    small = pred.reshape({"data": (1, 4)})
    assert small._exec.arg_dict["state"] is state
    assert small._exec.arg_dict["data"] is not pred._exec.arg_dict["data"]
    out = small.forward(data=np.ones((1, 4), np.float32))[0].asnumpy()
    assert np.array_equal(out, [[5.0, 5.0]])


def test_run_batch_leaves_the_outputs_on_the_device():
    sym = get_transformer_lm_prefill(V, LAYERS, HEADS, HID, seq_len=8,
                                     max_seq_len=S)
    bp = BucketedPredictor(sym, dict(_PARAMS), {"data": (8,)}, (1, 2))
    items = [{"data": np.arange(8, dtype=np.float32)}]
    b, outs = bp.run_batch(items)
    assert b == 1 and outs[0].shape == (1, 8, V)
    assert all(isinstance(o, mx.nd.NDArray) for o in outs)
    _, per_item = bp.forward_batch(items)
    assert all(np.array_equal(o.asnumpy()[0], h)
               for o, h in zip(outs, per_item[0]))
    assert bp.executor_calls == 2 and bp.cold_runs == 1


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _mode_spec(mode):
    if mode == "prefix":
        return dict(SPEC, prefix_cache_pages=PAGES)
    if mode == "speculative":
        return dict(SPEC, draft={"params": _lm_params(3, layers=1),
                                 "num_layers": 1, "num_heads": HEADS,
                                 "hidden": HID, "k": 3})
    return dict(SPEC)


@pytest.mark.parametrize("mode", ["plain", "prefix", "speculative"])
def test_greedy_tokens_equal_the_dense_reference(mode, dense_decode):
    """Plain, prefix-cache (a full hit: copy-on-write of the last page; a
    partial hit: catch-up) and speculative runs serve the tokens the dense
    recompute gives."""
    rng = np.random.RandomState(29)
    shared = [int(t) for t in rng.randint(0, V, size=12)]  # 3 full pages
    work = [(shared, 6), (shared, 4), (shared + [5, 9, 2], 6),
            ([7, 3], 9), (shared[:6], 5)]
    ref = [dense_decode(p, n) for p, n in work]
    eng = DecodeEngine(_PARAMS, **_mode_spec(mode))
    try:
        first = eng.generate(*work[0])  # publishes the shared pages
        streams = [eng.submit(p, n) for p, n in work[1:]]
        got = [first] + [s.result(timeout=120) for s in streams]
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert got == ref
    assert snap["kv"]["total_refcount"] == 0
    if mode == "prefix":
        assert snap["kv"]["prefix_hits"] >= 2
        assert snap["kv"]["cow_copies"] >= 1
        assert streams[0].prefill_tokens == 0  # the full hit
    if mode == "speculative":
        assert snap["draft"]["proposed"] > 0
        assert snap["draft"]["kv"]["device_bytes"] == 2 * PLANE_BYTES


def test_lane_buckets_alternate_inside_a_run(dense_decode):
    """A long request with short ones joining and leaving: steps go
    through bucket 1, 2, 1, 2, ... on one pool, and the tokens hold."""
    eng = DecodeEngine(_PARAMS, **dict(SPEC, lane_buckets=(1, 2)))
    used = []
    run = eng._dispatch_lanes

    def recording(pred, *feeds):
        used.append(pred._exec._program_name)
        return run(pred, *feeds)

    eng._dispatch_lanes = recording
    try:
        long = eng.submit([1, 2, 3], 24)
        got_short = []
        for prompt in ([4, 5], [6], [7, 8, 9]):
            got_short.append(eng.generate(prompt, 3))
        got_long = long.result(timeout=120)
    finally:
        eng.stop()
    assert got_long == dense_decode([1, 2, 3], 24)
    assert got_short == [dense_decode(p, 3)
                         for p in ([4, 5], [6], [7, 8, 9])]
    changes = sum(1 for a, b in zip(used, used[1:]) if a != b)
    assert {"decode_b1", "decode_b2"} <= set(used) and changes >= 3


def test_pool_lives_where_the_weights_live():
    eng = DecodeEngine(_PARAMS, ctx=mx.tpu(1), **SPEC)  # host device 1
    try:
        eng.generate([1, 2, 3], 3)
        where = eng.devices()
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert where["pool"] == where["weights"] == where["decode"] == \
        where["prefill"]
    assert where["pool"] == [str(mx.tpu(1).jax_device())] != \
        [str(mx.tpu(0).jax_device())]
    assert snap["kv"]["device_bytes"] == 2 * LAYERS * PLANE_BYTES
    assert snap["step_donated"] is True


def test_warmup_compiles_everything_a_run_uses():
    """After ``warmup()`` a run with prefills of every bucket, catch-up and
    copy-on-write traces no new program."""
    import jax

    eng = DecodeEngine(_PARAMS, **_mode_spec("prefix"))
    compiled = []

    def on_event(name, *args, **kwargs):
        if "backend_compile" in name:
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        rng = np.random.RandomState(5)
        shared = [int(t) for t in rng.randint(0, V, size=12)]
        for prompt, n in ((shared, 3), (shared, 3), (shared + [1, 2], 3),
                          ([3] * 20, 2), ([9], 2)):
            eng.generate(prompt, n)
        assert eng.cold_decode_runs() == 0
        assert eng.snapshot()["kv"]["cow_copies"] >= 1
    finally:
        eng.stop()
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiled == []
