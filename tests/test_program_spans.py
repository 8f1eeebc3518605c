"""The one span primitive (``profiler.Frame``) on the profiler's clock, the
spans it puts where the work happens, and the names that reach a compiled
program: all on the host platform.

A ``Frame`` under a ``jax.profiler`` session is a host event of the session's
``.xplane.pb`` with its args as stats; with nothing listening it reads no
clock.  The decode engine, the Module's step and the fused step record the
spans PERF.md lists; Symbol nodes, the optimizer and the flash kernels keep
their names in the compiled text.
"""
import glob
import os
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import hlo_analysis, telemetry
from mxnet_tpu import profiler as prof
from mxnet_tpu.generation import DecodeEngine

V, LAYERS, HEADS, HID, S = 64, 2, 2, 32, 32
SPEC = dict(vocab_size=V, num_layers=LAYERS, num_heads=HEADS, hidden=HID,
            max_seq_len=S, lane_buckets=(1, 2, 4), page_size=4,
            num_pages=48, prefill_len_buckets=(8, 16, 32))
STEP_CHILDREN = ("gen:grow", "gen:feed", "gen:pool_h2d", "gen:forward",
                 "gen:pool_d2h", "gen:emit")


class Session:
    """A ``jax.profiler`` session around a block; afterwards ``events`` holds
    (name, start_ns, end_ns, line index, stats) of the host plane."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "xplane")
        self.events = []

    def __enter__(self):
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                      "*.xplane.pb"))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    self.events.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns, i,
                                        dict(e.stats)))

    def named(self, name):
        return sorted(e for e in self.events if e[0] == name)


def _inside(child, parent):
    return child[3] == parent[3] and parent[1] <= child[1] and \
        child[2] <= parent[2]


def _lm_params():
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=LAYERS,
                                       num_heads=HEADS, hidden=HID,
                                       seq_len=S)
    shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(0)
    return {name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
            for name, shp in zip(net.list_arguments(), shapes)
            if name not in ("data", "softmax_label")}


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_frame_under_a_profiler_session_lands_in_the_xplane(tmp_path):
    with Session(tmp_path) as ses:
        with prof.Frame("outer:span", "test", {"n": 3, "sids": "1|2"}):
            with prof.Frame("inner:span", "test") as inner:
                time.sleep(0.001)
                inner.set(done=7)
    (outer,), (inner,) = ses.named("outer:span"), ses.named("inner:span")
    assert outer[4] == {"n": 3, "sids": "1|2"}
    assert inner[4] == {"done": 7}
    # the parent of a nested span is the enclosing span of its thread
    assert _inside(inner, outer)
    assert inner[2] - inner[1] >= 1_000_000  # it slept a millisecond


def test_frame_reads_no_clock_when_nothing_listens(monkeypatch):
    def no_clock():
        raise AssertionError("a Frame read the clock with nothing listening")

    assert not telemetry.enabled()
    monkeypatch.setattr(prof.time, "perf_counter_ns", no_clock)
    with prof.Frame("quiet:span", "test", {"n": 1}) as span:
        span.set(m=2)
    assert span.args == {"n": 1, "m": 2}


def test_frame_still_feeds_the_chrome_trace(tmp_path):
    """The legacy sink is unchanged in format, and ``set`` reaches it."""
    out = tmp_path / "prof.json"
    mx.profiler.profiler_set_config(mode="all", filename=str(out))
    mx.profiler.profiler_set_state("run")
    try:
        with prof.Frame("chrome:span", "test", {"sid": 4}) as span:
            span.set(emitted=2)
    finally:
        mx.profiler.profiler_set_state("stop")
    events, _ = prof._snapshot_events()
    (ev,) = [e for e in events if e["name"] == "chrome:span"]
    assert ev["ph"] == "X" and ev["cat"] == "test" and ev["dur"] >= 0
    assert ev["args"] == {"sid": 4, "emitted": 2}


def test_one_span_primitive():
    """``TraceAnnotation`` is entered in profiler.py and nowhere else."""
    root = os.path.dirname(os.path.abspath(mx.__file__))
    hits = []
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if "TraceAnnotation" in fh.read():
                        hits.append(os.path.relpath(os.path.join(d, f),
                                                    root))
    assert hits == ["profiler.py"]


# ---------------------------------------------------------------------------
# the decode engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_session(tmp_path_factory):
    eng = DecodeEngine(_lm_params(), **SPEC)
    try:
        with Session(tmp_path_factory.mktemp("gen")) as ses:
            streams = [eng.submit([1, 2, 3, 4, 5][:n + 2], 5)
                       for n in range(3)]
            for s in streams:
                s.result(120)
        snap = eng.snapshot()
    finally:
        eng.stop()
    return ses, streams, snap


@pytest.mark.parametrize("name", ("gen:admit", "gen:prefill", "gen:queued",
                                  "gen:step") + STEP_CHILDREN)
def test_engine_records_every_span_of_the_table(engine_session, name):
    assert engine_session[0].named(name)


def _children(ses, step):
    kids = [k for n in STEP_CHILDREN for k in ses.named(n)
            if _inside(k, step)]
    return sorted(kids, key=lambda k: k[1])


def test_step_children_lie_inside_their_step(engine_session):
    """One ``gen:step`` an iteration that dispatches, none overlapping the
    next, each with exactly one of every child; the step that was in flight
    is read after the dispatch, or before it where its ids are another lane
    count's; a read with nothing to dispatch after it is a ``gen:drain``."""
    ses = engine_session[0]
    steps = ses.named("gen:step")
    assert all(a[3] == b[3] and a[2] <= b[1]
               for a, b in zip(steps, steps[1:]))
    early = STEP_CHILDREN[:1] + STEP_CHILDREN[-2:] + STEP_CHILDREN[1:-2]
    for s in steps:
        kids = _children(ses, s)
        # siblings, none overlapping the next
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        order = [k[0] for k in kids]
        assert order == list(STEP_CHILDREN) or \
            (not s[4]["inflight"] and order == list(early))
    drains = ses.named("gen:drain")
    assert drains and not any(_inside(d, s) for d in drains for s in steps)
    for name in STEP_CHILDREN:
        hosts = drains if name in STEP_CHILDREN[-2:] else []
        assert all(any(_inside(k, s) for s in steps + hosts)
                   for k in ses.named(name))


def test_step_and_pool_args(engine_session):
    ses, streams, snap = engine_session
    sids = {str(s.sid) for s in streams}
    assert sids == {"0", "1", "2"}
    steps = ses.named("gen:step")
    for step in steps:
        st = step[4]
        assert "," not in st["sids"] and "#" not in st["sids"]
        assert set(st["sids"].split("|")) <= sids
        assert st["lanes"] == len(st["sids"].split("|")) <= st["bucket"]
        assert 0 <= st["fed_device"] <= st["lanes"] * st["inflight"]
        assert st["dropped"] == 0  # no EOS set: no lane rides for nothing
    # the step in flight counts once: a span a device step
    assert len(steps) == snap["steps"] >= snap["steps_overlapped"] > 0
    assert sum(s[4]["inflight"] for s in steps) == snap["steps_overlapped"]
    assert snap["tokens_dropped"] == 0
    # ids, positions, sources and tables up, picked ids down: no logits and
    # no plane crosses
    plane = 48 * 4 * HEADS * (HID // HEADS) * 4  # one layer's K plane
    max_pages = S // 4
    for step in steps:
        kids = {k[0]: k[4] for k in _children(ses, step)}
        lanes = step[4]["bucket"]
        assert kids["gen:pool_h2d"]["bytes"] == \
            lanes * (3 + max_pages) * 4 < plane
        down = kids["gen:pool_d2h"]["bytes"]
        if step[4]["inflight"]:
            # the read of the step dispatched an iteration earlier
            assert down == lanes * 4
        else:  # nothing was in flight, or another lane count's step
            assert down in (0, 4, 8, 16) and down != lanes * 4
    assert not ses.named("gen:pool_copyback")
    emits = ses.named("gen:emit")
    assert sum(e[4]["emitted"] for e in emits) == \
        sum(len(s.tokens) - 1 for s in streams)  # prefill emits the first
    assert sum(e[4]["retired"] for e in emits) == len(streams)
    assert all(e[4]["preempted"] == 0 for e in ses.named("gen:grow"))


def test_one_queued_span_per_admitted_request(engine_session):
    ses, streams, _ = engine_session
    queued = ses.named("gen:queued")
    assert sorted(q[4]["sid"] for q in queued) == \
        sorted(s.sid for s in streams)
    assert all(float(q[4]["wait_ms"]) >= 0 for q in queued)
    prefills = ses.named("gen:prefill")
    assert all(any(_inside(q, p) for p in prefills) for q in queued)
    assert sum(p[4]["n"] for p in prefills) == len(streams)
    assert sum(p[4]["tokens"] for p in prefills) == \
        sum(len(s.prompt) for s in streams)
    admits = ses.named("gen:admit")
    assert all(any(_inside(p, a) for a in admits) for p in prefills)


def test_speculative_step_has_draft_and_verify(tmp_path):
    params = _lm_params()
    eng = DecodeEngine(params, draft={"params": dict(params),
                                      "num_layers": LAYERS,
                                      "num_heads": HEADS, "hidden": HID,
                                      "k": 2}, **SPEC)
    try:
        with Session(tmp_path) as ses:
            eng.generate([1, 2, 3], 4)
    finally:
        eng.stop()
    steps = ses.named("gen:step")
    for name in ("gen:draft", "gen:verify"):
        spans = ses.named(name)
        assert spans and all(any(_inside(x, s) for s in steps)
                             for x in spans)
    # the verify pass reaches the pool through the same children
    verify = ses.named("gen:verify")
    assert all(any(_inside(h, v) for v in verify)
               for h in ses.named("gen:pool_d2h")
               if not any(_inside(h, d) for d in ses.named("gen:draft")))


def test_engine_programs_are_named(tmp_path):
    eng = DecodeEngine(_lm_params(), warmup=False, **SPEC)
    try:
        assert {b: p._exec._program_name
                for b, p in eng._decode.items()} == \
            {1: "decode_b1", 2: "decode_b2", 4: "decode_b4"}
        assert {p._exec._program_name
                for p in eng._prefill[16]._preds.values()} == {"prefill_L16"}
        pred = eng._decode[4]
        pred._exec.forward(is_train=False)
        fn = pred._exec._jit_cache[("gen-step", False, False)]
        assert fn.__name__ == "decode_b4"
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

def _toy_module(batch=8):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 12))],
             label_shapes=[("softmax_label", (batch,))], for_training=True)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    rng = np.random.RandomState(1)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(batch, 12).astype("f"))],
        label=[mx.nd.array(rng.randint(0, 4, batch).astype("f"))], pad=0)
    return mod, batch


@pytest.fixture(scope="module")
def toy_module():
    mod, batch = _toy_module()
    mod.forward_backward(batch)
    mod.update()
    return mod, batch


def test_training_step_spans(toy_module, tmp_path):
    mod, batch = toy_module
    with Session(tmp_path) as ses:
        for _ in range(2):
            mod.forward_backward(batch)
            mod.update()
    updates = ses.named("Module.update")
    assert len(ses.named("Module.forward_backward")) == len(updates) == 2
    for name in ("ExecGroup.load_batch", "Executor.fused_step:pack",
                 "Executor.fused_step", "Executor.fused_step:rebind"):
        spans = ses.named(name)
        assert len(spans) == 2
        assert all(_inside(s, u) for s, u in zip(spans, updates))
    # pack, the dispatch and rebind are siblings, in that order
    for pack, run, rebind in zip(ses.named("Executor.fused_step:pack"),
                                 ses.named("Executor.fused_step"),
                                 ses.named("Executor.fused_step:rebind")):
        assert pack[2] <= run[1] and run[2] <= rebind[1]


def test_fused_step_scopes_name_nodes_and_the_optimizer(toy_module):
    mod, _ = toy_module
    ex = mod._exec_group.execs[0]
    scopes = ex.fused_op_scopes()
    # (a parameter's "scope" is its argument path, ``diff_args['w']``)
    assert scopes and all(s.startswith("jit(fused_step)/")
                          for s in scopes.values() if "/" in s)

    def under(component):
        return {k for k, v in scopes.items()
                if "/%s/" % component in v + "/"}

    assert under("optimizer")
    for node in ("fc1", "fc2", "softmax"):
        assert any("(%s)" % node in v or "/%s/" % node in v
                   for v in scopes.values()), node
    # the backward of a node carries its name too
    assert any("transpose(jvp(fc1))" in v for v in scopes.values())
    # the update of a parameter is no node's operation
    assert not any("fc1" in scopes[k] or "fc2" in scopes[k]
                   for k in under("optimizer"))


def test_op_scopes_reads_a_compiled_modules_text():
    text = """HloModule jit_fused_step
fused_computation {
  p0 = f32[8]{0} parameter(0)
  ROOT multiply.1 = f32[8]{0} multiply(p0, p0), metadata={op_name="jit(fused_step)/optimizer/mul" source_file="x.py" source_line=3}
}
ENTRY main {
  %arg = f32[8]{0} parameter(0), metadata={op_name="args[0]"}
  %fusion.7 = f32[8]{0} fusion(%arg), kind=kLoop, calls=fused_computation, metadata={op_name="jit(fused_step)/optimizer/mul"}
  ROOT %flash_fwd.1 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused_step)/jvp(attn0)/flash_fwd/pallas_call"}
  %bare = f32[8]{0} copy(%arg)
}"""
    assert hlo_analysis.op_scopes(text) == {
        "multiply.1": "jit(fused_step)/optimizer/mul",
        "arg": "args[0]",
        "fusion.7": "jit(fused_step)/optimizer/mul",
        "flash_fwd.1": "jit(fused_step)/jvp(attn0)/flash_fwd/pallas_call"}


def test_jitted_programs_have_stable_names(toy_module):
    mod, batch = toy_module
    ex = mod._exec_group.execs[0]
    fn, abstract = ex._fused_introspect
    assert "jit_fused_step" in fn.lower(*abstract).as_text()[:400]
    ex.forward(is_train=False)
    ex.forward_backward()
    names = {getattr(f, "__name__", None) for f in ex._jit_cache.values()}
    assert {"fused_step", "forward", "forward_backward"} <= names


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def test_flash_kernels_are_named_in_the_traced_program():
    """Interpret mode: the ``pallas_call``s of the forward and the two
    backward kernels carry the names a device trace will show."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import flash_attention

    q = jnp.ones((1, 128, 2, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, q, q, causal=True, interpret=True).sum()))(q)
    names = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)
                elif hasattr(getattr(inner, "jaxpr", None), "eqns"):
                    walk(inner.jaxpr)

    walk(jaxpr.jaxpr)
    assert names == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]


def test_paged_attention_bodies_are_scoped():
    eng = DecodeEngine(_lm_params(), warmup=False, **SPEC)
    try:
        ex = eng._decode[4]._exec
        text = ex._get_fwd(False).lower(*ex._forward_args(None)).as_text(
            debug_info=True)
    finally:
        eng.stop()
    assert "jit_decode_b4" in text
    assert "/paged_attention/" in text
