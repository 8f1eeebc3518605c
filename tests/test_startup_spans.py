"""The start of a process: the ``start:*`` spans that ``profiler.Frame``
always stamps into the start-up record, and the compile ledger
``compile_cache`` keeps of JAX's own events, on the host platform.

A toy ``Module`` and a toy engine leave the spans of PERF.md's table with
their args and nesting; a program's first call is one ``start:program`` and
its second leaves none; a compile is charged to the span it fell in; both
records stop at their bounds; a plain ``Frame`` still reads no clock.
"""
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import compile_cache as cc
from mxnet_tpu import context
from mxnet_tpu import profiler as prof
from mxnet_tpu import telemetry
from mxnet_tpu.generation import DecodeEngine

from test_program_spans import SPEC, Session, _inside, _lm_params, _toy_module


@pytest.fixture(scope="module", autouse=True)
def room_in_the_records():
    """Both records are bounded and a test process builds engines all its
    life: the files that ran before this one in the same process may have
    filled them.  Make room for this file's.  And build this file's
    programs as plain ``jax.jit``: a file that left the executable cache on
    would hand out compiled programs that ``jax.clear_caches`` cannot make
    compile again."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(prof, "STARTUP_SPANS", len(prof._startup) + 2000)
        mp.setattr(cc, "LEDGER_PROGRAMS", len(cc._ledger_programs) + 2000)
        mp.delenv("MXNET_COMPILE_CACHE_DIR", raising=False)
        cc.detach_bundles()
        yield


def _spans_since(n):
    return [s for s in prof.startup()["spans"] if s["id"] >= n]


def _next_id():
    return len(prof._startup)


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _rows_of(span):
    return [r for r in cc.ledger() if r["span_id"] == span["id"]]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_a_start_span_is_stamped_with_nothing_listening():
    assert not telemetry.enabled()
    n = _next_id()
    with prof.Frame("start:outer", "test", {"n": 1}) as outer:
        with prof.Frame("plain:span", "test"):
            with prof.Frame("start:inner", "test"):
                pass
        outer.set(m=2)
    outer_rec, inner_rec = _spans_since(n)
    assert (outer_rec["name"], outer_rec["args"]) == ("start:outer",
                                                      {"n": 1, "m": 2})
    # the enclosing START-UP span of its thread, through a plain one
    assert inner_rec["parent"] == outer_rec["id"]
    assert outer_rec["parent"] is None
    assert outer_rec["start"] <= inner_rec["start"] <= inner_rec["end"] \
        <= outer_rec["end"]
    assert outer_rec["thread"] == "MainThread"
    assert prof.open_frames() == []


def test_a_plain_frame_reads_no_clock_and_leaves_no_record(monkeypatch):
    def no_clock():
        raise AssertionError("a Frame read the clock with nothing listening")

    n = _next_id()
    monkeypatch.setattr(prof.time, "perf_counter_ns", no_clock)
    monkeypatch.setattr(prof.time, "perf_counter", no_clock)
    with prof.Frame("gen:step", "test", {"n": 1}) as span:
        assert prof.open_frames() == [span]
    assert _spans_since(n) == [] and prof.open_frames() == []


def test_the_import_is_the_first_span():
    first = prof.startup()["spans"][0]
    assert first["name"] == "start:import" and first["id"] == 0
    assert first["end"] > first["start"]


def test_the_record_stops_at_its_bound(monkeypatch):
    n = _next_id()
    dropped = prof.startup()["dropped"]
    monkeypatch.setattr(prof, "STARTUP_SPANS", n + 2)
    for i in range(5):
        with prof.Frame("start:bound%d" % i, "test"):
            pass
    assert [s["name"] for s in _spans_since(n)] == ["start:bound0",
                                                    "start:bound1"]
    assert prof.startup()["dropped"] == dropped + 3
    assert prof.open_frames() == []


def test_the_ledger_stops_at_its_bound(monkeypatch):
    x = jnp.ones(3)
    monkeypatch.setattr(cc, "LEDGER_PROGRAMS", len(cc._ledger_programs) + 1)
    before = {(r["program"], r["phase"]): r["events"] for r in cc.ledger()}
    for name in ("bounded_a", "bounded_b", "bounded_c"):
        def fn(x):
            return x + 1
        fn.__name__ = fn.__qualname__ = name
        jax.jit(fn)(x)
    after = {(r["program"], r["phase"]): r["events"] for r in cc.ledger()
             if r["span"] is None}
    assert after[("bounded_a", "compile")] == 1
    assert ("bounded_b", "compile") not in after
    assert after[("other", "compile")] \
        == before.get(("other", "compile"), 0) + 2


def test_nested_events_are_counted_once():
    """A function jitted inside another is traced inside the outer's
    trace: the rows hold own time and add up to no more than the call."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    def outer(x):
        return inner(x) + inner(x * 3)

    outer.__name__ = "nested_outer"
    n = _next_id()
    program = prof.first_call(jax.jit(outer), "test", lambda fn: None)
    program(jnp.ones(4))
    (span,) = _spans_since(n)
    rows = _rows_of(span)
    assert {r["program"] for r in rows} == {"nested_outer"}
    assert {r["phase"] for r in rows} == {"trace", "lower", "compile"}
    assert 0 < sum(r["seconds"] for r in rows) <= span["end"] - span["start"]


def test_the_always_on_paths_import_nothing_of_the_package(monkeypatch):
    """The ledger's listeners, the first contact with the devices and the
    report run on whatever thread gets there.  Under the kvstore server's
    bootstrap the package's import is still in progress on the main thread
    for the life of the process, and an import statement of the package on
    another thread waits for it for ever (kvstore_server.py's rule)."""
    import builtins

    real = builtins.__import__

    def guarded(name, globals=None, locals=None, fromlist=(), level=0):
        package = (globals or {}).get("__package__") or ""
        if name.split(".")[0] == "mxnet_tpu" or (
                level and package.split(".")[0] == "mxnet_tpu"):
            raise AssertionError("%r (level %d) imported from %s on an "
                                 "always-on path" % (name, level, package))
        return real(name, globals, locals, fromlist, level)

    def fn(x):
        return x * 5 + 1

    fn.__name__ = fn.__qualname__ = "imports_nothing"
    x = jnp.ones(3)
    monkeypatch.setattr(cc, "_instruments", None)  # made anew by the listener
    monkeypatch.setattr(context, "_backend_met", False)
    misses = cc.stats()["jax"]["misses"]
    n = _next_id()
    monkeypatch.setattr(builtins, "__import__", guarded)
    jax.jit(fn)(x)
    context._local_devices()
    with prof.Frame("start:guarded", "test"):
        pass
    report = telemetry.startup_report()
    monkeypatch.undo()
    assert [s["name"] for s in _spans_since(n)] == ["start:backend",
                                                    "start:guarded"]
    assert report["spans"]["start:guarded"]["count"] == 1
    assert cc.stats()["jax"]["misses"] == misses + 1
    assert ("imports_nothing", "compile") in {
        (r["program"], r["phase"]) for r in cc.ledger()}


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

def test_a_module_leaves_the_tables_spans():
    n = _next_id()
    mod, batch = _toy_module()
    built = _spans_since(n)
    (bind,), (params,), (opt,) = (_named(built, "start:" + k)
                                  for k in ("bind", "params", "optimizer"))
    assert bind["args"] == {"kind": "train", "bucket": 8}
    # fc1 and fc2, a weight and a bias each, float32
    assert params["args"] == {"leaves": 4,
                              "bytes": 4 * (16 * 12 + 16 + 4 * 16 + 4)}
    # the fused step makes its states at its first call
    assert opt["args"] == {"states": 0, "bytes": 0}
    assert bind["end"] <= params["start"] and params["end"] <= opt["start"]
    assert not _named(built, "start:program")

    n = _next_id()
    mod.forward_backward(batch)
    mod.update()
    (program,) = _spans_since(n)
    assert program["name"] == "start:program"
    assert program["args"] == {"program": "fused_step", "kind": "fused"}
    rows = _rows_of(program)
    assert {r["phase"] for r in rows} == {"trace", "lower", "compile"}
    assert all(r["span"] == "start:program" and r["program"] == "fused_step"
               for r in rows)
    # the second call leaves none, and nothing of the wrapper is left
    n = _next_id()
    mod.forward_backward(batch)
    mod.update()
    assert _spans_since(n) == []
    ex = mod._exec_group.execs[0]
    assert not any(isinstance(f, prof.first_call)
                   for f in ex._jit_cache.values())


def test_set_params_is_a_params_span():
    mod, _ = _toy_module()
    args, auxs = mod.get_params()
    n = _next_id()
    mod.set_params(args, auxs, allow_missing=True)
    (span,) = _spans_since(n)
    assert span["name"] == "start:params" and span["args"]["leaves"] == 4


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    n = _next_id()
    eng = DecodeEngine(_lm_params(), start=False, **SPEC)
    yield eng, _spans_since(n)
    eng.stop()


def test_an_engine_leaves_the_tables_spans(engine):
    eng, built = engine
    (root,) = _named(built, "start:engine")
    assert root["parent"] is None
    by_id = {s["id"]: s for s in built}
    for s in built:
        if s is not root:
            assert by_id[s["parent"]]["name"] in ("start:engine",
                                                  "start:bind")
            assert root["start"] <= s["start"] and s["end"] <= root["end"]
    (pool,) = _named(built, "start:pool")
    assert pool["args"] == {"bytes": eng.pool.device_bytes(), "pages": 48,
                            "slots": 0}
    # one predictor a bucket: three prefill lengths x three batches, three
    # lane buckets; each places its parameters inside its bind
    binds = _named(built, "start:bind")
    assert len(binds) == 12
    assert {b["args"]["kind"] for b in binds} == {"predict"}
    assert sorted(b["args"]["bucket"] for b in binds) == sorted([1, 2, 4] * 4)
    params = _named(built, "start:params")
    assert len(params) == 13
    assert [p["parent"] for p in params][0] == root["id"]
    assert {by_id[p["parent"]]["name"] for p in params[1:]} == {"start:bind"}
    assert params[0]["args"]["leaves"] == len(eng._params)


def test_every_program_of_the_engine_has_one_first_call(engine):
    eng, built = engine
    programs = _named(built, "start:program")
    counts = {}
    for p in programs:
        key = (p["args"]["program"], p["args"]["kind"])
        counts[key] = counts.get(key, 0) + 1
    want = {("decode_b%d" % b, "gen-step"): 1 for b in (1, 2, 4)}
    for length in (8, 16, 32):
        # one program a batch bucket under each name
        want[("prefill_L%d" % length, "gen-prefill")] = 3
        want[("pool_write_L%d" % length, "pool")] = 3
    want[("prefill_rows", "gen-prefill")] = 9
    assert counts == want
    for p in programs:
        rows = _rows_of(p)
        assert {r["phase"] for r in rows} >= {"trace", "lower", "compile"}
        assert {r["program"] for r in rows} == {p["args"]["program"]}
        assert sum(r["seconds"] for r in rows) <= p["end"] - p["start"]


def test_serving_leaves_no_span_and_a_recompile_names_its_step(engine):
    def in_step():
        return {(r["program"], r["phase"]): r["events"]
                for r in cc.ledger() if r["span"] == "gen:step"}

    eng, _ = engine
    n = _next_id()
    before = in_step()
    stream = eng.submit([1, 2, 3], 3)
    eng._admit()
    for _ in range(6):
        eng._decode_step()
    assert len(stream.tokens) == 3
    assert _spans_since(n) == [] and in_step() == before
    jax.clear_caches()  # every program must be traced and compiled again
    stream = eng.submit([1, 2, 3, 4], 3)
    eng._admit()
    for _ in range(6):
        eng._decode_step()
    assert len(stream.tokens) == 3
    assert _spans_since(n) == []
    new = {k for k, v in in_step().items() if v > before.get(k, 0)}
    assert new == {("decode_b1", phase)
                   for phase in ("trace", "lower", "compile")}
    assert all(r["span_id"] is None for r in cc.ledger()
               if r["span"] == "gen:step")
    in_prefill = [r for r in cc.ledger() if r["span"] == "gen:prefill"]
    assert "prefill_L8" in {r["program"] for r in in_prefill}
    report = eng.snapshot()["startup"]
    assert {r["program"] for r in report["recompiles"]} >= {"decode_b1"}


def test_an_operator_reads_the_report(engine):
    eng, built = engine
    report = telemetry.summary()["startup"]
    assert report == eng.snapshot()["startup"]
    assert report["spans"]["start:import"]["count"] == 1
    assert report["spans"]["start:engine"]["count"] >= 1
    assert 0 < report["wall_s"] <= sum(
        v["seconds"] for v in report["spans"].values())
    # (another test file of this process may have emptied the ledger)
    decode = [p for p in report["programs"]
              if p["program"] == "decode_b4" and "trace_s" in p]
    assert decode and all(
        p["kind"] == "gen-step" and p["seconds"] >= p["trace_s"]
        + p["lower_s"] + p["compile_s"] > 0 for p in decode)
    assert report["jax_cache"]["misses"] == cc.stats()["jax"]["misses"] > 0
    assert report["jax_cache"]["hits"] == 0  # no persistent cache on the host


def test_start_spans_under_an_operators_session(tmp_path):
    """A ``jax.profiler`` session begun before construction has the
    ``start:*`` spans as host events with their args, nested in time."""
    with Session(tmp_path) as ses:
        eng = DecodeEngine(_lm_params(), start=False, **dict(
            SPEC, lane_buckets=(2,), prefill_len_buckets=(8,),
            prefill_batch_buckets=(1,)))
        eng.stop()
    (root,) = ses.named("start:engine")
    (pool,) = ses.named("start:pool")
    assert pool[4] == {"bytes": eng.pool.device_bytes(), "pages": 48,
                       "slots": 0}
    programs = ses.named("start:program")
    assert sorted(p[4]["program"] for p in programs) == [
        "decode_b2", "pool_write_L8", "prefill_L8", "prefill_rows"]
    assert {p[4]["kind"] for p in programs} == {"gen-step", "pool",
                                               "gen-prefill"}
    binds = ses.named("start:bind")
    assert [b[4] for b in binds] == [{"kind": "predict", "bucket": 1},
                                     {"kind": "predict", "bucket": 2}]
    for child in [pool] + programs + binds + ses.named("start:params"):
        assert _inside(child, root)
