"""Unified telemetry subsystem (mxnet_tpu.telemetry): registry/renderer
basics, the cross-layer merged Chrome trace, the StepMonitor MFU path, the
recompile detector, the comm_stats/serving registry folds, the real-tid
profiler satellite, and the telemetry-off overhead guard."""
import json
import os
import subprocess
import sys
import threading
import time
import timeit
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu import profiler as prof
from mxnet_tpu.comm_engine import make_async

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry._reset_for_tests()
    yield
    telemetry._reset_for_tests()


def _fit_small(epochs=1, bs=10, n=50, speedometer=None, seed=0):
    rng = np.random.RandomState(seed)
    data = rng.uniform(size=(n, 10)).astype(np.float32)
    label = rng.randint(0, 2, (n,)).astype(np.float32)
    it = mx.io.NDArrayIter(data, label, batch_size=bs)
    x = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, label_names=("softmax_label",))
    cbs = [speedometer] if speedometer is not None else None
    mod.fit(it, num_epoch=epochs, batch_end_callback=cbs,
            optimizer_params={"learning_rate": 0.1})
    return mod, it


# ---------------------------------------------------------------------------
# registry + renderer
# ---------------------------------------------------------------------------
def test_registry_instruments_and_prometheus_render():
    telemetry.enable(trace=False)
    c = telemetry.counter("mxtpu_t_total", "doc")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = telemetry.gauge("mxtpu_t_gauge")
    g.set(7)
    g.set_max(3)  # set_max never lowers
    assert g.value == 7
    h = telemetry.histogram("mxtpu_t_ms", start=1.0, factor=2.0, count=3)
    for v in (0.5, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["sum"] == pytest.approx(103.5)
    lc = telemetry.labeled_counter("mxtpu_t_kinds", "kind")
    lc.inc("a")
    lc.inc("a")
    lc.inc("b")
    assert lc.get("a") == 2

    text = telemetry.render_prometheus()
    assert "# TYPE mxtpu_t_total counter" in text
    assert "mxtpu_t_total 5" in text
    assert "mxtpu_t_gauge 7" in text
    assert 'mxtpu_t_ms_bucket{le="+Inf"} 3' in text
    assert 'mxtpu_t_kinds{kind="a"} 2' in text
    # same name, wrong type is a hard error, not silent aliasing
    with pytest.raises(TypeError):
        telemetry.gauge("mxtpu_t_total")


def test_event_log_ring_and_jsonl(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_DIR", str(tmp_path))
    telemetry.enable(trace=False)
    telemetry.log_event("alpha", x=1)
    telemetry.log_event("beta", y="z")
    evs = telemetry.events()
    assert [e["kind"] for e in evs] == ["alpha", "beta"]
    assert all("ts" in e for e in evs)
    path = tmp_path / "events.jsonl"
    telemetry.disable()  # flush/close
    lines = [json.loads(ln) for ln in path.read_text().splitlines() if ln]
    assert [e["kind"] for e in lines] == ["alpha", "beta"]
    assert lines[0]["x"] == 1


def test_log_event_noop_when_disabled():
    assert not telemetry.enabled()
    assert telemetry.log_event("nope") is None
    assert telemetry.events() == []


# ---------------------------------------------------------------------------
# acceptance: ONE merged trace, spans from every layer on named tracks
# ---------------------------------------------------------------------------
def test_merged_trace_spans_all_layers(tmp_path):
    """Short training run + comm-engine traffic + a serving batch: the
    merged Chrome trace holds training-step, comm-engine and serving spans
    on distinct thread tracks, schema-valid, with thread_name metadata."""
    from mxnet_tpu import serving

    telemetry.enable(trace=True)

    _fit_small()  # 'fit' + 'exec' spans on the main thread

    kv = make_async(mx.kv.create("local"), num_threads=2, bucket_bytes=0)
    try:
        kv.init(1, nd.ones((8,)))
        kv.push(1, nd.ones((8,)))
        out = nd.zeros((8,))
        kv.pull(1, out)
        kv.wait()
    finally:
        kv.close()

    rng = np.random.RandomState(0)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    params = {"fc_weight": mx.nd.array(rng.randn(3, 6).astype(np.float32)),
              "fc_bias": mx.nd.array(rng.randn(3).astype(np.float32))}
    srv = serving.InferenceServer(net, params, {"data": (4, 6)},
                                  max_wait_us=1000, max_queue=16)
    try:
        srv.submit(data=rng.randn(6).astype(np.float32)).result(5)
    finally:
        srv.stop(drain=True)

    payload = telemetry.merged_trace()
    telemetry.validate_trace(payload)
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    by_cat = {}
    for e in spans:
        by_cat.setdefault(e.get("cat"), set()).add(e["tid"])
    assert "fit" in by_cat, by_cat.keys()
    assert "comm" in by_cat, by_cat.keys()
    assert "serving" in by_cat, by_cat.keys()
    # distinct thread tracks: comm-engine workers and the serving batcher
    # are their own threads, not the training main thread
    assert not (by_cat["fit"] & by_cat["comm"])
    assert not (by_cat["fit"] & by_cat["serving"])
    # every span's tid has a thread_name metadata record
    named = {e["tid"]: e["args"]["name"]
             for e in payload["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    for e in spans:
        assert e["tid"] in named
    assert any("comm" in v for v in named.values())

    out = tmp_path / "merged.json"
    telemetry.dump_trace(str(out))
    reloaded = json.loads(out.read_text())
    telemetry.validate_trace(reloaded)
    assert len(reloaded["traceEvents"]) == len(payload["traceEvents"])


# ---------------------------------------------------------------------------
# StepMonitor: counters, MFU parity with the probe path, memory/report
# ---------------------------------------------------------------------------
def test_step_monitor_counts_and_report():
    telemetry.enable(trace=False)
    mod, _ = _fit_small(bs=10, n=50)
    mon = telemetry.current_step_monitor()
    assert mon is not None
    assert mon.c_steps.value == 5
    assert mon.c_samples.value == 50
    rep = mon.report()
    assert rep["steps"] == 5
    assert rep["avg_step_ms"] and rep["avg_step_ms"] > 0
    assert rep["data_wait_ms_total"] >= 0
    assert rep["samples_per_sec"] and rep["samples_per_sec"] > 0
    summ = telemetry.summary()
    assert summ["counters"]["mxtpu_steps_total"] == 5
    assert summ["step"]["steps"] == 5


def test_step_monitor_mfu_matches_probe_path(monkeypatch):
    """The monitor's flop count is the XLA cost analysis of the SAME
    compiled executable tools/perf_probe.py lowers — parity within 10%
    (exact, in practice) by construction."""
    # the test CPU has no listed peak (hence no MFU): name one
    monkeypatch.setenv("MXNET_TELEMETRY_PEAK_FLOPS", "1e12")
    telemetry.enable(trace=False)
    mod, _ = _fit_small()
    mon = telemetry.current_step_monitor()
    assert mon.c_compiles.value >= 1
    ex = mod._exec_group.execs[0]
    info = telemetry.fused_cost_analysis(ex)
    if info is None or not info.get("flops"):
        pytest.skip("backend exposes no cost analysis")
    assert mon.flops_per_step == pytest.approx(info["flops"], rel=0.10)
    mfu = mon.mfu()
    assert mfu is not None
    expect = info["flops"] / mon.avg_step_s() / telemetry.peak_flops()
    assert mfu == pytest.approx(expect, rel=0.10)


def test_peak_flops_table_and_override(monkeypatch):
    # one table keyed by device_kind; an unlisted kind (the test CPU) has
    # no peak and therefore no MFU — never the v5e's
    assert telemetry.peak_flops("TPU v5 lite") == 197e12
    assert telemetry.peak_flops() is None
    monkeypatch.setenv("MXNET_TELEMETRY_PEAK_FLOPS", "1e12")
    assert telemetry.peak_flops() == 1e12


# ---------------------------------------------------------------------------
# recompile detector
# ---------------------------------------------------------------------------
def test_recompile_detector_fires_exactly_once_per_new_shape():
    telemetry.enable(trace=False)
    mod, _ = _fit_small(bs=10, n=50)
    mon = telemetry.current_step_monitor()
    assert mon.c_recompiles.value == 0  # constant shapes: silent

    rng = np.random.RandomState(1)
    data9 = rng.uniform(size=(45, 10)).astype(np.float32)
    label9 = rng.randint(0, 2, (45,)).astype(np.float32)
    it9 = mx.io.NDArrayIter(data9, label9, batch_size=9)
    batch = next(iter(it9))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mod.forward_backward(batch)  # batch 10 -> 9: NEW signature
        mod.forward_backward(batch)  # same signature again: no new warning
    rws = [x for x in w if issubclass(x.category, telemetry.RecompileWarning)]
    assert len(rws) == 1
    assert "10" in str(rws[0].message) and "9" in str(rws[0].message)
    assert mon.c_recompiles.value == 1
    assert any(e["kind"] == "recompile" for e in telemetry.events())


def test_recompile_detector_silent_across_epochs():
    """Epoch boundaries replay the SAME shapes — never a recompile."""
    telemetry.enable(trace=False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        mod, _ = _fit_small(epochs=3)  # keep the module (it owns the monitor)
    assert not [x for x in w
                if issubclass(x.category, telemetry.RecompileWarning)]
    assert telemetry.current_step_monitor().c_recompiles.value == 0
    assert mod is not None


# ---------------------------------------------------------------------------
# overhead guard: telemetry off must stay near-free
# ---------------------------------------------------------------------------
def test_disabled_overhead_under_two_percent():
    """Off, each hook site costs one module-global bool read.  Budget:
    ~12 hook reads per step must stay under 2% of even a tiny CPU step."""
    assert not telemetry.enabled()
    mod, it = _fit_small()  # telemetry off: fit runs the plain path
    assert telemetry.current_step_monitor() is None  # no monitor was built

    # measured cost of one gate read, amortized over 200k calls
    n = 200_000
    per_gate_s = timeit.timeit(telemetry.enabled, number=n) / n

    # measured steady-state step time for the same tiny module
    it.reset()
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    t0 = time.perf_counter()
    for _ in range(20):
        mod.forward_backward(batch)
        mod.update()
    step_s = (time.perf_counter() - t0) / 20

    hooks_per_step = 12  # fit fetch + fwd/bwd + update + iterator + comm
    assert per_gate_s * hooks_per_step < 0.02 * step_s, \
        "telemetry-off gate cost %.3fus x %d vs step %.1fus" % (
            per_gate_s * 1e6, hooks_per_step, step_s * 1e6)


# ---------------------------------------------------------------------------
# satellites: profiler tids + mid-run flush, comm_stats fold, serving fold
# ---------------------------------------------------------------------------
def test_profiler_records_real_thread_ids(tmp_path):
    out = tmp_path / "prof.json"
    mx.profiler.profiler_set_config(mode="all", filename=str(out))
    mx.profiler.profiler_set_state("run")
    try:
        with prof.Frame("main.span", "test"):
            pass

        def worker():
            with prof.Frame("worker.span", "test"):
                pass

        t = threading.Thread(target=worker, name="tele-test-worker")
        t.start()
        t.join()
        # satellite: dump_profile flushes mid-run, without stop
        mx.profiler.dump_profile()
    finally:
        mx.profiler.profiler_set_state("stop")
    events = json.loads(out.read_text())["traceEvents"]
    mine = [e for e in events if e["name"].endswith(".span")]
    assert len(mine) == 2
    tids = {e["tid"] for e in mine}
    assert len(tids) == 2  # real per-thread ids, not the old constant 0
    assert all(e["ph"] == "X" and "dur" in e for e in events)


def test_comm_stats_is_view_over_registry():
    telemetry.enable(trace=False)
    kv = make_async(mx.kv.create("local"), num_threads=1, bucket_bytes=0)
    try:
        kv.init(7, nd.ones((4,)))
        kv.push(7, nd.ones((4,)))
        out = nd.zeros((4,))
        kv.pull(7, out)
        kv.wait()
        stats = kv.comm_stats()
        # the dict API is unchanged...
        for key in ("pushes", "pulls", "bytes_pushed", "bytes_pulled",
                    "bucket_flushes", "bucket_keys", "wait_calls",
                    "wait_ms_total", "bucket_fill_ratio", "avg_wait_ms"):
            assert key in stats
        assert stats["pushes"] == 1 and stats["pulls"] == 1
        # ...and is backed by the registry the Prometheus render reads
        text = telemetry.render_prometheus()
        assert "mxtpu_comm_pushes 1" in text
        assert "mxtpu_comm_queue_depth" in text  # live gauge
    finally:
        kv.close()
    # dead collector drops out of the global render
    import gc

    del kv
    gc.collect()
    assert "mxtpu_comm_pushes 1" not in telemetry.render_prometheus()


def test_serving_metrics_registry_backed():
    from mxnet_tpu.serving.metrics import ServingMetrics

    telemetry.enable(trace=False)
    m = ServingMetrics()
    m.on_submit(3)
    m.on_batch(bucket=4, occupancy=3)
    m.on_complete(1.5)
    text = m.render_text()
    assert "# TYPE mxtpu_serving_requests_total counter" in text
    assert "mxtpu_serving_requests_total 1" in text
    assert 'mxtpu_serving_batch_size{bucket="4"} 1' in text
    assert "mxtpu_serving_padded_items_total 1" in text
    # surfaced through the shared exposition as a collector
    assert "mxtpu_serving_requests_total 1" in telemetry.render_prometheus()
    assert m.snapshot()["requests_completed"] == 1


def test_fault_injection_counter():
    from mxnet_tpu import faults

    telemetry.enable(trace=False)
    plan = faults.FaultPlan("demo.op:delay=1@1ms", seed=3)
    plan.fire("demo.op")
    lc = telemetry.labeled_counter("mxtpu_faults_injected_total", "kind")
    assert lc.get("delay") == 1
    assert any(e["kind"] == "fault_injected" for e in telemetry.events())


def test_prefetch_iter_instrumented():
    telemetry.enable(trace=False)
    data = np.arange(40, dtype=np.float32).reshape(20, 2)
    base = mx.io.NDArrayIter(data, batch_size=5)
    it = mx.io.PrefetchingIter(base)
    n = sum(1 for _ in it)
    assert n == 4
    text = telemetry.render_prometheus()
    assert "mxtpu_prefetch_batches_total 4" in text


# ---------------------------------------------------------------------------
# tools/telemetry_dump.py
# ---------------------------------------------------------------------------
def test_telemetry_dump_tool_smoke(tmp_path):
    telemetry.enable(trace=True)
    with telemetry.span("tool.span", "test"):
        pass
    trace_a = tmp_path / "a.json"
    telemetry.dump_trace(str(trace_a))
    events = tmp_path / "events.jsonl"
    events.write_text(json.dumps({"ts": 1.0, "kind": "step", "n": 1}) + "\n" +
                      json.dumps({"ts": 2.5, "kind": "compile"}) + "\n")
    tool = os.path.join(REPO, "tools", "telemetry_dump.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    r = subprocess.run([sys.executable, tool, "events", str(events),
                        "--tail", "5"], capture_output=True, text=True,
                       env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "step" in r.stdout and "2 event(s)" in r.stdout

    merged = tmp_path / "merged.json"
    r = subprocess.run([sys.executable, tool, "trace", str(trace_a),
                        str(trace_a), "-o", str(merged)],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    payload = json.loads(merged.read_text())
    telemetry.validate_trace(payload)
    assert any(e.get("name") == "tool.span" for e in payload["traceEvents"])


# ---------------------------------------------------------------------------
# Speedometer data-wait satellite
# ---------------------------------------------------------------------------
def test_speedometer_reports_data_wait():
    telemetry.enable(trace=False)
    spd = mx.callback.Speedometer(batch_size=10, frequent=2)
    _fit_small(speedometer=spd)
    assert spd.last_speed is not None and spd.last_speed > 0
    assert spd.last_data_wait_ms is not None
    assert spd.last_data_wait_ms >= 0.0


def test_speedometer_without_telemetry():
    spd = mx.callback.Speedometer(batch_size=10, frequent=2)
    _fit_small(speedometer=spd)
    assert spd.last_speed is not None
    assert spd.last_data_wait_ms is None


def test_disabled_overhead_distributed_two_workers():
    """Satellite of the cluster-observability PR: the <2% disabled-cost
    guard extended to a 2-worker kvstore exchange.  Off, the dist RPC
    path adds exactly two gate reads per RPC (client _rpc + server
    _dispatch_timed) and keeps the plain 4-element wire envelope."""
    from mxnet_tpu import kvstore_server as kvs

    assert not telemetry.enabled()
    srv = kvs.start_server(num_workers=2)
    clients = []
    try:
        host, port = srv.addr
        clients = [kvs.ServerClient(host, port) for _ in range(2)]
        clients[0].init("w", np.zeros(8, np.float32))
        # structural check: no trace ctx rides the wire while off
        ent = clients[0]._submit(("membership",))
        ent["event"].wait()
        assert len(ent["env"]) == 4

        # measured per-RPC time across both workers, steady state
        for c in clients:
            c.push("w", np.ones(8, np.float32))
            c.pull("w")
        n = 50
        t0 = time.perf_counter()
        for _ in range(n):
            for c in clients:
                c.push("w", np.ones(8, np.float32))
                c.pull("w")
        per_rpc_s = (time.perf_counter() - t0) / (n * 4)

        m = 200_000
        per_gate_s = timeit.timeit(telemetry.enabled, number=m) / m
        gates_per_rpc = 2  # client-side _rpc + server-side dispatch
        assert per_gate_s * gates_per_rpc < 0.02 * per_rpc_s, \
            "telemetry-off gate cost %.3fus x %d vs rpc %.1fus" % (
                per_gate_s * 1e6, gates_per_rpc, per_rpc_s * 1e6)
    finally:
        for c in clients:
            c.close()
        srv.stop()
