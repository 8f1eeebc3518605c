"""mxnet_tpu.serving tests — batch coalescing, bucket padding, deadlines,
admission control, graceful drain, metrics, HTTP front end.  All CPU-only
and fast: the model is a tiny FullyConnected net and warmup is enabled
only where the test is about steady-state compile behaviour."""
import json
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving


IN_DIM = 6
HID = 3


def _tiny_model(seed=0):
    rng = np.random.RandomState(seed)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=HID,
                                name="fc")
    params = {
        "fc_weight": mx.nd.array(rng.randn(HID, IN_DIM).astype(np.float32)),
        "fc_bias": mx.nd.array(rng.randn(HID).astype(np.float32)),
    }
    return net, params


def _reference_outputs(net, params, X):
    pred = mx.Predictor(net, dict(params), {"data": (1, IN_DIM)})
    return np.stack([pred.forward(data=X[i:i + 1])[0].asnumpy()[0]
                     for i in range(len(X))])


def test_pow2_buckets():
    assert serving.pow2_buckets(1) == (1,)
    assert serving.pow2_buckets(16) == (1, 2, 4, 8, 16)
    assert serving.pow2_buckets(12) == (1, 2, 4, 8, 12)
    with pytest.raises(ValueError):
        serving.pow2_buckets(0)


@pytest.mark.parametrize("max_batch,buckets,want", [
    (8, None, (1, 2, 4, 8)),      # a power of two: the pow2 ladder
    (6, None, (1, 2, 4, 6)),      # any other: the ladder, capped by it
    (8, (8, 2, 2), (2, 8)),       # an explicit list, as given
])
def test_server_bucket_ladder(max_batch, buckets, want):
    """The server's padded batch sizes: the list it is given, else the
    pow2 ladder up to the leading dim of its inputs."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params),
                                  {"data": (max_batch, IN_DIM)},
                                  buckets=buckets, warmup=False, start=False)
    try:
        assert tuple(srv.buckets) == want
    finally:
        srv.stop()


def test_bucketed_predictor_padding_matches_per_request():
    """Padded bucketed execution is numerically the per-request forward."""
    net, params = _tiny_model()
    bp = serving.BucketedPredictor(net, dict(params), {"data": (IN_DIM,)},
                                   buckets=(1, 2, 4, 8))
    assert bp.bucket_for(1) == 1
    assert bp.bucket_for(3) == 4
    assert bp.bucket_for(8) == 8
    with pytest.raises(mx.MXNetError):
        bp.bucket_for(9)
    X = np.random.RandomState(1).randn(5, IN_DIM).astype(np.float32)
    ref = _reference_outputs(net, params, X)
    bucket, per_item = bp.forward_batch([{"data": X[i]} for i in range(5)])
    assert bucket == 8  # 5 requests pad up to the next bucket
    assert len(per_item) == 5
    for i in range(5):
        np.testing.assert_allclose(per_item[i][0], ref[i], rtol=1e-5,
                                   atol=1e-6)


def test_concurrent_submits_coalesce_into_buckets():
    """Acceptance criterion: 64 concurrent single-item requests run in at
    most len(buckets) distinct compiled shapes and strictly fewer executor
    invocations than 64 sequential Predictor.forward calls — asserted via
    the metrics batch-size histogram AND a wrapper around the real
    executor forward of every bucket predictor."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (16, IN_DIM)},
                                  max_wait_us=20000, max_queue=256)
    try:
        # count true post-warmup executor invocations per bucket predictor
        exec_calls = {"n": 0}
        count_lock = threading.Lock()
        for rep in srv._replicas:
            for pred in rep._preds.values():
                orig = pred._exec.forward

                def counted(*a, _orig=orig, **kw):
                    with count_lock:
                        exec_calls["n"] += 1
                    return _orig(*a, **kw)

                pred._exec.forward = counted

        X = np.random.RandomState(2).randn(64, IN_DIM).astype(np.float32)
        with ThreadPoolExecutor(max_workers=16) as pool:
            futs = list(pool.map(lambda i: srv.submit(data=X[i]), range(64)))
        results = [f.result(timeout=60) for f in futs]

        ref = _reference_outputs(net, params, X)
        for i in range(64):
            np.testing.assert_allclose(results[i][0], ref[i], rtol=1e-5,
                                       atol=1e-6)

        snap = srv.metrics.snapshot()
        hist = snap["batch_size_hist"]
        # every flush ran at a pre-compiled bucket shape: at most
        # len(buckets) distinct shapes, no novel-shape compiles
        assert set(hist) <= set(srv.buckets)
        assert len(hist) <= len(srv.buckets)
        # measurably fewer executor invocations than 64 sequential
        # Predictor.forward calls, and the histogram reports them honestly
        assert sum(hist.values()) == snap["batches_total"] == exec_calls["n"]
        assert exec_calls["n"] < 64
        assert sum(n * c for n, c in snap["occupancy_hist"].items()) == 64
        assert snap["requests_completed"] == 64
    finally:
        srv.stop()


def test_deadline_expiry():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  max_wait_us=200000, warmup=False)
    try:
        x = np.zeros(IN_DIM, np.float32)
        fut = srv.submit(deadline_ms=10, data=x)
        with pytest.raises(serving.DeadlineExceededError):
            fut.result(timeout=30)
        assert srv.metrics.snapshot()["requests_expired"] == 1
    finally:
        srv.stop()


def test_queue_full_rejection():
    net, params = _tiny_model()
    # flush deadline far out and batch bigger than the queue bound, so
    # submits pile up in the queue until admission control trips
    srv = serving.InferenceServer(net, dict(params), {"data": (8, IN_DIM)},
                                  max_wait_us=300000, max_queue=4,
                                  warmup=False)
    try:
        x = np.zeros(IN_DIM, np.float32)
        futs = [srv.submit(data=x) for _ in range(4)]
        with pytest.raises(serving.QueueFullError):
            srv.submit(data=x)
        assert srv.metrics.snapshot()["requests_rejected"] == 1
        # the queued four still complete once the flush deadline fires
        for f in futs:
            assert len(f.result(timeout=30)) == 1
    finally:
        srv.stop()


def test_graceful_drain():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (8, IN_DIM)},
                                  max_wait_us=500000, warmup=False)
    X = np.random.RandomState(3).randn(6, IN_DIM).astype(np.float32)
    futs = [srv.submit(data=X[i]) for i in range(6)]
    srv.stop(drain=True)  # flushes the queue before the workers exit
    ref = _reference_outputs(net, params, X)
    for i in range(6):
        np.testing.assert_allclose(futs[i].result(timeout=1)[0], ref[i],
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(serving.ServerClosedError):
        srv.submit(data=X[0])


def test_stop_without_drain_fails_pending():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (8, IN_DIM)},
                                  max_wait_us=500000, warmup=False)
    fut = srv.submit(data=np.zeros(IN_DIM, np.float32))
    srv.stop(drain=False)
    with pytest.raises(serving.ServerClosedError):
        fut.result(timeout=1)


def test_input_validation():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (2, IN_DIM)},
                                  warmup=False)
    try:
        with pytest.raises(mx.MXNetError):
            srv.submit(data=np.zeros(IN_DIM + 1, np.float32))
        with pytest.raises(mx.MXNetError):
            srv.submit(bogus=np.zeros(IN_DIM, np.float32))
        with pytest.raises(mx.MXNetError):
            srv.submit()
        # a unit batch axis is accepted and squeezed
        out = srv.predict(data=np.zeros((1, IN_DIM), np.float32))
        assert out[0].shape == (HID,)
    finally:
        srv.stop()


def test_metrics_text_output():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    try:
        srv.predict(data=np.zeros(IN_DIM, np.float32))
        text = srv.metrics_text()
        # registry-backed: the server's per-instance registry is a live
        # collector of the shared telemetry exposition
        from mxnet_tpu import telemetry
        assert "mxtpu_serving_requests_total 1" in \
            telemetry.render_prometheus()
    finally:
        srv.stop()
    assert "mxtpu_serving_requests_total 1" in text
    assert "mxtpu_serving_requests_completed 1" in text
    assert 'mxtpu_serving_batch_size{bucket="1"} 1' in text
    assert 'mxtpu_serving_latency_ms{quantile="0.99"}' in text
    assert "mxtpu_serving_qps" in text
    snap = srv.metrics.snapshot()
    assert snap["qps"] > 0
    assert snap["latency_ms_p50"] > 0


def test_batches_emit_profiler_frames(tmp_path):
    net, params = _tiny_model()
    trace = str(tmp_path / "serving_trace.json")
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    try:
        mx.profiler.profiler_set_config(mode="all", filename=trace)
        mx.profiler.profiler_set_state("run")
        srv.predict(data=np.zeros(IN_DIM, np.float32))
        mx.profiler.profiler_set_state("stop")
        mx.profiler.dump_profile()
    finally:
        srv.stop()
    with open(trace) as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events if e["name"].startswith("serving/batch")]
    assert spans and spans[0]["cat"] == "serving"


def test_multi_replica_dispatch():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  ctx=[mx.cpu(0), mx.cpu(1)],
                                  max_wait_us=2000, warmup=False)
    try:
        assert len(srv._replicas) == 2
        X = np.random.RandomState(4).randn(12, IN_DIM).astype(np.float32)
        futs = [srv.submit(data=X[i]) for i in range(12)]
        ref = _reference_outputs(net, params, X)
        for i in range(12):
            np.testing.assert_allclose(futs[i].result(timeout=60)[0],
                                       ref[i], rtol=1e-5, atol=1e-6)
        assert srv.metrics.snapshot()["requests_completed"] == 12
    finally:
        srv.stop()


def test_http_endpoint():
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    try:
        host, port = srv.serve_http()
        base = "http://%s:%d" % (host, port)
        x = list(range(IN_DIM))
        body = json.dumps({"inputs": {"data": x}}).encode()
        resp = urllib.request.urlopen(urllib.request.Request(
            base + "/predict", data=body,
            headers={"Content-Type": "application/json"}), timeout=30)
        out = json.loads(resp.read())["outputs"]
        ref = _reference_outputs(
            net, params, np.asarray(x, np.float32)[None])[0]
        np.testing.assert_allclose(np.asarray(out[0]), ref, rtol=1e-5,
                                   atol=1e-6)
        with urllib.request.urlopen(base + "/metrics", timeout=10) as m:
            assert "mxtpu_serving_requests_total" in m.read().decode()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as h:
            assert h.read() == b"ok"
        # malformed input -> 400, not a hung or dropped connection
        bad = json.dumps({"inputs": {"data": [1.0]}}).encode()
        try:
            urllib.request.urlopen(urllib.request.Request(
                base + "/predict", data=bad,
                headers={"Content-Type": "application/json"}), timeout=10)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
    finally:
        srv.stop()


def test_from_checkpoint(tmp_path):
    """A trained Module checkpoint serves through the batching tier and
    matches the plain Predictor on the same checkpoint."""
    np.random.seed(5)
    X = np.random.randn(40, IN_DIM).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    prefix = str(tmp_path / "served")
    mod.save_checkpoint(prefix, 1)

    srv = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, IN_DIM)}, max_wait_us=1000, warmup=False)
    try:
        out = srv.predict(data=X[0])
        pred = mx.Predictor.from_checkpoint(prefix, 1, {"data": (1, IN_DIM)})
        ref = pred.forward(data=X[0:1])[0].asnumpy()[0]
        np.testing.assert_allclose(out[0], ref, rtol=1e-5, atol=1e-6)
    finally:
        srv.stop()


def test_ready_lifecycle_and_readyz_endpoint():
    """Readiness (may I take traffic?) is distinct from liveness (am I
    alive?): /readyz must say 503 while starting, warming, or stopped,
    with the why-not in the body, while /healthz keeps its dead-worker
    semantics untouched."""
    import urllib.error

    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  warmup=False, start=False)
    try:
        assert not srv.ready()
        assert srv.ready_state() == "starting"
        srv.start()
        assert srv.ready()
        assert srv.ready_state() == "ready"

        host, port = srv.serve_http()
        base = "http://%s:%d" % (host, port)
        with urllib.request.urlopen(base + "/readyz", timeout=10) as r:
            assert r.read() == b"ready"
        # re-enter the warming window: /readyz flips to 503 "warming"
        # while /healthz stays 200 — the router drains traffic off a
        # warming replica without the orchestrator killing it
        srv._warmed = False
        assert srv.ready_state() == "warming"
        try:
            urllib.request.urlopen(base + "/readyz", timeout=10)
            raise AssertionError("expected HTTP 503")
        except urllib.error.HTTPError as exc:
            assert exc.code == 503
            assert json.loads(exc.read())["status"] == "warming"
        with urllib.request.urlopen(base + "/healthz", timeout=10) as h:
            assert h.read() == b"ok"
        srv.warmup()
        assert srv.ready()
        assert srv.cold_bucket_runs() == 0
    finally:
        srv.stop()
    assert not srv.ready()
    assert srv.ready_state() == "stopped"


def test_stop_is_idempotent():
    """A second stop() (any drain value) is a no-op: it must not re-fail
    futures, re-join workers, or raise — and submit() after stop raises
    the typed ServerClosedError immediately instead of queueing into the
    dead batcher."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (8, IN_DIM)},
                                  max_wait_us=500000, warmup=False)
    X = np.random.RandomState(6).randn(3, IN_DIM).astype(np.float32)
    futs = [srv.submit(data=X[i]) for i in range(3)]
    srv.stop(drain=True)
    results = [f.result(timeout=1) for f in futs]
    assert len(results) == 3
    srv.stop(drain=False)  # no-op: the drained results stay results
    srv.stop()
    assert all(f.exception() is None for f in futs)
    with pytest.raises(serving.ServerClosedError):
        srv.submit(data=X[0])


def test_stop_releases_device_memory():
    """stop() must release device-resident params and executables — a
    paged-out model cannot pin HBM.  resident_bytes() is the proof: >0
    while serving, 0 after stop; cold_bucket_runs() survives the release
    so warm-start accounting still reads correctly post-mortem."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  warmup=True)
    x = np.zeros(IN_DIM, np.float32)
    srv.submit(data=x).result(timeout=30)
    assert srv.resident_bytes() > 0
    cold_before = srv.cold_bucket_runs()
    srv.stop(drain=True)
    assert srv.resident_bytes() == 0
    assert srv.cold_bucket_runs() == cold_before


def test_http_deadline_header():
    """X-Deadline-Ms on /predict must reach submit(deadline_ms=...): a
    request that can't make its deadline dies as a 504, not as unbounded
    queueing."""
    import urllib.error

    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (8, IN_DIM)},
                                  max_wait_us=200000, warmup=False)
    try:
        host, port = srv.serve_http()
        body = json.dumps(
            {"inputs": {"data": list(range(IN_DIM))}}).encode()
        try:
            urllib.request.urlopen(urllib.request.Request(
                "http://%s:%d/predict" % (host, port), data=body,
                headers={"Content-Type": "application/json",
                         "X-Deadline-Ms": "10"}), timeout=30)
            raise AssertionError("expected HTTP 504")
        except urllib.error.HTTPError as exc:
            assert exc.code == 504
        assert srv.metrics.snapshot()["requests_expired"] == 1
    finally:
        srv.stop()


def test_healthz_degraded_when_worker_thread_dies():
    """A dead replica worker must flip /healthz to 503 degraded (with the
    dead thread named) and bump the worker_crashes counter — a server
    that looks alive but silently lost its executor loop is the failure
    mode health checks exist for."""
    import urllib.error

    net, params = _tiny_model()
    srv = serving.InferenceServer(net, dict(params), {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    try:
        host, port = srv.serve_http()
        base = "http://%s:%d" % (host, port)
        with urllib.request.urlopen(base + "/healthz", timeout=10) as h:
            assert h.read() == b"ok"
        assert srv.health() == ("ok", [])

        # make the worker's NEXT _collect() blow up; the current request
        # completes normally, then the loop crashes
        def boom():
            raise RuntimeError("injected worker crash")

        prev_hook = threading.excepthook  # keep the traceback out of logs
        threading.excepthook = lambda args: None
        try:
            srv._batcher._collect = boom
            srv.predict(data=np.zeros(IN_DIM, np.float32))
            deadline = time.monotonic() + 10.0
            while not srv._batcher.dead_workers():
                assert time.monotonic() < deadline, "worker never died"
                time.sleep(0.02)
        finally:
            threading.excepthook = prev_hook

        status, dead = srv.health()
        assert status == "degraded"
        assert any("injected worker crash" in d for d in dead)
        assert srv.metrics.snapshot()["worker_crashes"] == 1
        try:
            urllib.request.urlopen(base + "/healthz", timeout=10)
            raise AssertionError("expected HTTP 503")
        except urllib.error.HTTPError as exc:
            assert exc.code == 503
            body = json.loads(exc.read())
            assert body["status"] == "degraded"
            assert body["dead_workers"]
        text = srv.metrics_text()
        assert "mxtpu_serving_worker_crashes 1" in text
    finally:
        srv.stop()


def test_drain_deadline_force_cancels_wedged_worker():
    """A wedged batch worker must not hang retirement: stop(drain=True)
    past MXNET_SERVING_DRAIN_TIMEOUT_MS force-cancels every remaining
    future with DrainTimeoutError instead of waiting forever."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, params, {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    wedge = threading.Event()
    real_forward = srv._replicas[0].forward_batch

    def wedged_forward(items):
        wedge.wait()                    # the worker is stuck mid-batch
        return real_forward(items)

    srv._replicas[0].forward_batch = wedged_forward
    futs = [srv.submit(data=np.zeros(IN_DIM, np.float32))
            for _ in range(6)]
    t0 = time.monotonic()
    srv.stop(drain=True, timeout_ms=300)
    assert time.monotonic() - t0 < 10.0     # bounded, not forever
    cancelled = 0
    for f in futs:
        assert f.done()
        try:
            f.result(timeout=0)
        except serving.DrainTimeoutError:
            cancelled += 1
    assert cancelled == len(futs)
    wedge.set()                             # unwedge; late completion is
    time.sleep(0.1)                         # dropped, never raised


def test_drain_completes_before_deadline_without_cancel():
    """The hard deadline is a backstop: a healthy drain still flushes
    every queued request successfully."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, params, {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    futs = [srv.submit(data=np.zeros(IN_DIM, np.float32))
            for _ in range(6)]
    srv.stop(drain=True, timeout_ms=30000)
    for f in futs:
        assert f.result(timeout=0) is not None


def test_begin_drain_flips_readiness_only():
    """begin_drain quiesces arrivals (readyz 503) while the server keeps
    completing work — the scale-in first step."""
    net, params = _tiny_model()
    srv = serving.InferenceServer(net, params, {"data": (4, IN_DIM)},
                                  max_wait_us=1000, warmup=False)
    try:
        fut = srv.submit(data=np.zeros(IN_DIM, np.float32))
        srv.begin_drain()
        assert srv.ready_state() == "draining" and not srv.ready()
        assert fut.result(timeout=30) is not None   # in-flight completes
        status, _ = srv.health()
        assert status == "ok"                       # liveness untouched
    finally:
        srv.stop()
