"""Compile-cache tests — the PR-10 acceptance criteria as assertions.

Cross-process executable reuse (a second process starts warm: hits > 0,
zero compiles, bit-identical outputs), hot-swap under a warm cache (zero
cold-bucket runs, no new compiles), AOT bundle save/attach roundtrip with
a LOUD refusal on topology mismatch, version-mismatch invalidation as an
observable event, and — chaos-marked — corrupt/torn entries degrading to
a plain recompile with a structured telemetry event, never a crash.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import faults, serving, telemetry
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "compile_cache_worker.py")

IN_DIM = 6
HID = 3


def _reset():
    """Zero the counters AND drop the in-memory executable cache, so the
    next build must go through the disk (or an attached bundle)."""
    telemetry._reset_for_tests()
    cc.reset_stats()


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """Fresh cache dir + clean instrument/memory state on both sides."""
    d = str(tmp_path / "cc")
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", d)
    _reset()
    yield d
    _reset()


def _tiny_model(seed=0):
    rng = np.random.RandomState(seed)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=HID,
                                name="fc")
    params = {
        "fc_weight": mx.nd.array(rng.randn(HID, IN_DIM).astype(np.float32)),
        "fc_bias": mx.nd.array(rng.randn(HID).astype(np.float32)),
    }
    return net, params


def _forward(net, params, X):
    pred = mx.Predictor(net, dict(params), {"data": X.shape})
    return pred.forward(data=X)[0].asnumpy()


def _run_worker(mode, cache_dir):
    env = dict(os.environ, MXNET_COMPILE_CACHE_DIR=cache_dir)
    proc = subprocess.run([sys.executable, WORKER, mode], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# in-process roundtrip + keying
# ---------------------------------------------------------------------------

def test_predictor_roundtrip_in_process(cache_dir):
    """First build compiles and stores; after dropping the in-memory
    cache a fresh executor loads the disk entry — a hit, no compile —
    and produces bit-identical outputs."""
    net, params = _tiny_model()
    X = np.random.RandomState(3).randn(2, IN_DIM).astype(np.float32)
    out_cold = _forward(net, params, X)
    s = cc.stats()
    assert s["misses"] >= 1 and s["stores"] >= 1 and s["hits"] == 0
    assert cc.ls_entries(cache_dir), "store left no entry on disk"

    _reset()  # drops the in-memory executable cache: force disk
    out_warm = _forward(net, params, X)
    s = cc.stats()
    assert s["hits"] >= 1 and s["misses"] == 0 and s["errors"] == 0
    np.testing.assert_array_equal(out_cold, out_warm)


def test_signature_change_is_a_new_entry(cache_dir):
    """A different batch signature must not hit the old entry — the
    Compiled executable does not retrace on shape change, so serving it
    for the wrong shape would be a correctness bug."""
    net, params = _tiny_model()
    _forward(net, params, np.zeros((2, IN_DIM), np.float32))
    n1 = len(cc.ls_entries(cache_dir))
    _forward(net, params, np.zeros((4, IN_DIM), np.float32))
    n2 = len(cc.ls_entries(cache_dir))
    assert n2 > n1, "shape change reused the same cache entry"


def test_min_ms_threshold_skips_store(cache_dir, monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_CACHE_MIN_MS", "1e9")
    net, params = _tiny_model()
    _forward(net, params, np.zeros((2, IN_DIM), np.float32))
    s = cc.stats()
    assert s["misses"] >= 1 and s["stores"] == 0
    assert not cc.ls_entries(cache_dir)


def test_version_mismatch_invalidates_with_event(cache_dir, monkeypatch):
    """An entry recorded under another jax version is a miss with a
    structured ``compile_cache_invalidate`` event — never served, never
    a crash."""
    net, params = _tiny_model()
    X = np.zeros((2, IN_DIM), np.float32)
    _forward(net, params, X)
    assert cc.stats()["stores"] >= 1
    _reset()
    telemetry.enable(trace=False)
    fake = dict(cc.env_fingerprint())
    fake["jax"] = "0.0.0-stale-test"
    monkeypatch.setattr(cc, "_env_fp_cache", fake)

    out = _forward(net, params, X)
    s = cc.stats()
    assert s["hits"] == 0 and s["misses"] >= 1 and s["errors"] == 0
    kinds = [e["kind"] for e in telemetry.events()]
    assert "compile_cache_invalidate" in kinds
    assert out.shape == (2, HID)


# ---------------------------------------------------------------------------
# cross-process reuse — the headline acceptance criterion
# ---------------------------------------------------------------------------

def test_cross_process_predictor_reuse(cache_dir):
    a = _run_worker("predict", cache_dir)
    assert a["stats"]["misses"] >= 1 and a["stats"]["stores"] >= 1

    b = _run_worker("predict", cache_dir)
    assert b["stats"]["hits"] >= 1, b["stats"]
    assert b["stats"]["misses"] == 0, \
        "second process ran the XLA compiler: %s" % b["stats"]
    assert b["stats"]["compile_ms"] == 0.0
    assert b["digest"] == a["digest"], \
        "cache-served outputs are not bit-identical"


@pytest.mark.slow
def test_cross_process_fused_train_reuse(cache_dir):
    """The fused train step (forward+backward+optimizer, donated) also
    roundtrips: the second process trains to bit-identical weights with
    zero compiles."""
    a = _run_worker("train", cache_dir)
    assert a["stats"]["misses"] >= 1 and a["stats"]["stores"] >= 1

    b = _run_worker("train", cache_dir)
    assert b["stats"]["hits"] >= 1 and b["stats"]["misses"] == 0, b["stats"]
    assert b["digest"] == a["digest"], \
        "warm-start training diverged from the cold-start run"


# ---------------------------------------------------------------------------
# serving: warm swap + AOT bundles
# ---------------------------------------------------------------------------

def test_hot_swap_warm_cache_zero_compiles(cache_dir, tmp_path):
    """swap() under a warm cache: the shadow replica's full warmup is
    served from cache — no cold-bucket runs, no new compiles, and the
    post-swap outputs carry the NEW params (the executable is reused,
    the weights are not baked in)."""
    net, params1 = _tiny_model(seed=12)
    _, params2 = _tiny_model(seed=13)
    prefix = str(tmp_path / "swapcc")
    mx.model.save_checkpoint(prefix, 1, net, dict(params1), {})
    mx.model.save_checkpoint(prefix, 2, net, dict(params2), {})
    X = np.random.RandomState(8).randn(4, IN_DIM).astype(np.float32)

    srv = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, IN_DIM)}, max_wait_us=1000)
    try:
        before = cc.stats()
        assert before["misses"] >= 1  # initial warmup did compile
        srv.swap(prefix, 2)
        after = cc.stats()
        assert srv.cold_bucket_runs() == 0
        assert after["misses"] == before["misses"], \
            "swap shadow recompiled instead of inheriting executables"
        assert after["compile_ms"] == before["compile_ms"]
        assert after["hits"] > before["hits"]
        ref2 = _forward(net, params2, X[:1])
        np.testing.assert_allclose(srv.predict(data=X[0])[0], ref2[0],
                                   rtol=1e-5, atol=1e-6)
    finally:
        srv.stop()


@pytest.mark.parametrize("leftovers", [False, True],
                         ids=["plain", "tuning_leftovers"])
def test_aot_bundle_roundtrip(leftovers, cache_dir, tmp_path, monkeypatch):
    """save_aot_bundle beside the checkpoint, then restore with NO cache
    dir configured: from_checkpoint auto-attaches the bundle and the
    whole warmup is deserialize-only.  A bundle written before PR 46 may
    carry a tuning store (an ``autotune/`` directory, ``autotune_entries``
    in its manifest): both are ignored."""
    net, params = _tiny_model(seed=4)
    prefix = str(tmp_path / "aot")
    mx.model.save_checkpoint(prefix, 1, net, dict(params), {})
    X = np.random.RandomState(5).randn(4, IN_DIM).astype(np.float32)

    srv = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, IN_DIM)}, max_wait_us=1000)
    try:
        ref = srv.predict(data=X[0])[0]
        bundle = srv.save_aot_bundle(prefix, 1)
    finally:
        srv.stop()
    manifest = cc.read_manifest(bundle)
    assert manifest["entries"], "bundle saved no executables"
    assert manifest["warmup"]["buckets"]
    assert "autotune_entries" not in manifest
    assert not os.path.exists(os.path.join(bundle, "autotune"))
    if leftovers:
        manifest["autotune_entries"] = 1
        with open(os.path.join(bundle, cc.MANIFEST_NAME), "w") as f:
            json.dump(manifest, f)
        os.makedirs(os.path.join(bundle, "autotune"))
        with open(os.path.join(bundle, "autotune", "0" * 32 + ".mxt"),
                  "wb") as f:
            f.write(b"MXTPUAT1" + b"\0" * 16)

    _reset()  # also detaches bundles + drops the memory cache
    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", "")
    srv2 = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, IN_DIM)}, max_wait_us=1000)
    try:
        s = cc.stats()
        assert s["hits"] >= 1 and s["misses"] == 0 and s["errors"] == 0, \
            "bundle-attached warmup still compiled: %s" % s
        np.testing.assert_array_equal(srv2.predict(data=X[0])[0], ref)
    finally:
        srv2.stop()


def test_one_program_one_key(cache_dir, monkeypatch):
    """What a key is made of is the program, its operands and the
    devices: two builds of one program share one entry, and nothing else
    joins the key."""
    seen = []
    key_parts = cc.CachedFunction._key_parts

    def spy(self, args):
        seen.append(key_parts(self, args))
        return seen[-1]

    monkeypatch.setattr(cc.CachedFunction, "_key_parts", spy)
    net, params = _tiny_model()
    X = np.zeros((2, IN_DIM), np.float32)
    _forward(net, params, X)
    _reset()
    _forward(net, params, X)
    assert len(seen) == 2 and seen[0] == seen[1]
    assert sorted(seen[0]) == [
        "cast_exclude", "compute_dtype", "devices", "graph", "group2ctx",
        "kind", "remat", "schema", "sig", "static"]
    assert len(cc.ls_entries(cache_dir)) == 1
    assert cc.stats()["hits"] == 1 and cc.stats()["misses"] == 0


def test_aot_bundle_topology_mismatch_refused(cache_dir, tmp_path):
    """A bundle built for a different device topology must be refused
    loudly at attach time, and attach_aot=False must still serve."""
    net, params = _tiny_model(seed=4)
    prefix = str(tmp_path / "aotbad")
    mx.model.save_checkpoint(prefix, 1, net, dict(params), {})
    srv = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, IN_DIM)}, max_wait_us=1000)
    try:
        bundle = srv.save_aot_bundle(prefix, 1)
    finally:
        srv.stop()
    mpath = os.path.join(bundle, cc.MANIFEST_NAME)
    manifest = cc.read_manifest(bundle)
    manifest["env"]["device_count"] = manifest["env"]["device_count"] + 8
    with open(mpath, "w") as f:
        json.dump(manifest, f)

    _reset()
    with pytest.raises(MXNetError, match="device_count"):
        serving.InferenceServer.from_checkpoint(
            prefix, 1, {"data": (4, IN_DIM)}, max_wait_us=1000)
    srv3 = serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, IN_DIM)}, attach_aot=False,
        max_wait_us=1000)
    srv3.stop()


# ---------------------------------------------------------------------------
# admin surface
# ---------------------------------------------------------------------------

def test_admin_ls_verify_prune(cache_dir):
    net, params = _tiny_model()
    _forward(net, params, np.zeros((2, IN_DIM), np.float32))
    _forward(net, params, np.zeros((4, IN_DIM), np.float32))
    entries = cc.ls_entries(cache_dir)
    assert len(entries) >= 2
    assert all(e["env_ok"] for e in entries)
    for e in entries:
        ok, detail = cc.verify_entry(e["path"])
        assert ok, detail

    # budget 0 MB: prune removes everything, oldest first
    removed = cc.prune(cache_dir, 0)
    assert sorted(removed) == sorted(e["path"] for e in entries)
    assert not cc.ls_entries(cache_dir)
    assert not [n for n in os.listdir(cache_dir) if n.endswith(".crc32")]


def test_admin_cli_verify_flags_corruption(cache_dir):
    net, params = _tiny_model()
    _forward(net, params, np.zeros((2, IN_DIM), np.float32))
    entry = cc.ls_entries(cache_dir)[0]["path"]
    tool = os.path.join(ROOT, "tools", "compile_cache_admin.py")
    env = dict(os.environ, MXNET_COMPILE_CACHE_DIR=cache_dir)

    proc = subprocess.run(
        [sys.executable, tool, "verify", "--dir", cache_dir, "--json"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert json.loads(proc.stdout)["bad"] == 0

    with open(entry, "r+b") as f:  # flip one payload byte
        f.seek(-1, os.SEEK_END)
        byte = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([byte[0] ^ 0xFF]))
    proc = subprocess.run(
        [sys.executable, tool, "verify", "--dir", cache_dir, "--json"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert json.loads(proc.stdout)["bad"] >= 1


# ---------------------------------------------------------------------------
# chaos: corruption and injected I/O faults degrade to recompiles
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_corrupt_entry_degrades_to_recompile(cache_dir):
    net, params = _tiny_model()
    X = np.random.RandomState(9).randn(2, IN_DIM).astype(np.float32)
    out_cold = _forward(net, params, X)
    entry = cc.ls_entries(cache_dir)[0]["path"]
    with open(entry, "r+b") as f:  # corrupt the payload: CRC must catch it
        f.seek(-4, os.SEEK_END)
        f.write(b"\xde\xad\xbe\xef")

    _reset()
    telemetry.enable(trace=False)
    out = _forward(net, params, X)
    s = cc.stats()
    assert s["errors"] >= 1, "corruption went unnoticed"
    assert s["misses"] >= 1 and s["hits"] == 0
    np.testing.assert_array_equal(out, out_cold)
    kinds = [e["kind"] for e in telemetry.events()]
    assert "compile_cache_corrupt" in kinds


@pytest.mark.chaos
def test_injected_load_ioerr_degrades(cache_dir):
    net, params = _tiny_model()
    X = np.zeros((2, IN_DIM), np.float32)
    out_cold = _forward(net, params, X)
    _reset()
    with faults.inject("compile_cache.load:ioerr=1") as plan:
        out = _forward(net, params, X)
        assert ("compile_cache.load", "ioerr", 1) in plan.events
    s = cc.stats()
    assert s["errors"] >= 1 and s["misses"] >= 1 and s["hits"] == 0
    np.testing.assert_array_equal(out, out_cold)


@pytest.mark.chaos
def test_torn_store_never_leaves_partial_entry(cache_dir):
    """A torn write mid-store (injected partial) must leave NO entry file
    behind (atomic_write tears the temp, not the target) and the build
    itself still succeeds — store failure is an error counter, not an
    exception."""
    net, params = _tiny_model()
    with faults.inject("compile_cache.store:partial=1@0.5"):
        out = _forward(net, params, np.zeros((2, IN_DIM), np.float32))
    assert out.shape == (2, HID)
    s = cc.stats()
    assert s["errors"] >= 1 and s["stores"] == 0
    assert not cc.ls_entries(cache_dir)
    leftovers = [n for n in os.listdir(cache_dir)
                 if n.endswith(cc.ENTRY_SUFFIX)] \
        if os.path.isdir(cache_dir) else []
    assert not leftovers

    # the NEXT store (fault cleared) repopulates the cache cleanly
    _forward(net, params, np.zeros((4, IN_DIM), np.float32))
    assert cc.stats()["stores"] >= 1


@pytest.mark.chaos
def test_strict_mode_raises_on_corrupt(cache_dir, monkeypatch):
    net, params = _tiny_model()
    _forward(net, params, np.zeros((2, IN_DIM), np.float32))
    entry = cc.ls_entries(cache_dir)[0]["path"]
    with open(entry, "r+b") as f:
        f.seek(-4, os.SEEK_END)
        f.write(b"\xde\xad\xbe\xef")
    _reset()
    monkeypatch.setenv("MXNET_COMPILE_CACHE_STRICT", "1")
    with pytest.raises(Exception):
        _forward(net, params, np.zeros((2, IN_DIM), np.float32))
