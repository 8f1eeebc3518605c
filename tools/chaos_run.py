#!/usr/bin/env python
"""Replay (or sweep) fault-injection seeds against a training command.

A chaos test that fails reports its (spec, seed); this tool reruns the
exact same fault schedule — the FaultPlan decision for the N-th matching
call is a pure function of (spec, seed, N), so the failure reproduces
outside pytest where it can be debugged:

    # replay the failing schedule
    python tools/chaos_run.py --spec "kv.client.*:drop=0.3" --seed 7 -- \\
        python tools/launch.py -n 2 -s 1 python train.py

    # sweep seeds 0..19 hunting for a schedule that breaks the job
    python tools/chaos_run.py --spec "kv.client.*:drop=0.3" --seeds 0:20 -- \\
        python train.py

The spec/seed reach the command (and every child it spawns, e.g. via
tools/launch.py) through MXNET_FAULTS_SPEC / MXNET_FAULTS_SEED, which
mxnet_tpu.faults reads at import.  See docs/how_to/fault_tolerance.md
for the spec grammar.

Built-in scenarios (no command needed) exercise whole-stack robustness
properties end to end:

    # elastic membership churn: kill -> evict -> respawn-join
    python tools/chaos_run.py --scenario membership-churn --seeds 0:5

    # serving front door: replica failure + breaker recovery + hot-swap
    python tools/chaos_run.py --scenario serving-failover --seeds 0:5

``serving-failover`` drives a Router over N in-process InferenceServer
replicas under sustained load while a seeded FaultPlan hard-fails one
replica (the seed picks the victim), then lets it recover, then rolls a
checkpoint hot-swap through the fleet — asserting zero failed client
requests, breaker open -> half-open -> closed, and zero post-warmup
recompiles.

``sdc-rollback`` flips an exponent bit in one gradient tensor of a
seeded fit() (the seed picks which) and requires the training guardian
to detect it, roll back to the last-good ring snapshot, and replay to a
final state bit-identical to an uninjected control run; it also pushes a
NaN-poisoned gradient at a kvstore server and requires a typed NACK with
the stored value untouched.

``membership-churn`` runs N elastic workers against a sync-mode server
with eviction enabled, hard-kills one mid-run under a seeded FaultPlan
(the seed picks both the victim rank and the kill step), waits for the
server to evict it, then joins a fresh rank mid-run and verifies every
survivor lands on the churn-invariant final weight (see
tests/elastic_churn_worker.py).

``host-loss`` runs the multi-model platform on 2 hosts x 2 devices and
kills every replica on one host mid-stream and mid-fault-in (heartbeats
stop without deregistration); the health plane must flip the failure
domain dead and the degradation ladder must re-fault the evicted
interactive model warm, brown out the batch class with honest 503s, and
fail generate streams over mid-token with bit-identical transcripts.

Scenario sweeps print one machine-readable summary JSON object on
stdout — ``{"scenario", "seeds", "ok", "failing_seeds", "runs": [{seed,
ok, per-tenant failure counts, ...}]}`` — mirror it to a file with
``--summary-json PATH``; the exit code stays nonzero on any invariant
breach.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_membership_churn(seed, timeout=120.0, workers=3, steps=10,
                         join_step=6):
    """Elastic shrink/grow probe: ``workers`` elastic workers train
    against a sync-mode server with eviction on; a seeded FaultPlan
    hard-kills one mid-run (``os._exit(137)`` — kill -9 semantics, no
    leave RPC), the server evicts it on stale heartbeats and the
    survivors continue on renormalized merge rounds; a fresh rank then
    joins mid-run and the job finishes counting the full live set
    again.  Returns True when the victim died with rc 137, membership
    shrank and grew back, and every survivor landed on the
    churn-invariant final weight."""
    import glob
    import json
    import shutil
    import tempfile
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from mxnet_tpu.kvstore_server import ServerClient

    port = _free_port()
    victim = seed % workers
    kill_call = 2 + seed % max(1, join_step - 2)  # 1-based fire() count
    spec = "churn.worker.step:kill=1@#%d" % kill_call
    # flight recorder: the hard-killed victim must leave postmortem
    # evidence (its last spans/events) in this run-scoped directory
    telem_dir = tempfile.mkdtemp(prefix="chaos-telemetry-")
    base = dict(os.environ,
                DMLC_PS_ROOT_URI="127.0.0.1",
                DMLC_PS_ROOT_PORT=str(port),
                DMLC_NUM_WORKER=str(workers),
                MXNET_KVSTORE_ELASTIC="1",
                MXNET_KVSTORE_HEARTBEAT_INTERVAL="0.2",
                MXNET_TELEMETRY="1",
                MXNET_TELEMETRY_DIR=telem_dir,
                CHURN_TOTAL_STEPS=str(steps),
                CHURN_JOIN_STEP=str(join_step),
                CHURN_EXPECT_MEMBERS=str(workers),
                CHURN_KILL_RANK=str(victim),
                CHURN_FAULTS_SPEC=spec,
                CHURN_FAULTS_SEED=str(seed))
    # the kill must be rank-gated IN-PROCESS by the worker script: a
    # plain MXNET_FAULTS_SPEC would reach every worker with the same
    # seed and kill the whole fleet
    base.pop("MXNET_FAULTS_SPEC", None)
    base.setdefault("JAX_PLATFORMS", "cpu")
    base["PYTHONPATH"] = repo + (
        os.pathsep + base["PYTHONPATH"] if base.get("PYTHONPATH") else "")
    worker_py = os.path.join(repo, "tests", "elastic_churn_worker.py")
    print("chaos_run: membership-churn seed %d: victim rank %d dies at "
          "step %d/%d (spec %r)" % (seed, victim, kill_call - 1, steps,
                                    spec), file=sys.stderr, flush=True)
    server = subprocess.Popen(
        [sys.executable, "-c", "import mxnet_tpu"],
        env=dict(base, DMLC_ROLE="server", MXNET_KVSTORE_SYNC="1",
                 MXNET_KVSTORE_EVICT_TIMEOUT="1.0"),
        cwd=repo)
    procs = {}
    results = {}
    grown = None
    try:
        for r in range(workers):
            procs[r] = subprocess.Popen(
                [sys.executable, worker_py],
                env=dict(base, DMLC_WORKER_ID=str(r)),
                stdout=subprocess.PIPE, text=True)
        with ServerClient("127.0.0.1", port) as cli:
            deadline = time.monotonic() + timeout

            def wait_members(pred, what):
                while time.monotonic() < deadline:
                    try:
                        m = cli.membership()
                    except Exception:
                        m = None
                    if m is not None and pred(m):
                        return m
                    time.sleep(0.1)
                raise RuntimeError("membership-churn: timed out waiting "
                                   "for %s" % what)

            # kill -> evict: gen counts N joins plus the eviction bump,
            # which tells a late poll apart from "not everyone joined yet"
            wait_members(lambda m: m["gen"] >= workers + 1
                         and len(m["ranks"]) == workers - 1, "eviction")
            # respawn-join: a fresh rank, never the victim's reused
            procs[workers] = subprocess.Popen(
                [sys.executable, worker_py],
                env=dict(base, DMLC_WORKER_ID=str(workers),
                         MXNET_KVSTORE_ELASTIC_JOIN="1"),
                stdout=subprocess.PIPE, text=True)
            # the joiner's join is the next bump.  Wait on the generation,
            # not on the set: the survivors gate on the joiner, then need
            # four rounds to finish and leave, which can fall between
            # two polls on a loaded host
            grown = wait_members(lambda m: m["gen"] >= workers + 2,
                                 "mid-run join")
            print("chaos_run: membership grew back (gen %d, now %s)"
                  % (grown["gen"], grown["ranks"]),
                  file=sys.stderr, flush=True)
            for r, p in procs.items():
                out, _ = p.communicate(
                    timeout=max(1.0, deadline - time.monotonic()))
                line = [l for l in (out or "").splitlines()
                        if l.startswith("{")]
                results[r] = (p.returncode,
                              json.loads(line[-1]) if line else None)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.terminate()
        try:
            server.wait(timeout=10)
        except subprocess.TimeoutExpired:
            server.kill()

    ok = True
    rc, _ = results.pop(victim, (None, None))
    if rc != 137:
        print("chaos_run: victim rank %d exited rc %s, expected 137"
              % (victim, rc), file=sys.stderr, flush=True)
        ok = False
    for r, (rc, info) in sorted(results.items()):
        if rc != 0 or info is None or "final" not in info:
            print("chaos_run: worker rank %d failed (rc %s, %s)"
                  % (r, rc, info), file=sys.stderr, flush=True)
            ok = False
            continue
        if not info.get("joiner") and \
                abs(info["final"] - info["target"]) > 1e-4:
            print("chaos_run: rank %d final %.6f != invariant %.6f — "
                  "shrunken rounds were not renormalized"
                  % (r, info["final"], info["target"]),
                  file=sys.stderr, flush=True)
            ok = False
    # flight recorder: the fault-injected kill must have dumped the
    # victim's last spans/events before os._exit(137)
    pm = sorted(glob.glob(os.path.join(
        telem_dir, "postmortem-worker%d-*.json" % victim)))
    if not pm:
        print("chaos_run: no flight-recorder postmortem for victim rank %d "
              "in %s" % (victim, telem_dir), file=sys.stderr, flush=True)
        ok = False
    else:
        with open(pm[-1]) as f:
            post = json.load(f)
        if not post.get("reason", "").startswith("fault-kill:") or \
                not (post.get("spans") or post.get("events")):
            print("chaos_run: victim postmortem %s lacks kill reason or "
                  "span/event evidence" % pm[-1],
                  file=sys.stderr, flush=True)
            ok = False
        else:
            print("chaos_run: victim postmortem ok: %s (%d spans, %d "
                  "events)" % (os.path.basename(pm[-1]),
                               len(post["spans"]), len(post["events"])),
                  file=sys.stderr, flush=True)
    if ok:
        shutil.rmtree(telem_dir, ignore_errors=True)
    else:
        print("chaos_run: telemetry artifacts kept at %s" % telem_dir,
              file=sys.stderr, flush=True)
    return ok


def run_serving_failover(seed, timeout=120.0, replicas=3, load_threads=4):
    """Serving front-door probe, in-process: a Router over ``replicas``
    warmed InferenceServer replicas takes sustained load while a seeded
    FaultPlan hard-fails every call to one victim replica (the seed picks
    the victim), then the fault clears, then a checkpoint hot-swap rolls
    through the fleet — all under load.  Passes when zero client requests
    failed end to end, the victim's breaker opened and re-closed after
    recovery, the swap served the new params, and the warm-then-flip kept
    the recompile counter at zero."""
    import tempfile
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving

    in_dim, hid = 6, 3
    rng = np.random.RandomState(seed)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=hid,
                                name="fc")

    def ckpt_params(s):
        r = np.random.RandomState(s)
        return {"fc_weight": mx.nd.array(
                    r.randn(hid, in_dim).astype(np.float32)),
                "fc_bias": mx.nd.array(r.randn(hid).astype(np.float32))}

    victim = "r%d" % (seed % replicas)
    spec = "serving.replica.%s.call:ioerr=1" % victim
    print("chaos_run: serving-failover seed %d: victim %s (spec %r), "
          "%d replicas" % (seed, victim, spec, replicas),
          file=sys.stderr, flush=True)

    tmp = tempfile.mkdtemp(prefix="chaos-serving-")
    prefix = os.path.join(tmp, "m")
    mx.model.save_checkpoint(prefix, 1, net, ckpt_params(seed + 1), {})
    mx.model.save_checkpoint(prefix, 2, net, ckpt_params(seed + 2), {})
    srvs = [serving.InferenceServer.from_checkpoint(
        prefix, 1, {"data": (4, in_dim)}, max_wait_us=1000)
        for _ in range(replicas)]
    router = serving.Router(srvs, seed=seed, retries=2,
                            breaker_threshold=3, breaker_cooldown_ms=100)
    X = rng.randn(8, in_dim).astype(np.float32)
    stop_evt = threading.Event()
    failures = []
    served = [0]

    def load():
        i = 0
        while not stop_evt.is_set():
            try:
                router.predict(data=X[i % len(X)])
                served[0] += 1
            except Exception as exc:
                failures.append(repr(exc))
            i += 1

    deadline = time.monotonic() + timeout
    ok = True
    threads = [threading.Thread(target=load, daemon=True)
               for _ in range(load_threads)]
    try:
        for t in threads:
            t.start()
        # phase 1: hard-fail the victim mid-load until its breaker opens
        mx.faults.install(mx.faults.FaultPlan(spec, seed))
        try:
            while time.monotonic() < deadline:
                snap = router.metrics.snapshot()
                if snap["breaker_transitions"].get("open"):
                    break
                time.sleep(0.05)
            else:
                print("chaos_run: breaker never opened", file=sys.stderr)
                ok = False
        finally:
            mx.faults.uninstall()
        # phase 2: fault cleared — the breaker must walk half-open ->
        # closed on a probe request while the load keeps flowing
        while time.monotonic() < deadline:
            states = {d["name"]: d["state"] for d in router.describe()}
            if states.get(victim) == serving.router.BREAKER_CLOSED:
                break
            time.sleep(0.05)
        else:
            print("chaos_run: breaker never re-closed", file=sys.stderr)
            ok = False
        # phase 3: zero-downtime hot-swap under the same load
        swapped = router.swap(prefix, 2)
        time.sleep(0.2)
        stop_evt.set()
        for t in threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        stop_evt.set()
        router.close(stop_backends=True)

    snap = router.metrics.snapshot()
    if failures or snap["failed"]:
        print("chaos_run: %d client requests failed (first: %s)"
              % (len(failures), failures[:3]), file=sys.stderr, flush=True)
        ok = False
    if swapped != replicas:
        print("chaos_run: swap covered %d/%d replicas" % (swapped, replicas),
              file=sys.stderr, flush=True)
        ok = False
    cold = router.cold_bucket_runs()
    if cold:
        print("chaos_run: %d post-warmup recompiles — the swap shadows "
              "were not fully warmed" % cold, file=sys.stderr, flush=True)
        ok = False
    if ok:
        print("chaos_run: served %d requests, 0 failed; breaker %s; "
              "swap ok (0 recompiles)"
              % (served[0], dict(snap["breaker_transitions"])),
              file=sys.stderr, flush=True)
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    return ok


def run_flash_crowd(seed, timeout=120.0, max_replicas=3, load_threads=6):
    """Self-healing fleet probe, in-process: a replicated front door
    (two Routers over one ReplicaRegistry) serves diurnal + flash-crowd
    open-loop load over a fleet the Autoscaler grows 1→N and shrinks
    back to 1, spawning every replica warm (AOT bundle + compile cache
    attached), while one router is killed mid-flood and its clients
    fail over to the survivor.  Passes when the fleet scaled out (>= 2
    replicas at peak) and back in (1 at the end), zero client requests
    failed end to end, zero interactive-SLO violations (no sheds, no
    deadline expiries), and every scaled-out replica served its first
    request with ``cold_bucket_runs() == 0``."""
    import shutil
    import tempfile
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving

    in_dim, hid = 6, 3
    rng = np.random.RandomState(seed)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=hid,
                                name="fc")
    params = {"fc_weight": mx.nd.array(
                  rng.randn(hid, in_dim).astype(np.float32)),
              "fc_bias": mx.nd.array(rng.randn(hid).astype(np.float32))}

    tmp = tempfile.mkdtemp(prefix="chaos-flashcrowd-")
    prefix = os.path.join(tmp, "m")
    mx.model.save_checkpoint(prefix, 1, net, params, {})
    shapes = {"data": (4, in_dim)}
    server_kw = dict(max_wait_us=1000, max_queue=8)
    cache_key, cache_prev = "MXNET_COMPILE_CACHE_DIR", \
        os.environ.get("MXNET_COMPILE_CACHE_DIR")
    os.environ[cache_key] = os.path.join(tmp, "cache")

    class TrackingProvider(serving.LocalCheckpointProvider):
        """LocalCheckpointProvider remembering every spawn, so the
        cold-start acceptance check covers retired replicas too."""

        spawned = []

        def spawn(self):
            name, server = super().spawn()
            self.spawned.append((name, server))
            return name, server

    registry = serving.ReplicaRegistry(ttl_ms=2000)
    # the seed replica primes the compile cache and ships its AOT
    # bundle, so every scale-out spawn warms deserialize-only
    seed_srv = serving.InferenceServer.from_checkpoint(
        prefix, 1, shapes, attach_aot=False, **server_kw)
    seed_srv.save_aot_bundle(prefix, 1)
    stop_seed_beat = serving.start_heartbeater(registry, "seed0", seed_srv,
                                               interval_ms=200)
    slos = {"interactive": serving.SLOClass("interactive", deadline_ms=5000,
                                            priority=0, sheddable=False),
            "batch": serving.SLOClass("batch", priority=1, sheddable=True)}
    routers = [serving.Router(registry=registry, registry_sync_ms=50,
                              slo_classes=dict(slos), seed=seed + i,
                              retries=3)
               for i in range(2)]
    provider = TrackingProvider(prefix, 1, shapes, registry=registry,
                                attach_aot=True, **server_kw)
    autoscaler = serving.Autoscaler(
        routers[0], provider, min_replicas=1, max_replicas=max_replicas,
        interval_ms=50, out_pressure=0.3, in_pressure=0.05, hysteresis=2,
        cooldown_ms=300, drain_timeout_ms=10000)
    autoscaler.start()

    X = rng.randn(8, in_dim).astype(np.float32)
    alive = [True, True]  # routers[1] is killed mid-flood
    phase = ["low"]
    stop_evt = threading.Event()
    failures = []
    served = [0]
    peak = [1]

    def one_request(tid, i):
        """End-to-end client call: bounded retry over the replicated
        front door (a killed router or a 429/overload answer means
        back off and go to the other one — the documented contract)."""
        deadline = time.monotonic() + 10.0
        last = None
        while time.monotonic() < deadline:
            for k in range(2):
                r = (tid + i + k) % 2
                if not alive[r]:
                    continue
                try:
                    routers[r].predict(slo="interactive", deadline_ms=5000,
                                       data=X[i % len(X)])
                    served[0] += 1
                    return True
                except Exception as exc:
                    last = exc
            time.sleep(0.01)
        failures.append(repr(last))
        return False

    def load(tid):
        i = 0
        while not stop_evt.is_set():
            if phase[0] == "low":
                one_request(tid, i)
                i += 1
                time.sleep(0.05)
            else:  # flood: open-loop burst through the front door
                futs = []
                for _ in range(4):
                    r = 0 if not alive[1] else (tid + i) % 2
                    try:
                        futs.append(routers[r].submit(
                            slo="interactive", deadline_ms=5000,
                            data=X[i % len(X)]))
                    except Exception:
                        one_request(tid, i)
                    i += 1
                for f in futs:
                    try:
                        f.result()
                        served[0] += 1
                    except Exception:
                        one_request(tid, i)

    def active_replicas():
        sig = routers[0].signals()
        return sig["replicas"] - sig["draining"]

    deadline = time.monotonic() + timeout
    ok = True
    threads = [threading.Thread(target=load, args=(t,), daemon=True)
               for t in range(load_threads)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.8)  # diurnal trough: fleet must hold at 1
        print("chaos_run: flash crowd begins (replicas=%d)"
              % active_replicas(), file=sys.stderr, flush=True)
        phase[0] = "flood"
        while time.monotonic() < deadline:
            peak[0] = max(peak[0], active_replicas())
            if peak[0] >= 2:
                break
            time.sleep(0.05)
        if peak[0] < 2:
            print("chaos_run: fleet never scaled out under the flood",
                  file=sys.stderr, flush=True)
            ok = False
        # kill one front door mid-flood: clients must fail over
        alive[1] = False
        routers[1].close()
        print("chaos_run: router 1 killed mid-flood (replicas=%d)"
              % active_replicas(), file=sys.stderr, flush=True)
        t_flood_end = time.monotonic() + 1.0
        while time.monotonic() < min(t_flood_end, deadline):
            peak[0] = max(peak[0], active_replicas())
            time.sleep(0.05)
        phase[0] = "low"
        print("chaos_run: flash crowd over (peak replicas=%d); cooling"
              % peak[0], file=sys.stderr, flush=True)
        while time.monotonic() < deadline:
            if active_replicas() <= 1 and not autoscaler.owned():
                break
            time.sleep(0.1)
        else:
            print("chaos_run: fleet never scaled back in",
                  file=sys.stderr, flush=True)
            ok = False
        stop_evt.set()
        for t in threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        stop_evt.set()
        autoscaler.stop(retire_owned=True)
        for r, rt in enumerate(routers):
            if alive[r]:
                rt.close()
        stop_seed_beat()
        seed_srv.stop(drain=True)
        registry.close()
        if cache_prev is None:
            os.environ.pop(cache_key, None)
        else:
            os.environ[cache_key] = cache_prev

    if failures:
        print("chaos_run: %d client requests failed end to end (first: %s)"
              % (len(failures), failures[:3]), file=sys.stderr, flush=True)
        ok = False
    snap = routers[0].metrics.snapshot()
    violations = snap["expired"].get("interactive", 0) + \
        snap["shed"].get("interactive", 0)
    if violations:
        print("chaos_run: %d interactive-SLO violations" % violations,
              file=sys.stderr, flush=True)
        ok = False
    scale_outs = [e for e in autoscaler.events
                  if e["op"] == "scale_out" and e["ok"]]
    scale_ins = [e for e in autoscaler.events
                 if e["op"] == "scale_in" and e["ok"]]
    if not scale_outs or not scale_ins:
        print("chaos_run: missing scale events (out=%d in=%d)"
              % (len(scale_outs), len(scale_ins)),
              file=sys.stderr, flush=True)
        ok = False
    cold = {n: s.cold_bucket_runs() for n, s in TrackingProvider.spawned}
    if any(cold.values()):
        print("chaos_run: scaled-out replicas served cold buckets: %s"
              % cold, file=sys.stderr, flush=True)
        ok = False
    if not TrackingProvider.spawned:
        print("chaos_run: autoscaler never spawned a replica",
              file=sys.stderr, flush=True)
        ok = False
    if ok:
        print("chaos_run: served %d requests, 0 failed, 0 SLO violations; "
              "fleet 1→%d→1 (%d scale-outs, %d scale-ins), %d warm spawns "
              "with 0 cold buckets"
              % (served[0], peak[0], len(scale_outs), len(scale_ins),
                 len(cold)), file=sys.stderr, flush=True)
        shutil.rmtree(tmp, ignore_errors=True)
    else:
        print("chaos_run: artifacts kept at %s" % tmp,
              file=sys.stderr, flush=True)
    return ok


def run_decode_storm(seed, timeout=120.0, replicas=2, load_threads=3,
                     streams_per_thread=6):
    """Generative-serving probe, in-process: a Router streams token
    generations (``Router.generate`` — continuous batching + paged KV on
    every replica) under open-loop load from ``load_threads`` clients
    while one replica is hard-killed mid-storm (the seed picks the
    victim and the kill point).  Streams running on the victim must
    resume on a survivor by re-prefilling prompt + emitted tokens —
    greedy decode is deterministic, so every client transcript must be
    bit-identical to the single-engine reference.  Passes when zero
    streams failed, every transcript matched, TTFT p99 stayed bounded,
    and the survivors' decode loops performed zero post-warmup XLA
    compiles."""
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.serving.metrics import _percentile

    V, layers, heads, hid, S = 64, 2, 2, 32, 32
    rng = np.random.RandomState(seed)
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=layers,
                                       num_heads=heads, hidden=hid,
                                       seq_len=S)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    params = {
        name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
        for name, shp in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "softmax_label")}
    spec = dict(vocab_size=V, num_layers=layers, num_heads=heads,
                hidden=hid, max_seq_len=S, lane_buckets=(1, 2, 4),
                page_size=4, num_pages=48, prefill_len_buckets=(8, 16, 32))

    victim_idx = seed % replicas
    kill_after = 4 + seed % 5  # streams completed before the kill
    print("chaos_run: decode-storm seed %d: victim r%d dies after %d "
          "streams, %d replicas x %d clients"
          % (seed, victim_idx, kill_after, replicas, load_threads),
          file=sys.stderr, flush=True)

    srvs = [serving.InferenceServer(
        net, params, {"data": (4, S), "softmax_label": (4, S)},
        max_wait_us=1000, generator_spec=dict(spec))
        for _ in range(replicas)]
    router = serving.Router(srvs, seed=seed, retries=3)

    # greedy decode is deterministic: one reference engine's transcript
    # is THE correct answer for every (prompt, max_new) the storm sends
    ref_engine = mx.generation.DecodeEngine(params, **spec)
    prompts = []
    for i in range(8):
        plen = 2 + int(rng.randint(0, 10))
        prompts.append(([int(t) for t in rng.randint(0, V, size=plen)],
                        4 + int(rng.randint(0, 8))))
    reference = {i: ref_engine.generate(p, n)
                 for i, (p, n) in enumerate(prompts)}
    ref_engine.stop()

    stop_evt = threading.Event()
    failures = []
    mismatches = []
    ttfts = []
    completed = [0]
    lock = threading.Lock()

    def load(tid):
        i = tid
        while not stop_evt.is_set():
            pi = i % len(prompts)
            prompt, max_new = prompts[pi]
            try:
                t0 = time.monotonic()
                toks = []
                for tok in router.generate(prompt, max_new,
                                           request_id="storm-%d-%d"
                                           % (tid, i)):
                    if not toks:
                        with lock:
                            ttfts.append((time.monotonic() - t0) * 1e3)
                    toks.append(tok)
                if toks != reference[pi]:
                    with lock:
                        mismatches.append((pi, toks, reference[pi]))
                with lock:
                    completed[0] += 1
            except Exception as exc:
                with lock:
                    failures.append(repr(exc))
            i += load_threads

    deadline = time.monotonic() + timeout
    ok = True
    threads = [threading.Thread(target=load, args=(t,), daemon=True)
               for t in range(load_threads)]
    try:
        for t in threads:
            t.start()
        while time.monotonic() < deadline and completed[0] < kill_after:
            time.sleep(0.02)
        print("chaos_run: killing replica r%d mid-storm (%d streams done)"
              % (victim_idx, completed[0]), file=sys.stderr, flush=True)
        srvs[victim_idx].stop(drain=False)
        target = completed[0] + load_threads * streams_per_thread
        while time.monotonic() < deadline and completed[0] < target:
            time.sleep(0.05)
        stop_evt.set()
        for t in threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        stop_evt.set()
        router.close(stop_backends=True)

    snap = router.metrics.snapshot()
    if failures:
        print("chaos_run: %d streams failed (first: %s)"
              % (len(failures), failures[:3]), file=sys.stderr, flush=True)
        ok = False
    if mismatches:
        pi, got, want = mismatches[0]
        print("chaos_run: %d transcript mismatches (prompt %d: got %s "
              "want %s) — the resume duplicated or dropped tokens"
              % (len(mismatches), pi, got, want),
              file=sys.stderr, flush=True)
        ok = False
    if completed[0] < kill_after + 1:
        print("chaos_run: storm too short (%d streams) to cover the kill"
              % completed[0], file=sys.stderr, flush=True)
        ok = False
    p99 = _percentile(sorted(ttfts), 0.99) if ttfts else None
    if p99 is None or p99 > 30000.0:
        print("chaos_run: TTFT p99 unbounded (%s ms over %d streams)"
              % (p99, len(ttfts)), file=sys.stderr, flush=True)
        ok = False
    cold = sum(s._generator.cold_decode_runs()
               for i, s in enumerate(srvs) if i != victim_idx)
    if cold:
        print("chaos_run: %d post-warmup decode recompiles on survivors"
              % cold, file=sys.stderr, flush=True)
        ok = False
    if ok:
        print("chaos_run: %d streams completed, 0 failed, 0 mismatches; "
              "%d mid-stream resumes; TTFT p50/p99 %.1f/%.1f ms; 0 cold "
              "decode steps"
              % (completed[0], snap["stream_resumes"],
                 _percentile(sorted(ttfts), 0.50), p99),
              file=sys.stderr, flush=True)
    return ok


def run_prefix_storm(seed, timeout=120.0, replicas=2, load_threads=3,
                     streams_per_thread=5):
    """Prefix-cache/speculation probe, in-process: every client hammers
    prompts sharing one hot system-style prefix against a Router whose
    replicas run the copy-on-write prefix cache AND a draft model,
    while the fault plane fails prefix lookups and draft verifies
    (``generation.prefix.lookup`` / ``generation.draft.verify`` ioerr)
    and one replica is hard-killed mid-storm.  A lookup fault must
    degrade to a cache miss and a verify fault to a plain decode step —
    never to a wrong token: greedy decode is deterministic, so every
    transcript must be bit-identical to an uncached, non-speculative
    reference engine.  Passes when zero streams failed, every
    transcript matched, the cache actually served hits under the fault
    storm, survivors did zero post-warmup compiles, and — after
    shutdown — every replica's pool refcounts returned to zero (no
    leaked shared pages)."""
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import faults as mx_faults
    from mxnet_tpu import serving

    V, layers, heads, hid, S = 64, 2, 2, 32, 32
    rng = np.random.RandomState(seed)
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=layers,
                                       num_heads=heads, hidden=hid,
                                       seq_len=S)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    params = {
        name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
        for name, shp in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "softmax_label")}
    spec = dict(vocab_size=V, num_layers=layers, num_heads=heads,
                hidden=hid, max_seq_len=S, lane_buckets=(1, 2, 4),
                page_size=4, num_pages=40, prefill_len_buckets=(8, 16, 32))
    gen_spec = dict(spec, prefix_cache_pages=12,
                    draft={"params": params, "num_layers": layers,
                           "num_heads": heads, "hidden": hid, "k": 2})

    victim_idx = seed % replicas
    kill_after = 4 + seed % 5
    print("chaos_run: prefix-storm seed %d: victim r%d dies after %d "
          "streams; prefix lookups and draft verifies fault at 25%%"
          % (seed, victim_idx, kill_after), file=sys.stderr, flush=True)

    # one hot shared prefix, per-prompt unique tails — heavy page
    # sharing plus COW splits the moment the tails diverge
    shared = [int(t) for t in rng.randint(0, V, size=12)]
    prompts = []
    for i in range(8):
        tail = [int(t) for t in rng.randint(0, V, size=int(
            rng.randint(0, 7)))]
        prompts.append((shared + tail, 4 + int(rng.randint(0, 5))))

    # greedy reference: NO cache, NO draft, NO faults — THE transcript
    ref_engine = mx.generation.DecodeEngine(params, **spec)
    reference = {i: ref_engine.generate(p, n)
                 for i, (p, n) in enumerate(prompts)}
    ref_engine.stop()

    srvs = [serving.InferenceServer(
        net, params, {"data": (4, S), "softmax_label": (4, S)},
        max_wait_us=1000, generator_spec=dict(gen_spec))
        for _ in range(replicas)]
    engines = [s._generator for s in srvs]
    router = serving.Router(srvs, seed=seed, retries=3)

    stop_evt = threading.Event()
    failures = []
    mismatches = []
    completed = [0]
    lock = threading.Lock()

    def load(tid):
        i = tid
        while not stop_evt.is_set():
            pi = i % len(prompts)
            prompt, max_new = prompts[pi]
            try:
                toks = list(router.generate(prompt, max_new,
                                            request_id="pstorm-%d-%d"
                                            % (tid, i)))
                if toks != reference[pi]:
                    with lock:
                        mismatches.append((pi, toks, reference[pi]))
                with lock:
                    completed[0] += 1
            except Exception as exc:
                with lock:
                    failures.append(repr(exc))
            i += load_threads

    deadline = time.monotonic() + timeout
    ok = True
    threads = [threading.Thread(target=load, args=(t,), daemon=True)
               for t in range(load_threads)]
    fault_spec = ("generation.prefix.lookup:ioerr=0.25;"
                  "generation.draft.verify:ioerr=0.25")
    try:
        with mx_faults.inject(fault_spec, seed=seed):
            for t in threads:
                t.start()
            while time.monotonic() < deadline and \
                    completed[0] < kill_after:
                time.sleep(0.02)
            print("chaos_run: killing replica r%d mid-storm (%d streams "
                  "done)" % (victim_idx, completed[0]),
                  file=sys.stderr, flush=True)
            srvs[victim_idx].stop(drain=False)
            target = completed[0] + load_threads * streams_per_thread
            while time.monotonic() < deadline and completed[0] < target:
                time.sleep(0.05)
            stop_evt.set()
            for t in threads:
                t.join(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        stop_evt.set()
        router.close(stop_backends=True)

    if failures:
        print("chaos_run: %d streams failed (first: %s)"
              % (len(failures), failures[:3]), file=sys.stderr, flush=True)
        ok = False
    if mismatches:
        pi, got, want = mismatches[0]
        print("chaos_run: %d transcript mismatches (prompt %d: got %s "
              "want %s) — a degraded cache/draft path changed tokens"
              % (len(mismatches), pi, got, want),
              file=sys.stderr, flush=True)
        ok = False
    if completed[0] < kill_after + 1:
        print("chaos_run: storm too short (%d streams) to cover the kill"
              % completed[0], file=sys.stderr, flush=True)
        ok = False
    snaps = [e.pool.snapshot() for e in engines]
    hits = sum(s["prefix_hits"] for s in snaps)
    if not hits:
        print("chaos_run: prefix cache never hit — the storm did not "
              "exercise sharing", file=sys.stderr, flush=True)
        ok = False
    leaked = {i: s["total_refcount"] for i, s in enumerate(snaps)
              if s["total_refcount"]}
    dleaked = {i: e._draft_pool.total_refcount()
               for i, e in enumerate(engines)
               if e._draft_pool is not None
               and e._draft_pool.total_refcount()}
    if leaked or dleaked:
        print("chaos_run: leaked shared pages after shutdown "
              "(target %s draft %s)" % (leaked, dleaked),
              file=sys.stderr, flush=True)
        ok = False
    cold = sum(engines[i].cold_decode_runs()
               for i in range(replicas) if i != victim_idx)
    if cold:
        print("chaos_run: %d post-warmup decode recompiles on survivors"
              % cold, file=sys.stderr, flush=True)
        ok = False
    if ok:
        fb = sum(e.metrics.spec_fallbacks.value for e in engines)
        cow = sum(s["cow_copies"] for s in snaps)
        print("chaos_run: %d streams completed, 0 failed, 0 mismatches; "
              "%d prefix hits, %d COW splits, %d verify-fault fallbacks; "
              "refcounts drained to 0"
              % (completed[0], hits, cow, fb),
              file=sys.stderr, flush=True)
    return ok


def run_sparse_replay(seed, timeout=120.0):
    """Exactly-once probe for the sparse wire: one row-sparse push whose
    ACK the server drops (``kv.server.send:drop=1@#1``).  The client sees
    a dead connection and replays the request under the SAME idempotency
    token; the server's dedup window must recognize it and answer from
    the recorded reply without re-applying.  Passes when the retried run
    applied exactly one row push and its table rows are bit-identical to
    an uninterrupted control run."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from mxnet_tpu import faults
    from mxnet_tpu.kvstore_server import ServerClient, start_server

    rng = np.random.RandomState(seed)
    ids = np.unique(rng.randint(0, 1000, size=64)).astype(np.int64)
    vals = rng.randn(ids.size, 8).astype(np.float32)
    meta = {"num_rows": 1000, "row_shape": (8,), "init": ("zeros",),
            "dtype": "float32", "num_servers": 1, "server_index": 0}

    def one_run(drop_ack):
        srv = start_server(port=0)
        cli = ServerClient(*srv.addr)
        try:
            cli.init_table("emb", meta)
            if drop_ack:
                # installed only around the push so fire #1 on
                # kv.server.send is exactly the push_rows ACK
                with faults.inject("kv.server.send:drop=1@#1", seed):
                    cli.push_rows("emb", ids, vals)
            else:
                cli.push_rows("emb", ids, vals)
            applied = srv.applied_row_pushes
            rows = cli.pull_rows("emb", ids)
            return applied, rows
        finally:
            try:
                cli.stop_server()
            except Exception:
                pass
            cli.close()

    applied_r, rows_r = one_run(drop_ack=True)
    applied_c, rows_c = one_run(drop_ack=False)
    ok = True
    if applied_r != 1:
        print("chaos_run: sparse-replay applied %d row pushes after the "
              "dropped-ACK retry, expected exactly 1" % applied_r,
              file=sys.stderr, flush=True)
        ok = False
    if applied_c != 1:
        print("chaos_run: control run applied %d row pushes, expected 1"
              % applied_c, file=sys.stderr, flush=True)
        ok = False
    if rows_r.tobytes() != rows_c.tobytes():
        print("chaos_run: sparse-replay table rows diverge from the "
              "uninterrupted control run (replay was not exactly-once)",
              file=sys.stderr, flush=True)
        ok = False
    if ok:
        print("chaos_run: sparse-replay ok: dropped ACK, 1 application, "
              "%d rows bit-identical to control" % ids.size,
              file=sys.stderr, flush=True)
    return ok


def run_sdc_rollback(seed, timeout=120.0):
    """Silent-data-corruption containment, both halves of the guardian:

    Training half: the same seeded 2-epoch fit() runs twice — a control
    run, and a run with ``guardian.grad:bitflip@#N`` installed (the seed
    picks N, i.e. which gradient tensor of which step takes an exponent
    bit-flip).  The guardian must catch the poisoned step (the f32
    grad-norm square-sum overflows to inf), roll back to the last-good
    ring snapshot — params, updater state, framework PRNG, and the
    data-iterator cursor — and replay.  Passes when exactly one rollback
    fired and the final params are bit-identical to the control run.

    Fleet half: a kvstore server takes a clean dense push, then a
    NaN-poisoned push from another rank.  The poisoned push must be
    NACKed (typed NonFiniteGradientError at the client, counted per rank
    in mxtpu_kvsrv_rejected_pushes_total) and the stored value must stay
    bit-identical to the clean-only state — containment, not detection
    after the fact."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    env = {"MXNET_FUSED_STEP": "0",     # corruption rewrites host grad
                                        # buffers, which forces the eager
                                        # path — the control run must
                                        # match it for bit-identity
           "MXNET_GUARDIAN": "1",
           "MXNET_GUARDIAN_SKIP_MAX": "0",      # straight to rollback
           "MXNET_GUARDIAN_REWARM_STEPS": "0",
           "MXNET_GUARDIAN_RING": "2",
           "MXNET_GUARDIAN_SNAPSHOT_EVERY": "4"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        import numpy as np

        import mxnet_tpu as mx
        from mxnet_tpu import faults, guardian, telemetry
        from mxnet_tpu.kvstore_server import (NonFiniteGradientError,
                                              ServerClient, start_server)

        # the env var only matters at import; in-process (pytest) the
        # module is long imported, so flip the gate directly
        guardian.enable()

        def one_fit(spec):
            guardian.reset_stats()
            if spec:
                faults.install(faults.FaultPlan(spec, seed=seed))
            else:
                faults.uninstall()
            try:
                data = mx.sym.Variable("data")
                net = mx.sym.FullyConnected(data, name="fc1",
                                            num_hidden=16)
                net = mx.sym.Activation(net, name="relu1",
                                        act_type="relu")
                net = mx.sym.FullyConnected(net, name="fc2", num_hidden=4)
                net = mx.sym.SoftmaxOutput(net, name="softmax")
                mod = mx.mod.Module(net, context=mx.cpu())
                mx.random.seed(3)
                np.random.seed(3)
                rng = np.random.RandomState(7)
                x = rng.randn(64, 10).astype(np.float32)
                y = rng.randint(0, 4, (64,)).astype(np.float32)
                it = mx.io.NDArrayIter(x, y, batch_size=8, shuffle=True,
                                       label_name="softmax_label")
                mod.fit(it, num_epoch=2, optimizer="sgd",
                        optimizer_params={"learning_rate": 0.05,
                                          "momentum": 0.9},
                        initializer=mx.init.Xavier(), eval_metric="acc")
                args, _ = mod.get_params()
                return ({k: v.asnumpy() for k, v in args.items()},
                        guardian.stats())
            finally:
                faults.uninstall()

        # 16 steps x 4 gradient tensors -> 64 corruption polls; the seed
        # picks which one flips (any step of either epoch), and #1 — the
        # very first gradient, before the spike detector has any history
        # — is always exercised too (the acceptance-pinned worst case)
        n = 1 + np.random.RandomState(seed).randint(64)
        clean, st_clean = one_fit(None)

        ok = True
        if st_clean["rollbacks"] != 0 or st_clean["anomalies"] != 0:
            print("chaos_run: sdc-rollback control run tripped the "
                  "guardian: %r" % (st_clean,), file=sys.stderr, flush=True)
            ok = False
        for idx in sorted({1, n}):
            inj, st_inj = one_fit("guardian.grad:bitflip@#%d" % idx)
            if st_inj["anomalies"] < 1 or st_inj["rollbacks"] != 1:
                print("chaos_run: sdc-rollback injected run (bitflip@#%d) "
                      "expected 1 rollback, got %r" % (idx, st_inj),
                      file=sys.stderr, flush=True)
                ok = False
            diverged = [k for k in clean
                        if clean[k].tobytes() != inj[k].tobytes()]
            if diverged:
                print("chaos_run: sdc-rollback bitflip@#%d replay diverged "
                      "from control in %s" % (idx, ", ".join(sorted(diverged))),
                      file=sys.stderr, flush=True)
                ok = False
        if ok:
            print("chaos_run: sdc-rollback ok: bitflip@#{1,%d} detected, "
                  "1 rollback each, replays bit-identical to control" % n,
                  file=sys.stderr, flush=True)

        # ---- fleet half: server-side NACK containment
        telemetry.enable(trace=False)
        srv = start_server(port=0)
        cli = ServerClient(*srv.addr)
        try:
            cli.init(0, np.zeros(8, dtype=np.float32))
            good = np.random.RandomState(seed + 1).randn(8) \
                .astype(np.float32)
            cli.push(0, good, rank=0)
            want = cli.pull(0).tobytes()
            # the registry is process-global: under --seeds sweeps the
            # counter carries over from earlier iterations, so assert
            # the delta, not the absolute count
            rej0 = telemetry.registry().snapshot().get(
                "mxtpu_kvsrv_rejected_pushes_total", {}).get("3", 0)
            bad = good.copy()
            bad[int(seed) % 8] = np.nan
            try:
                cli.push(0, bad, rank=3)
                print("chaos_run: sdc-rollback poisoned push was ACKed",
                      file=sys.stderr, flush=True)
                ok = False
            except NonFiniteGradientError:
                pass
            if cli.pull(0).tobytes() != want:
                print("chaos_run: sdc-rollback NACKed push mutated the "
                      "store", file=sys.stderr, flush=True)
                ok = False
            rej = telemetry.registry().snapshot().get(
                "mxtpu_kvsrv_rejected_pushes_total", {})
            if srv.rejected_pushes != 1 or rej.get("3", 0) - rej0 != 1:
                print("chaos_run: sdc-rollback rejected-push accounting "
                      "off: server=%d telemetry=%r"
                      % (srv.rejected_pushes, rej),
                      file=sys.stderr, flush=True)
                ok = False
            elif ok:
                print("chaos_run: sdc-rollback ok: poisoned push NACKed, "
                      "store bit-identical, rank 3 counted",
                      file=sys.stderr, flush=True)
        finally:
            try:
                cli.stop_server()
            except Exception:
                pass
            cli.close()
            telemetry.disable()
        return ok
    finally:
        try:
            from mxnet_tpu import guardian as _g
            _g.disable()
        except Exception:
            pass
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def run_tenant_storm(seed, timeout=120.0, good_threads=2):
    """Multi-tenant platform probe, in-process: a FrontDoor over a
    ModelManager serves three models on a pool with room for two while
    one tenant ('storm') floods its model in a tight loop and its
    neighbours ('good0'/'good1') run steady interactive load.  Mid-storm
    the victim model is paged out, then hard-killed mid-migration (its
    server stopped out from under the router without deregistration) —
    each time, demand paging must fault it back in WARM from its AOT
    bundle.  Passes when the storm tenant was shed at the door (429s
    with Retry-After), the good tenants saw ZERO quota sheds and zero
    end-to-end failures, and every post-storm fault-in served with
    ``cold_bucket_runs() == 0``."""
    import shutil
    import tempfile
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.platform import (DevicePool, FrontDoor, ModelManager,
                                    ModelSpec, TenantQuotaExceededError)

    tmp = tempfile.mkdtemp(prefix="chaos-tenantstorm-")
    envs = {"MXNET_COMPILE_CACHE_DIR": os.path.join(tmp, "cache"),
            "MXNET_PLATFORM_MIN_RESIDENT_S": "0"}
    prev = {k: os.environ.get(k) for k in envs}
    os.environ.update(envs)

    in_dim = 6
    rng = np.random.RandomState(seed)
    specs = []
    for i, (name, tenant) in enumerate((("victim", "storm"),
                                        ("good-a", "good0"),
                                        ("good-b", "good1"))):
        hid = 3 + i  # distinct programs: no cross-model cache riding
        net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                    num_hidden=hid, name="fc")
        params = {"fc_weight": mx.nd.array(
                      rng.randn(hid, in_dim).astype(np.float32)),
                  "fc_bias": mx.nd.array(rng.randn(hid)
                                         .astype(np.float32))}
        prefix = os.path.join(tmp, name)
        mx.model.save_checkpoint(prefix, 1, net, params, {})
        specs.append(ModelSpec(
            name, prefix, 1, {"data": (1, in_dim)}, tenant=tenant,
            param_bytes=1000,
            server_kwargs={"buckets": (1,), "max_wait_us": 1000,
                           "max_queue": 256}))

    total = specs[0].footprint()["total"]
    pool = DevicePool(num_devices=1,
                      bytes_per_device=int(2 * total * 1.2))
    mgr = ModelManager(pool)
    for s in specs:
        mgr.register_model(s)
    door = FrontDoor(mgr)
    x = np.zeros(in_dim, np.float32)
    stop_evt = threading.Event()
    good_failures = []
    good_served = [0]
    storm_stats = {"admitted": 0, "shed": 0}
    deadline = time.monotonic() + timeout
    ok = True

    def good_load(tid):
        model = ("good-a", "good-b")[tid % 2]
        tenant = ("good0", "good1")[tid % 2]
        while not stop_evt.is_set():
            t_req = time.monotonic() + 10.0
            last = None
            while time.monotonic() < min(t_req, deadline):
                try:
                    door.predict(model, tenant=tenant, deadline_ms=5000,
                                 data=x)
                    good_served[0] += 1
                    last = None
                    break
                except TenantQuotaExceededError as exc:
                    # a neighbour's flood must NEVER shed us — fatal
                    good_failures.append("QUOTA:%r" % exc)
                    return
                except Exception as exc:  # dead replica mid-kill: retry
                    last = exc
                    time.sleep(0.02)
            if last is not None:
                good_failures.append(repr(last))
                return
            time.sleep(0.02)

    def storm_load():
        while not stop_evt.is_set():
            try:
                door.predict("victim", tenant="storm", deadline_ms=5000,
                             data=x)
                storm_stats["admitted"] += 1
            except TenantQuotaExceededError as exc:
                if exc.retry_after <= 0:
                    good_failures.append("storm retry_after <= 0")
                storm_stats["shed"] += 1
            except Exception:
                pass  # storm tenant gets no service guarantees

    threads = [threading.Thread(target=good_load, args=(t,), daemon=True)
               for t in range(good_threads)]
    threads.append(threading.Thread(target=storm_load, daemon=True))
    try:
        # the storm tenant is rate-limited; its neighbours are not
        door.quotas.set_quota("storm", rate=25.0, burst=5.0)
        for name, d in (("victim", 5.0), ("good-a", 4.0)):
            mgr.record_demand(name, d)
        mgr.replan()  # victim + good-a resident; good-b demand-pages in
        for t in threads:
            t.start()
        time.sleep(1.0)

        # chaos 1: the victim model is paged out mid-storm — requests
        # in flight drain, the next one demand-pages it back in warm
        mgr.page_out("victim")
        print("chaos_run: victim paged out mid-storm",
              file=sys.stderr, flush=True)
        time.sleep(1.0)

        # chaos 2: hard-kill mid-migration — the victim's server dies
        # out from under the router (no dereg, no drain), exactly what
        # a preempted device looks like; the platform must recover it
        srv = mgr.server_for("victim")
        if srv is not None:
            srv.stop(drain=False)
        mgr.page_out("victim")  # reconcile the corpse
        print("chaos_run: victim replica hard-killed mid-migration",
              file=sys.stderr, flush=True)
        time.sleep(1.5)
        # in-quota storm traffic must have demand-paged the victim back
        # in — WARM, from the bundle its first page-out wrote
        srv = mgr.server_for("victim")
        victim_cold_runs = None if srv is None else srv.cold_bucket_runs()
        stop_evt.set()
        for t in threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        stop_evt.set()
        door.close()
        mgr.close()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    if good_failures:
        print("chaos_run: good-tenant violations: %s"
              % good_failures[:3], file=sys.stderr, flush=True)
        ok = False
    snap = door.quotas.snapshot()
    for tenant in ("good0", "good1"):
        if snap.get(tenant, {}).get("shed", 0):
            print("chaos_run: tenant %s was shed by the storm" % tenant,
                  file=sys.stderr, flush=True)
            ok = False
    if not storm_stats["shed"]:
        print("chaos_run: storm tenant was never shed",
              file=sys.stderr, flush=True)
        ok = False
    if not storm_stats["admitted"]:
        print("chaos_run: storm tenant never got its in-quota share",
              file=sys.stderr, flush=True)
        ok = False
    if victim_cold_runs != 0:
        print("chaos_run: victim's post-kill fault-in was not warm "
              "(cold_bucket_runs=%r)" % (victim_cold_runs,),
              file=sys.stderr, flush=True)
        ok = False
    if good_served[0] < 20:
        print("chaos_run: good tenants served only %d requests"
              % good_served[0], file=sys.stderr, flush=True)
        ok = False
    # every fault-in after the first left/loaded an AOT bundle: the
    # recovery path must have been warm (metrics survive close())
    fault_ins = sum(
        int(float(line.rsplit(None, 1)[1]))
        for line in mgr.metrics.render_prometheus().splitlines()
        if line.startswith("mxtpu_platform_fault_ins_total{"))
    if fault_ins < 3:
        print("chaos_run: expected >= 3 victim fault-ins, saw %d"
              % fault_ins, file=sys.stderr, flush=True)
        ok = False
    if ok:
        print("chaos_run: tenant-storm ok: good tenants served %d with "
              "0 sheds and 0 failures through page-out + hard-kill; "
              "storm admitted %d, shed %d; %d fault-ins"
              % (good_served[0], storm_stats["admitted"],
                 storm_stats["shed"], fault_ins),
              file=sys.stderr, flush=True)
        shutil.rmtree(tmp, ignore_errors=True)
    else:
        print("chaos_run: artifacts kept at %s" % tmp,
              file=sys.stderr, flush=True)
    return ok


def run_host_loss(seed, timeout=120.0, stream_threads=3):
    """Failure-domain survival probe, in-process: a FrontDoor platform
    serves three tenants on 2 hosts x 2 devices — 'chat' (generate SLO,
    2 replicas spread across hosts), 'gold' (interactive), 'bulk'
    (batch) — and every replica on one host is killed mid-stream and
    mid-fault-in (heartbeats stop WITHOUT deregistration: only the
    health plane's probe can discover the loss).  The degradation
    ladder must then (1) reap the corpses and re-fault the evicted
    interactive model WARM onto the survivors, (2) brown out the batch
    class (503 + Retry-After) while capacity is short, (3) keep every
    live chat stream bit-identical to the single-engine reference via
    mid-stream failover.  Passes when chat saw zero failures and zero
    transcript mismatches with >= 1 mid-stream resume, gold saw zero
    hard failures (its fault-in-window 503s carried a positive
    Retry-After) and recovered with zero cold-bucket runs, bulk was
    shed by the brownout, the plan generation advanced, every surviving
    placement sits on an alive device, and resident_bytes drops to
    zero at close.  Returns a summary dict (``ok`` + per-tenant failure
    counts) that main() folds into the machine-readable summary JSON."""
    import shutil
    import tempfile
    import threading
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import faults, telemetry
    from mxnet_tpu.platform import (BrownoutError, DevicePool,
                                    FaultInProgressError, FrontDoor,
                                    HealthPlane, ModelManager, ModelSpec)
    from mxnet_tpu.serving.batcher import ServerClosedError
    from mxnet_tpu.serving.registry import ReplicaRegistry
    from mxnet_tpu.serving.router import NoReplicaAvailableError

    tmp = tempfile.mkdtemp(prefix="chaos-hostloss-")
    envs = {"MXNET_COMPILE_CACHE_DIR": os.path.join(tmp, "cache"),
            "MXNET_PLATFORM_MIN_RESIDENT_S": "0",
            "MXNET_PLATFORM_DRAIN_MS": "2000",
            "MXNET_SERVING_REGISTRY_HEARTBEAT_MS": "25"}
    prev = {k: os.environ.get(k) for k in envs}
    os.environ.update(envs)
    telemetry.enable()

    V, S, in_dim = 32, 16, 4
    rng = np.random.RandomState(seed)
    # prefill buckets must cover prompt + emitted: a mid-stream resume
    # re-prefills the whole transcript so far
    gspec = dict(vocab_size=V, num_layers=1, num_heads=2, hidden=16,
                 max_seq_len=S, lane_buckets=(1, 2), page_size=4,
                 num_pages=16, prefill_len_buckets=(8, 16))
    lm = mx.models.get_transformer_lm(vocab_size=V, num_layers=1,
                                      num_heads=2, hidden=16, seq_len=S)
    arg_shapes, _, _ = lm.infer_shape(data=(1, S), softmax_label=(1, S))
    lm_params = {
        name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
        for name, shp in zip(lm.list_arguments(), arg_shapes)
        if name not in ("data", "softmax_label")}
    lm_prefix = os.path.join(tmp, "chat")
    mx.model.save_checkpoint(lm_prefix, 1, lm, lm_params, {})
    fc_prefix = {}
    for name in ("gold", "bulk"):
        net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                    num_hidden=2, name="fc")
        params = {"fc_weight": mx.nd.array(
                      rng.randn(2, in_dim).astype(np.float32)),
                  "fc_bias": mx.nd.array(rng.randn(2).astype(np.float32))}
        fc_prefix[name] = os.path.join(tmp, name)
        mx.model.save_checkpoint(fc_prefix[name], 1, net, params, {})

    # greedy decode is deterministic: one reference engine's transcript
    # is THE correct answer for every (prompt, max_new) the storm sends
    ref_engine = mx.generation.DecodeEngine(lm_params, **gspec)
    prompts = []
    for i in range(6):
        plen = 2 + int(rng.randint(0, 6))
        prompts.append(([int(t) for t in rng.randint(0, V, size=plen)],
                        4 + int(rng.randint(0, 6))))
    reference = {i: ref_engine.generate(p, n)
                 for i, (p, n) in enumerate(prompts)}
    ref_engine.stop()

    specs = [
        ModelSpec("chat", lm_prefix, 1,
                  {"data": (1, S), "softmax_label": (1, S)},
                  tenant="chat", slo="generate", replicas=2,
                  param_bytes=1000, generator_spec=dict(gspec),
                  server_kwargs={"buckets": (1,), "max_wait_us": 1000}),
        ModelSpec("gold", fc_prefix["gold"], 1, {"data": (1, in_dim)},
                  tenant="gold", slo="interactive", param_bytes=7554,
                  server_kwargs={"buckets": (1,), "max_wait_us": 1000}),
        ModelSpec("bulk", fc_prefix["bulk"], 1, {"data": (1, in_dim)},
                  tenant="bulk", slo="batch", param_bytes=7554,
                  server_kwargs={"buckets": (1,), "max_wait_us": 1000}),
    ]
    totals = {s.name: s.footprint()["total"] for s in specs}
    if len(set(totals.values())) != 1:
        print("chaos_run: footprint mismatch %r" % (totals,),
              file=sys.stderr, flush=True)
        return {"ok": False, "notes": ["footprint mismatch"]}
    # one model-replica per device, exactly — and pin the declared
    # footprints: live cost-analysis refinement would re-scale the toy
    # byte budget mid-run
    orig_observe = ModelSpec.observe_exec_bytes
    ModelSpec.observe_exec_bytes = lambda self, nbytes: None

    pool = DevicePool(num_devices=4,
                      bytes_per_device=totals["chat"] + 1,
                      devices_per_host=2)
    reg = ReplicaRegistry(ttl_ms=400)
    mgr = ModelManager(pool, registry=reg)
    hp = mgr.attach_health(HealthPlane(pool, registry=reg, probe_fails=2))
    for s in specs:
        mgr.register_model(s)
    door = FrontDoor(mgr)
    # delayed fault-ins hold every fault-in window open ~0.4s so the
    # kill provably lands mid-fault-in and the door's 503s are
    # observable from the gold tenant's thread
    faults.install(faults.FaultPlan("platform.fault_in:delay=1@0.4",
                                    seed))

    counts = {"chat_ok": 0, "chat_fail": 0, "mismatch": 0,
              "gold_ok": 0, "gold_fail": 0, "gold_503": 0,
              "bulk_ok": 0, "bulk_shed": 0, "bulk_fail": 0}
    errs = []
    lock = threading.Lock()
    stop_evt = threading.Event()
    deadline = time.monotonic() + timeout
    x = np.zeros(in_dim, np.float32)

    def chat_load(tid):
        i = tid
        while not stop_evt.is_set() and time.monotonic() < deadline:
            pi = i % len(prompts)
            prompt, max_new = prompts[pi]
            try:
                toks = list(door.generate("chat", prompt, max_new,
                                          tenant="chat",
                                          deadline_ms=10_000))
                with lock:
                    if toks != reference[pi]:
                        counts["mismatch"] += 1
                    else:
                        counts["chat_ok"] += 1
            except (ServerClosedError, NoReplicaAvailableError,
                    FaultInProgressError):
                time.sleep(0.02)  # mid-reap race window: retryable
            except Exception as exc:
                with lock:
                    counts["chat_fail"] += 1
                    errs.append("chat: %r" % (exc,))
                time.sleep(0.05)
            i += stream_threads

    def gold_load():
        while not stop_evt.is_set() and time.monotonic() < deadline:
            try:
                door.predict("gold", tenant="gold", deadline_ms=5000,
                             data=x)
                with lock:
                    counts["gold_ok"] += 1
            except (FaultInProgressError, BrownoutError) as exc:
                with lock:
                    counts["gold_503"] += 1
                    if not exc.retry_after > 0:
                        counts["gold_fail"] += 1
                        errs.append("gold: 503 with retry_after=%r"
                                    % (exc.retry_after,))
                time.sleep(min(exc.retry_after, 0.2))
            except (ServerClosedError, NoReplicaAvailableError):
                time.sleep(0.02)  # mid-reap race window: retryable
            except Exception as exc:
                with lock:
                    counts["gold_fail"] += 1
                    errs.append("gold: %r" % (exc,))
            time.sleep(0.01)

    def bulk_load():
        while not stop_evt.is_set() and time.monotonic() < deadline:
            try:
                door.predict("bulk", tenant="bulk", slo="batch",
                             deadline_ms=5000, data=x)
                with lock:
                    counts["bulk_ok"] += 1
            except BrownoutError as exc:
                with lock:
                    counts["bulk_shed"] += 1
                    if not exc.retry_after > 0:
                        counts["bulk_fail"] += 1
                        errs.append("bulk: 503 with retry_after=%r"
                                    % (exc.retry_after,))
                time.sleep(0.05)
            except (FaultInProgressError, ServerClosedError,
                    NoReplicaAvailableError):
                time.sleep(0.02)
            except Exception as exc:
                with lock:
                    counts["bulk_fail"] += 1
                    errs.append("bulk: %r" % (exc,))
            time.sleep(0.02)

    ok = True
    notes = []

    def fail(msg):
        nonlocal ok
        ok = False
        notes.append(msg)
        print("chaos_run: host-loss: %s" % msg, file=sys.stderr,
              flush=True)

    gen1 = resumes = gold_cold = 0
    victim_dom = -1
    # two gold clients: during recovery one gets "queued" (blocks inside
    # the ladder-raced fault-in), the other observes the open window and
    # must get the honest 503 + Retry-After
    threads = ([threading.Thread(target=chat_load, args=(t,), daemon=True)
                for t in range(stream_threads)]
               + [threading.Thread(target=gold_load, daemon=True),
                  threading.Thread(target=gold_load, daemon=True),
                  threading.Thread(target=bulk_load, daemon=True)])
    try:
        for name, d in (("chat", 9.0), ("gold", 5.0), ("bulk", 1.0)):
            mgr.record_demand(name, d)
        mgr.replan()
        placed = mgr.replica_placement()
        doms = {pool.domain_of(d) for d in placed.get("chat", {}).values()}
        if doms != {0, 1}:
            fail("chat replicas not spread across hosts: %r" % (placed,))
        gen0 = mgr.plan_generation()
        # gold's host is the victim: it holds gold plus one chat replica
        victim_dom = pool.domain_of(placed["gold"][0])
        victims = [(n, i) for n, reps in placed.items()
                   for i, d in reps.items()
                   if pool.domain_of(d) == victim_dom]
        kill_after = 2 + seed % 3  # chat streams completed pre-kill
        print("chaos_run: host-loss seed %d: host %d dies (%s) after %d "
              "streams" % (seed, victim_dom,
                           ",".join("%s/r%d" % v for v in victims),
                           kill_after),
              file=sys.stderr, flush=True)
        for t in threads:
            t.start()
        while time.monotonic() < deadline and counts["chat_ok"] < kill_after:
            time.sleep(0.02)
        # "mid-stream" must be literal: hold the kill until the victim
        # chat replica has a generate stream actually in flight
        chat_vic = next(i for n, i in victims if n == "chat")
        vic_srv = mgr._servers["chat"][chat_vic]
        while time.monotonic() < deadline and \
                vic_srv._generator.active_lanes() < 1:
            time.sleep(0.002)
        pre_kill = dict(counts)
        for n, i in victims:
            mgr.kill_replica(n, replica=i)
        # only the probe can discover the loss: corpses TTL out of the
        # registry, K consecutive misses flip the domain, and the
        # ladder runs inline right here
        while time.monotonic() < deadline and \
                victim_dom not in hp.dead_domains():
            hp.probe()
            time.sleep(0.05)
        if victim_dom not in hp.dead_domains():
            fail("health plane never declared host %d dead" % victim_dom)
        while time.monotonic() < deadline and \
                mgr.server_for("gold") is None:
            time.sleep(0.05)
        srv = mgr.server_for("gold")
        if srv is None:
            fail("gold never re-faulted onto a survivor")
        else:
            gold_cold = srv.cold_bucket_runs()
            if gold_cold != 0:
                fail("gold re-fault was cold (cold_bucket_runs=%d)"
                     % gold_cold)
        # run the degraded storm until every class shows its verdict
        settle = time.monotonic() + 8.0
        while time.monotonic() < min(deadline, settle) and not (
                counts["chat_ok"] > pre_kill["chat_ok"] + stream_threads
                and counts["gold_ok"] > pre_kill["gold_ok"]
                and counts["bulk_shed"] > 0):
            time.sleep(0.05)
        stop_evt.set()
        for t in threads:
            t.join(timeout=30)
        if any(t.is_alive() for t in threads):
            fail("load threads failed to stop")

        gen1 = mgr.plan_generation()
        resumes = door.router_for("chat").metrics.snapshot()[
            "stream_resumes"]
        if not gen1 > gen0:
            fail("plan generation did not advance (%d -> %d)"
                 % (gen0, gen1))
        if counts["chat_fail"] or counts["mismatch"]:
            fail("chat streams broke: %d failures, %d mismatches"
                 % (counts["chat_fail"], counts["mismatch"]))
        if counts["chat_ok"] <= pre_kill["chat_ok"] + stream_threads:
            fail("chat barely served post-kill (%d -> %d)"
                 % (pre_kill["chat_ok"], counts["chat_ok"]))
        if resumes < 1:
            fail("no mid-stream resume was exercised")
        if counts["gold_fail"]:
            fail("gold saw %d hard failures" % counts["gold_fail"])
        if counts["gold_ok"] <= pre_kill["gold_ok"]:
            fail("gold never served after the ladder ran")
        if counts["gold_503"] < 1:
            fail("gold never saw the fault-in-window 503")
        if counts["bulk_fail"]:
            fail("bulk saw %d hard failures" % counts["bulk_fail"])
        if counts["bulk_shed"] < 1:
            fail("bulk was never browned out")
        b = door.quotas.brownout()
        if b is None:
            fail("no brownout active after capacity loss")
        if mgr.server_for("bulk") is not None:
            fail("bulk still resident on degraded capacity")
        bad = [(n, d) for n, reps in mgr.replica_placement().items()
               for d in reps.values()
               if pool.domain_of(d) == victim_dom]
        if bad:
            fail("placements still on the dead host: %r" % (bad,))
    finally:
        stop_evt.set()
        faults.uninstall()
        ModelSpec.observe_exec_bytes = orig_observe
        try:
            door.close()
            mgr.close()
        finally:
            hp.close()
            reg.close()
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    if mgr.resident_bytes() != 0:
        fail("resident_bytes=%d after close" % mgr.resident_bytes())
    for e in errs[:5]:
        print("chaos_run: host-loss error: %s" % e, file=sys.stderr,
              flush=True)
    if ok:
        print("chaos_run: host-loss ok: %d streams (0 failed, 0 "
              "mismatched, %d resumes), gold served %d with %d honest "
              "503s and a warm re-fault, bulk shed %d, plan gen %d"
              % (counts["chat_ok"], resumes, counts["gold_ok"],
                 counts["gold_503"], counts["bulk_shed"], gen1),
              file=sys.stderr, flush=True)
        shutil.rmtree(tmp, ignore_errors=True)
    else:
        print("chaos_run: artifacts kept at %s" % tmp,
              file=sys.stderr, flush=True)
    return {"ok": ok, "victim_domain": victim_dom,
            "streams": counts["chat_ok"], "stream_resumes": resumes,
            "transcript_mismatches": counts["mismatch"],
            "plan_generation": gen1,
            "tenant_failures": {"chat": counts["chat_fail"],
                                "gold": counts["gold_fail"],
                                "bulk": counts["bulk_fail"]},
            "gold_503s": counts["gold_503"],
            "bulk_shed": counts["bulk_shed"], "notes": notes}


_SCENARIOS = {"membership-churn": run_membership_churn,
              "serving-failover": run_serving_failover,
              "flash-crowd": run_flash_crowd,
              "decode-storm": run_decode_storm,
              "prefix-storm": run_prefix_storm,
              "sparse-replay": run_sparse_replay,
              "sdc-rollback": run_sdc_rollback,
              "tenant-storm": run_tenant_storm,
              "host-loss": run_host_loss}


def main():
    parser = argparse.ArgumentParser(
        description="Run a command under a deterministic fault schedule",
        usage="chaos_run.py (--spec SPEC -- command ... | --scenario NAME) "
              "(--seed N | --seeds A:B) [--timeout S]")
    parser.add_argument("--spec", default=None,
                        help="fault spec, e.g. 'kv.client.*:drop=0.3'")
    parser.add_argument("--scenario", choices=sorted(_SCENARIOS),
                        default=None,
                        help="run a built-in end-to-end scenario instead "
                             "of a command")
    parser.add_argument("--seed", type=int, default=None,
                        help="replay one seed")
    parser.add_argument("--seeds", type=str, default=None, metavar="A:B",
                        help="sweep seeds A..B-1, report pass/fail each")
    parser.add_argument("--timeout", type=float, default=None,
                        help="per-run timeout in seconds")
    parser.add_argument("--summary-json", default=None, metavar="PATH",
                        help="also write the scenario summary JSON to "
                             "this file (it always goes to stdout)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if (args.seed is None) == (args.seeds is None):
        parser.error("exactly one of --seed / --seeds is required")

    if args.seeds is not None:
        a, _, b = args.seeds.partition(":")
        seeds = range(int(a), int(b))
    else:
        seeds = [args.seed]

    if args.scenario is not None:
        if command or args.spec:
            parser.error("--scenario runs its own processes and builds its "
                         "own rank-gated spec; drop --spec and the command")
        scenario = _SCENARIOS[args.scenario]
        failures = []
        runs = []
        for seed in seeds:
            try:
                res = scenario(seed, timeout=args.timeout or 120.0)
            except Exception as exc:
                print("chaos_run: scenario %s seed %d CRASHED: %r"
                      % (args.scenario, seed, exc),
                      file=sys.stderr, flush=True)
                res = {"ok": False, "error": repr(exc)}
            # scenarios return a bare bool or a summary dict ({"ok":
            # bool, ...extra fields}) folded into the summary JSON
            if isinstance(res, dict):
                ok = bool(res.get("ok"))
                extra = {k: v for k, v in res.items() if k != "ok"}
            else:
                ok, extra = bool(res), {}
            runs.append(dict({"seed": seed, "ok": ok}, **extra))
            print("chaos_run: scenario %s seed %d -> %s"
                  % (args.scenario, seed, "ok" if ok else "FAILED"),
                  file=sys.stderr, flush=True)
            if not ok:
                failures.append(seed)
        # machine-readable verdict: one JSON object on stdout (all the
        # human chatter goes to stderr), optionally mirrored to a file
        summary = {"scenario": args.scenario, "seeds": list(seeds),
                   "ok": not failures, "failing_seeds": failures,
                   "runs": runs}
        line = json.dumps(summary, sort_keys=True, default=str)
        print(line, flush=True)
        if args.summary_json:
            with open(args.summary_json, "w") as fh:
                fh.write(line + "\n")
        if failures:
            print("chaos_run: failing seeds: %s  (replay one with --seed N)"
                  % failures, file=sys.stderr, flush=True)
            sys.exit(1)
        return

    if not command:
        parser.error("no command given (put it after --)")

    # validate the spec before burning any runtime on it
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from mxnet_tpu.faults import parse_spec

    if not args.spec:
        parser.error("--spec is required when running a command")
    parse_spec(args.spec)

    failures = []
    for seed in seeds:
        env = dict(os.environ,
                   MXNET_FAULTS_SPEC=args.spec,
                   MXNET_FAULTS_SEED=str(seed))
        print("chaos_run: seed %d, spec %r" % (seed, args.spec),
              file=sys.stderr, flush=True)
        try:
            rc = subprocess.run(command, env=env,
                                timeout=args.timeout).returncode
        except subprocess.TimeoutExpired:
            rc = -1
            print("chaos_run: seed %d TIMED OUT" % seed,
                  file=sys.stderr, flush=True)
        status = "ok" if rc == 0 else "FAILED rc=%d" % rc
        print("chaos_run: seed %d -> %s" % (seed, status),
              file=sys.stderr, flush=True)
        if rc != 0:
            failures.append(seed)
    if failures:
        print("chaos_run: failing seeds: %s  (replay one with --seed N)"
              % failures, file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
