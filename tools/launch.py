#!/usr/bin/env python
"""Launch distributed training jobs as local processes.

Parity surface: /root/reference/tools/launch.py (dmlc-core tracker) —
``launch.py -n 4 python train.py ...`` spawns N worker processes with the
DMLC env-var contract set; ``-s K`` additionally spawns K parameter-server
processes (``dist_async``: the DMLC_ROLE=server import bootstrap in
mxnet_tpu/kvstore_server.py takes over in those).  For ``dist_sync`` no
servers are needed — workers rendezvous through the jax.distributed
coordinator at DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT (kvstore_dist.py).

Launchers: ``local`` (processes on this host) and ``ssh`` (one process
per entry of ``--hostfile``, reference tools/launch.py ssh mode — the mode
that maps to TPU-VM fleets, which are plain Linux hosts).  The reference's
mpi/sge/yarn modes are intentionally out of scope: XLA collectives replace
MPI, and pod slices are provisioned by the cloud control plane, not a
Hadoop-era batch queue (see docs/how_to/deviations.md).
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def local_chips():
    """Chips attached to this host, counted from their device files: the
    launcher never initialises a JAX backend (a parent that has touched
    JAX holds every chip, and its workers then fail or hang)."""
    import glob

    return sorted(glob.glob("/dev/accel[0-9]*")
                  or glob.glob("/dev/vfio/[0-9]*"))


def chip_env(env, slot, num_workers, chips):
    """``env`` for local worker ``slot`` with ONE chip of its own (a chip
    belongs to one process at a time; with identical environments worker 0
    would take them all).  Left alone where there is nothing to assign:
    no chip on the host, the platform forced to the host
    (``JAX_PLATFORMS=cpu``), the caller assigned chips itself
    (``TPU_VISIBLE_CHIPS`` already set), or a single worker (which keeps
    every chip for its in-process device mesh)."""
    forced = env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if (not chips or forced == "cpu" or "TPU_VISIBLE_CHIPS" in env
            or num_workers == 1):
        return env
    if num_workers > len(chips):
        raise SystemExit(
            "launch.py: -n %d local workers but this host has %d chip(s) "
            "(%s): a chip belongs to one process at a time. Use -n <= %d, "
            "one worker with an in-process device mesh "
            "(context=[mx.tpu(i) ...]), or JAX_PLATFORMS=cpu."
            % (num_workers, len(chips), ", ".join(chips), len(chips)))
    env = dict(env)
    env.update({"TPU_VISIBLE_CHIPS": str(slot),
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"})
    return env


def parse_elastic(spec):
    """``MIN:MAX`` (or a bare ``MIN``, meaning MIN:MIN) -> (min, max).
    The job keeps running while at least MIN workers are live and
    respawns grow it back toward MAX."""
    lo, sep, hi = spec.partition(":")
    try:
        mn = int(lo)
        mx = int(hi) if sep else mn
    except ValueError:
        raise ValueError("--elastic expects MIN:MAX, got %r" % (spec,))
    if mn < 1 or mx < mn:
        raise ValueError("--elastic needs 1 <= MIN <= MAX, got %r" % (spec,))
    return mn, mx


def respawn_delay(attempt, base=1.0, cap=30.0, jitter=0.3, rand=None):
    """Exponential backoff with multiplicative jitter between respawn
    attempts (``attempt`` counts from 1): a persistently-crashing
    process must not be relaunched in a tight loop, and the jitter
    decorrelates a fleet of respawns hammering one coordinator."""
    import random

    r = (rand if rand is not None else random.random)()
    return min(cap, base * (2 ** (attempt - 1))) * (1.0 + jitter * r)


def _local_ip():
    """A routable address for DMLC_PS_ROOT_URI in ssh mode (the UDP-connect
    trick; no packet is sent)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 53))
        return s.getsockname()[0]
    except OSError:
        return socket.gethostbyname(socket.gethostname())
    finally:
        s.close()


def _ssh_popen(host, env, command, ssh_port, cwd, extra_keys=()):
    """One remote process: env inlined into the remote command line (ssh
    does not forward the environment), cwd mirrored (the reference's ssh
    tracker does the same 'cd <pwd> && env ... cmd').  extra_keys carries
    the --env entries so 'every process' includes remote ones."""
    pass_keys = [k for k in env
                 if k.startswith(("DMLC_", "MXNET_"))
                 or k in ("PYTHONPATH", "JAX_PLATFORMS")
                 or k in extra_keys]
    env_str = " ".join("%s=%s" % (k, shlex.quote(env[k]))
                       for k in sorted(set(pass_keys)))
    remote = "cd %s && env %s %s" % (
        shlex.quote(cwd), env_str,
        " ".join(shlex.quote(c) for c in command))
    return subprocess.Popen(
        ["ssh", "-o", "StrictHostKeyChecking=no", "-p", str(ssh_port),
         host, remote])


def serving_main(argv):
    """``launch.py --serving``: one warm serving-replica process — the
    autoscaler's scale-out actuator (ProcessProvider) and the unit a
    cluster scheduler would run per pod.  Restores the checkpoint with
    its AOT bundle / compile cache attached (warm start: first request
    runs with zero cold buckets), serves HTTP, registers + heartbeats
    into the replica registry so every replicated router discovers it,
    and installs the SIGTERM preemption handler — scale-in retirement
    and cluster preemption are the same drain → deregister →
    postmortem → exit path."""
    import json
    import time

    parser = argparse.ArgumentParser(
        description="Launch one registered serving replica")
    parser.add_argument("--serving", action="store_true")
    parser.add_argument("--registry", required=True,
                        help="replica-registry address (host:port)")
    parser.add_argument("--name", required=True,
                        help="registry member name for this replica")
    parser.add_argument("--prefix", required=True,
                        help="checkpoint prefix (save_checkpoint files)")
    parser.add_argument("--epoch", type=int, required=True)
    parser.add_argument("--input-shapes", required=True,
                        help='JSON {input_name: [batch, ...]} shapes')
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--http-port", type=int, default=0)
    parser.add_argument("--no-aot", action="store_true",
                        help="serve without attaching the AOT bundle "
                             "(cold warmup compiles)")
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    from mxnet_tpu.serving import (InferenceServer, RegistryClient,
                                   install_preemption_handler,
                                   start_heartbeater)

    shapes = {k: tuple(v)
              for k, v in json.loads(args.input_shapes).items()}
    server = InferenceServer.from_checkpoint(
        args.prefix, args.epoch, shapes, attach_aot=not args.no_aot)
    host, port = server.serve_http(args.host, args.http_port)[:2]
    backend = "%s:%d" % (host, port)
    registry = RegistryClient(args.registry)
    stop_beat = start_heartbeater(registry, args.name, backend)
    install_preemption_handler(server, deregister=stop_beat)
    print("launch.py: serving replica %s at %s (cold_bucket_runs=%d)"
          % (args.name, backend, server.cold_bucket_runs()),
          file=sys.stderr, flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        stop_beat()
        server.stop(drain=True)


def main():
    if "--serving" in sys.argv[1:]:
        serving_main(sys.argv[1:])
        return
    parser = argparse.ArgumentParser(
        description="Launch a distributed job locally",
        usage="launch.py [-h] -n NUM_WORKERS [-s NUM_SERVERS] command ...")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0)
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"])
    parser.add_argument("-H", "--hostfile", type=str, default=None,
                        help="one host per line (ssh launcher); workers "
                             "and servers round-robin over the hosts")
    parser.add_argument("--ssh-port", type=int, default=22)
    parser.add_argument("--env", action="append", default=[],
                        help="extra KEY=VALUE env entries for every process")
    parser.add_argument("--auto-resume", type=int, default=0, metavar="N",
                        help="relaunch a worker that exits nonzero, up to N "
                             "times per worker (checkpoint-based fault "
                             "tolerance: the training script resumes via "
                             "mx.model.find_latest_checkpoint)")
    parser.add_argument("--metrics-port", type=int, default=0, metavar="P",
                        help="host a fleet metrics aggregator on this port: "
                             "every process pushes its telemetry registry "
                             "(MXNET_TELEMETRY_AGG_ADDR is exported) and "
                             "GET /metrics serves one Prometheus page with "
                             "role/rank labels plus fleet-derived gauges")
    parser.add_argument("--elastic", type=str, default=None,
                        metavar="MIN:MAX",
                        help="elastic membership: workers join the kvstore "
                             "server's live-rank table "
                             "(MXNET_KVSTORE_ELASTIC=1), the job keeps "
                             "running while at least MIN workers are live, "
                             "and auto-resume respawns rejoin as FRESH "
                             "ranks (mid-run join) growing back toward MAX")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    elastic = None
    if args.elastic is not None:
        try:
            elastic = parse_elastic(args.elastic)
        except ValueError as e:
            parser.error(str(e))
        if not (elastic[0] <= args.num_workers <= elastic[1]):
            parser.error("--elastic %s must bracket -n %d"
                         % (args.elastic, args.num_workers))

    hosts = None
    if args.launcher == "ssh":
        if not args.hostfile:
            parser.error("--launcher ssh requires --hostfile")
        with open(args.hostfile) as f:
            stripped = [ln.strip() for ln in f]
        hosts = [ln for ln in stripped if ln and not ln.startswith("#")]
        if not hosts:
            parser.error("hostfile %s is empty" % args.hostfile)

    # ssh mode: the rendezvous endpoint (jax.distributed coordinator) is
    # hosted by worker 0, which lands on the FIRST hostfile entry — the
    # launcher machine itself may not run any process at all. Strip any
    # user@ login prefix: ssh accepts it, coordinator_address cannot.
    default_uri = hosts[0].rsplit("@", 1)[-1] if hosts else "127.0.0.1"
    port = os.environ.get("DMLC_PS_ROOT_PORT") or str(_free_port())
    base_env = dict(os.environ)
    base_env.update({
        "DMLC_PS_ROOT_URI": os.environ.get("DMLC_PS_ROOT_URI", default_uri),
        "DMLC_PS_ROOT_PORT": port,
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(args.num_servers),
    })
    if elastic is not None:
        base_env["MXNET_KVSTORE_ELASTIC"] = "1"
    if hosts is not None and args.num_servers > 0:
        # ssh mode places server i on hosts[i % len]; workers cannot derive
        # that from root_uri+port alone, so publish the authoritative
        # address list (server i binds, clients connect, from this)
        base_env["DMLC_SERVER_URIS"] = ",".join(
            "%s:%d" % (hosts[i % len(hosts)], int(port) + i)
            for i in range(args.num_servers))
    extra_keys = tuple(kv.partition("=")[0] for kv in args.env)
    for kv in args.env:
        k, _, v = kv.partition("=")
        base_env[k] = v

    aggregator = None
    if args.metrics_port:
        # fleet metrics: the launcher hosts the aggregation endpoint so it
        # outlives any single worker; processes push their registries to it
        # (telemetry.distributed.start_pusher reads the exported address)
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from mxnet_tpu.telemetry.distributed import FleetAggregator

        agg_host = _local_ip() if hosts is not None else "127.0.0.1"
        aggregator = FleetAggregator(host=agg_host, port=args.metrics_port)
        aggregator.start()
        base_env["MXNET_TELEMETRY_AGG_ADDR"] = aggregator.addr
        print("launch.py: fleet metrics at http://%s/metrics"
              % aggregator.addr, file=sys.stderr, flush=True)

    def spawn(env, rank):
        if hosts is None:
            return subprocess.Popen(args.command, env=env)
        return _ssh_popen(hosts[rank % len(hosts)], env, args.command,
                          args.ssh_port, os.getcwd(), extra_keys)

    procs = []
    server_procs = []
    worker_envs = []
    server_envs = []
    try:
        chips = local_chips() if hosts is None else []
        for i in range(args.num_servers):
            env = dict(base_env)
            env["DMLC_ROLE"] = "server"
            env["DMLC_SERVER_ID"] = str(i)
            if chips:
                # parameter servers are host-side: keep them off the chips
                env.setdefault("JAX_PLATFORMS", "cpu")
            server_envs.append(env)
            server_procs.append(spawn(env, i))
        for i in range(args.num_workers):
            env = dict(base_env)
            env["DMLC_ROLE"] = "worker"
            env["DMLC_WORKER_ID"] = str(i)
            env = chip_env(env, i, args.num_workers, chips)
            worker_envs.append(env)
            procs.append(spawn(env, i))
        rc = 0
        if args.auto_resume or elastic is not None:
            # supervise: a crashed worker comes back (its script resumes
            # from the newest checkpoint) and a crashed SERVER comes back
            # too (restoring its state from MXNET_KVSTORE_SNAPSHOT_PATH if
            # configured — workers ride out the outage through their
            # idempotent-retry transport, no worker restarts needed);
            # clean exits retire normally.  Respawns wait out an
            # exponential backoff with jitter (respawn_delay) so a
            # persistently-crashing process is not relaunched in a tight
            # loop.  --elastic additionally tolerates shrink (the job
            # continues while >= MIN workers are live) and respawns join
            # as FRESH ranks, growing back toward MAX.
            import time

            attempts = [0] * args.num_workers
            srv_attempts = [0] * args.num_servers
            live = dict(enumerate(procs))
            pending = {}      # worker slot -> (ready_at, env, rank)
            srv_pending = {}  # server idx -> (ready_at, env)
            next_rank = args.num_workers

            def n_live():
                return len(live) + len(pending)

            while live or pending:
                time.sleep(0.2)
                now = time.monotonic()
                for i, (t, env) in list(srv_pending.items()):
                    if now >= t:
                        del srv_pending[i]
                        server_procs[i] = spawn(env, i)
                for i, p in list(enumerate(server_procs)):
                    if i in srv_pending:
                        continue
                    r = p.poll()
                    if r is None or r == 0:
                        continue
                    if srv_attempts[i] >= args.auto_resume:
                        continue
                    srv_attempts[i] += 1
                    env = dict(server_envs[i])
                    env["MXNET_AUTORESUME_ATTEMPT"] = str(srv_attempts[i])
                    delay = respawn_delay(srv_attempts[i])
                    print("launch.py: server %d exited rc=%d; relaunch "
                          "%d/%d in %.1fs (%d attempts left)"
                          % (i, r, srv_attempts[i], args.auto_resume,
                             delay, args.auto_resume - srv_attempts[i]),
                          file=sys.stderr, flush=True)
                    srv_pending[i] = (now + delay, env)
                for slot, (t, env, rank) in list(pending.items()):
                    if now >= t:
                        del pending[slot]
                        p2 = spawn(env, rank)
                        live[slot] = p2
                        procs.append(p2)
                for i, p in list(live.items()):
                    r = p.poll()
                    if r is None:
                        continue
                    del live[i]
                    if r != 0 and attempts[i] < args.auto_resume and \
                            (elastic is None or n_live() < elastic[1]):
                        attempts[i] += 1
                        env = dict(worker_envs[i])
                        env["MXNET_AUTORESUME_ATTEMPT"] = str(attempts[i])
                        # rejoin contract (reference kvstore_dist.h:35-38):
                        # recovered workers skip startup barriers
                        env["DMLC_IS_RECOVERY"] = "1"
                        rank = i
                        if elastic is not None:
                            # a preempted rank never comes back as itself
                            # — the server may already have evicted it —
                            # so the respawn joins mid-run as a FRESH rank
                            rank = next_rank
                            next_rank += 1
                            env["DMLC_WORKER_ID"] = str(rank)
                            env["MXNET_KVSTORE_ELASTIC_JOIN"] = "1"
                        delay = respawn_delay(attempts[i])
                        print("launch.py: worker %d exited rc=%d; relaunch"
                              " %d/%d as rank %d in %.1fs (%d attempts "
                              "left)" % (i, r, attempts[i],
                                         args.auto_resume, rank, delay,
                                         args.auto_resume - attempts[i]),
                              file=sys.stderr, flush=True)
                        pending[i] = (now + delay, env, rank)
                    elif elastic is not None and r != 0 and \
                            n_live() >= elastic[0]:
                        # preemption the job absorbs: the fleet shrank but
                        # stays at or above MIN — not a job failure
                        print("launch.py: worker %d retired rc=%d; "
                              "continuing elastically with %d live "
                              "(min %d)" % (i, r, n_live(), elastic[0]),
                              file=sys.stderr, flush=True)
                    else:
                        rc = rc or r
        else:
            for p in procs:
                p.wait()
                rc = rc or p.returncode
    finally:
        # grace period before the TERM sweep: servers that exit on their
        # own (cooperative-stop command, or short-lived stub programs in
        # tests) must not race the teardown — without this a server
        # process spawned moments ago can be killed before it ever runs
        import time

        deadline = time.monotonic() + 1.0
        for p in server_procs:
            while p.poll() is None and time.monotonic() < deadline:
                time.sleep(0.02)
        for p in procs + server_procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in server_procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        if aggregator is not None:
            aggregator.stop()
    sys.exit(rc)


if __name__ == "__main__":
    main()
