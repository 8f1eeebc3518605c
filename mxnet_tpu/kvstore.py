"""KVStore — parameter synchronization facade.

TPU-native redesign of /root/reference/src/kvstore/ + python/mxnet/kvstore.py.
The reference moves gradients through Comm (pinned-host or GPU-P2P reduce)
and ps-lite; on TPU the synchronous data-parallel path is XLA collectives
(``psum`` over a mesh axis) compiled *into* the training step, so ``local``
and ``device`` collapse to the same thing: an aggregation point that applies
the optimizer once per key.  The KVStore class keeps the reference's API
(init/push/pull/set_optimizer/rank/num_workers) so Module and user scripts
port unchanged; multi-host ``dist_*`` flavors ride ``jax.distributed`` +
the global mesh (parallel/ package) rather than a parameter server.

Push semantics match kvstore_local.h:50-95: pushed grads for one key are
summed; with an updater installed the update runs eagerly on push and pull
returns the stored weight; without one, pull returns the summed grads.
"""
from __future__ import annotations

import logging
import os
import pickle
from typing import Callable, Dict, List, Optional, Union

import numpy as np

from .base import MXNetError, register_env
from .ndarray import NDArray
from . import ndarray as nd
from . import optimizer as opt
__all__ = ["KVStore", "create", "install_preemption_handler",
           "NonFiniteGradientError"]


def __getattr__(name):
    # typed NACK for non-finite pushes (numeric containment) — re-exported
    # here because workers catch it around push(), not around server code.
    # Lazy: an eager import would run kvstore_server's DMLC_ROLE=server
    # bootstrap earlier than the package __init__ sequences it.
    if name == "NonFiniteGradientError":
        from .kvstore_server import NonFiniteGradientError

        return NonFiniteGradientError
    raise AttributeError("module %r has no attribute %r" % (__name__, name))

register_env("MXNET_KVSTORE_COMPRESS", "", str,
             "Wire compression for dist_async push payloads: 'fp16' halves "
             "gradient bytes with per-key error-feedback residuals "
             "(convergence-preserving); empty disables.")
register_env("MXNET_KVSTORE_ELASTIC", 0, int,
             "Elastic membership for dist_async: workers join the server's "
             "live-rank table, barriers and sync rounds size themselves by "
             "the current generation, and a preemption handler is installed "
             "on the Module path (fault_tolerance.md §elasticity).")
register_env("MXNET_KVSTORE_ELASTIC_JOIN", 0, int,
             "Set by launch.py --elastic on respawned workers: this process "
             "is a mid-run joiner — it rides the recovery bring-up (skip "
             "startup barriers, pull current params) and aligns with the "
             "fleet at the next barrier.")
register_env("MXNET_KVSTORE_DRAIN_TIMEOUT", 30, float,
             "Seconds the preemption handler waits for in-flight comm-engine "
             "ops to drain before checkpointing and leaving.")


def _key_list(key):
    return (key if isinstance(key, (list, tuple)) else [key]), \
        not isinstance(key, (list, tuple))


def _val_list(value, nkeys):
    if isinstance(value, (list, tuple)) and nkeys == 1 and \
            not isinstance(value[0], (list, tuple)):
        return [list(value)]
    if nkeys == 1:
        return [value if isinstance(value, list) else [value]]
    out = []
    for v in value:
        out.append(v if isinstance(v, list) else [v])
    return out


class KVStore:
    """Single-process key-value store (reference kvstore.h:26-286 'local' /
    'device')."""

    def __init__(self, kv_type="local"):
        self._type = kv_type
        self._store: Dict[Union[int, str], NDArray] = {}
        self._updater: Optional[Callable] = None
        self._optimizer = None

    # -- identity ----------------------------------------------------------
    @property
    def type(self) -> str:
        return self._type

    @property
    def rank(self) -> int:
        import jax

        if "dist" in self._type:
            return jax.process_index()
        return 0

    @property
    def num_workers(self) -> int:
        import jax

        if "dist" in self._type:
            return jax.process_count()
        return 1

    # -- data plane --------------------------------------------------------
    def init(self, key, value):
        keys, _ = _key_list(key)
        vals = _val_list(value, len(keys))
        for k, v in zip(keys, vals):
            if k in self._store:
                raise MXNetError("duplicate init of key %s" % str(k))
            self._store[k] = v[0].copy() if isinstance(v[0], NDArray) \
                else nd.array(v[0])

    def push(self, key, value, priority=0):
        """Sum pushed values per key; run the updater eagerly if installed
        (reference KVStoreLocal::Push, kvstore_local.h:50)."""
        keys, _ = _key_list(key)
        vals = _val_list(value, len(keys))
        for k, vlist in zip(keys, vals):
            if k not in self._store:
                raise MXNetError("push to uninitialized key %s" % str(k))
            merged = vlist[0]
            if len(vlist) > 1:
                acc = vlist[0]._data
                for v in vlist[1:]:
                    acc = acc + v._data
                merged = NDArray(acc, vlist[0].context)
            if self._updater is not None:
                # the store lives where it was initialised: a value pushed
                # from elsewhere (a gradient replicated over a device
                # mesh) is brought to it, as pull brings it back
                # (reference CopyFromTo(merged, &local))
                local = self._store[k]._data.sharding
                if merged._data.sharding != local:
                    import jax

                    merged = NDArray(jax.device_put(merged._data, local),
                                     self._store[k].context)
                self._updater(k, merged, self._store[k])
            else:
                # no updater: the store holds the merged sum of this push
                # (reference KVStoreLocal::Push CopyFromTo(merged, &local))
                self._store[k]._set(merged._data)

    def pull(self, key, out=None, priority=0):
        keys, single = _key_list(key)
        outs = _val_list(out, len(keys))
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("pull of uninitialized key %s" % str(k))
            src = self._store[k]
            for o in olist:
                data = src._data.astype(o.dtype) if o.dtype != src.dtype \
                    else src._data
                # keep the destination's placement: pulling into a
                # mesh-replicated parameter must not collapse it onto the
                # store's single device
                if getattr(o._data, "sharding", None) is not None and \
                        data.sharding != o._data.sharding:
                    import jax

                    data = jax.device_put(data, o._data.sharding)
                o._set(data)

    # -- synchronization ---------------------------------------------------
    def wait(self, keys=None):
        """Block until outstanding ops on ``keys`` (all, when None) have
        completed.  Synchronous flavors finish every push/pull before
        returning, so this is a no-op; the async facade
        (comm_engine.AsyncKVStore) overrides it with a real barrier."""

    def wait_all(self):
        """Block until every outstanding op has completed (no-op here;
        see ``wait``)."""

    def drain(self, timeout=None):
        """Finish outstanding async work before a preemption exit (no-op
        for synchronous stores; the comm-engine facade overrides this
        with a bounded wait).  Returns True once everything completed."""
        return True

    # -- control plane -----------------------------------------------------
    def set_optimizer(self, optimizer):
        """Install an optimizer as the store-side updater.  In dist mode the
        reference pickles it to the servers (kvstore.py:232-255); collective
        DP needs no server, so both paths install locally."""
        self._optimizer = optimizer
        self._set_updater(opt.get_updater(optimizer))

    def _set_updater(self, updater):
        self._updater = updater

    def _barrier(self):
        pass

    def _send_command_to_servers(self, head, body):
        pass

    def save_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("Cannot save states for distributed training")
        with open(fname, "wb") as fout:
            fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("Cannot load states for distributed training")
        with open(fname, "rb") as fin:
            self._updater.set_states(fin.read())


class DistAsyncKVStore(KVStore):
    """``dist_async`` over the host-side parameter service
    (kvstore_server.py): every push triggers the server updater immediately
    — no worker synchronization (reference kvstore_dist_server.h:198-206
    async branch + kvstore_dist.h worker client)."""

    def __init__(self, kv_type="dist_async"):
        import os

        super().__init__(kv_type)
        from . import kvstore_server as kvs

        host = os.environ.get("DMLC_PS_ROOT_URI")
        # DMLC_SERVER_URIS ("h1:p1,h2:p2") is the launcher's authoritative
        # server list and stands on its own — no root URI needed (the
        # sparse-plane tests point a worker at already-running servers
        # this way)
        uris = os.environ.get("DMLC_SERVER_URIS")
        if host or uris:
            port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
            self._server = None
            # multi-server fleet: DMLC_SERVER_URIS when servers live on
            # different hosts, else root_port+i on the root host (the
            # launcher starts DMLC_NUM_SERVER of them)
            if uris:
                addrs = [(h, int(p)) for h, p in
                         (u.rsplit(":", 1) for u in uris.split(","))]
            else:
                n_srv = max(1, int(os.environ.get("DMLC_NUM_SERVER",
                                                  "1") or "1"))
                addrs = [(host, port + i) for i in range(n_srv)]
        else:
            # single-process bring-up: run the service in-process so the
            # async path works without a launcher
            self._server = kvs.start_server(
                num_workers=int(os.environ.get("DMLC_NUM_WORKER", "1")))
            addrs = [self._server.addr]
        self._clients = [kvs.ServerClient(h, p) for h, p in addrs]
        self._client = self._clients[0]
        # reference kvstore_dist.h:264-302: arrays with at least this many
        # elements are range-split evenly across the server fleet
        self._bigarray_bound = int(
            os.environ.get("MXNET_KVSTORE_BIGARRAY_BOUND", str(1000 * 1000)))
        self._rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
        self._num_workers = int(os.environ.get("DMLC_NUM_WORKER", "1"))
        # rejoin semantics (reference kvstore_dist.h:35-38 IsRecovery):
        # a relaunched worker must NOT wait at startup barriers — its
        # peers are mid-training and will never arrive. Server state is
        # safe: init is setdefault on the server, so re-init cannot
        # clobber trained weights; the worker pulls current ones. The
        # flag covers ONLY the bring-up phase: it expires at the first
        # push (bring-up itself pulls — Module interleaves init/pull per
        # parameter), so later barriers participate normally and a later
        # legitimate set_optimizer (LR drop at an epoch boundary)
        # installs instead of being dropped as a recovery re-ship.
        self._is_recovery = (
            os.environ.get("DMLC_IS_RECOVERY", "") == "1"
            or int(os.environ.get("MXNET_AUTORESUME_ATTEMPT", "0") or 0) > 0)
        self._pool = None  # lazy; lives for the store's lifetime
        # optional fp16 wire compression with error feedback: the
        # quantization error of each push is carried into the next one
        # per key, so the server integrates the true gradient sum over
        # time (convergence-preserving, unlike plain truncation)
        comp = os.environ.get("MXNET_KVSTORE_COMPRESS", "").lower()
        if comp in ("none", "0"):
            comp = ""
        if comp not in ("", "fp16"):
            raise MXNetError(
                "unsupported MXNET_KVSTORE_COMPRESS %r (only 'fp16')"
                % comp)
        self._compress = comp
        self._residuals: Dict[object, np.ndarray] = {}
        # elastic membership (docs/how_to/fault_tolerance.md §elasticity):
        # join every server's live-rank table so barriers and sync rounds
        # size themselves by the current generation.  A mid-run joiner
        # (MXNET_KVSTORE_ELASTIC_JOIN, set by launch.py --elastic on
        # respawns) additionally rides the recovery bring-up so it pulls
        # current params and aligns at the NEXT barrier instead of
        # waiting at startup ones.
        self._elastic = os.environ.get("MXNET_KVSTORE_ELASTIC", "0") == "1"
        self._left = False
        if os.environ.get("MXNET_KVSTORE_ELASTIC_JOIN", "0") == "1":
            self._is_recovery = True
        # liveness: periodic heartbeat so the server can report dead peers
        # and release stuck barriers (kvstore_dist.h:151-160 parity)
        hb_interval = float(os.environ.get(
            "MXNET_KVSTORE_HEARTBEAT_INTERVAL", "5"))
        if self._elastic:
            for c in self._clients:
                c.join(self._rank)
                # heartbeat EVERY server: each keeps its own eviction
                # clock, and a beat to server 0 alone would get this rank
                # evicted from the rest of the fleet
                c.start_heartbeat(self._rank, interval=hb_interval)
        else:
            self._client.start_heartbeat(self._rank, interval=hb_interval)

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def num_workers(self) -> int:
        return self._num_workers

    # -- key placement (reference kvstore_dist.h:264-302) -----------------
    def _server_for(self, key):
        """Stable small-key placement (crc32, NOT hash(): the builtin is
        salted per process, so workers would disagree)."""
        import zlib

        return zlib.crc32(str(key).encode()) % len(self._clients)

    def _ranges(self, n):
        """Even contiguous [lo, hi) element ranges, one per server."""
        ns = len(self._clients)
        base, rem = divmod(n, ns)
        bounds = [0]
        for i in range(ns):
            bounds.append(bounds[-1] + base + (1 if i < rem else 0))
        return list(zip(bounds[:-1], bounds[1:]))

    def _is_sharded(self, n_elements):
        return (len(self._clients) > 1
                and n_elements >= self._bigarray_bound)

    def _client_pool(self):
        """One long-lived thread pool for concurrent per-server RPCs —
        push/pull run every step; spawning threads per call would sit on
        the training hot path."""
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(len(self._clients))
        return self._pool

    def init(self, key, value):
        keys, _ = _key_list(key)
        vals = _val_list(value, len(keys))
        for k, v in zip(keys, vals):
            if self._rank == 0:
                arr = v[0].asnumpy() if isinstance(v[0], NDArray) else \
                    np.asarray(v[0])
                if self._is_sharded(arr.size):
                    flat = arr.reshape(-1)
                    for cid, (lo, hi) in enumerate(self._ranges(arr.size)):
                        self._clients[cid].init(k, flat[lo:hi])
                else:
                    self._clients[self._server_for(k)].init(k, arr)
        # the server decides whether a recovered worker may skip (only
        # once the job passed startup — see KVStoreServer barrier); the
        # init sends above are setdefault-safe either way
        self._client.barrier(rank=self._rank,
                             is_recovery=self._is_recovery)

    @staticmethod
    def _merge_vals(vlist):
        """Sum a key's device values ON DEVICE, then transfer the result
        to host once (the old path round-tripped every value through
        asnumpy() before summing — num_device host transfers per key)."""
        if not isinstance(vlist[0], NDArray):
            merged = np.asarray(vlist[0])
            for v in vlist[1:]:
                merged = merged + np.asarray(v)
            return merged
        if len(vlist) == 1:
            return vlist[0].asnumpy()
        acc = vlist[0]._data
        for v in vlist[1:]:
            acc = acc + v._data
        return NDArray(acc, vlist[0].context).asnumpy()

    def _compress_out(self, rkey, arr):
        """fp16 wire compression with error feedback: residual r_{t} =
        (g_t + r_{t-1}) - fp16(g_t + r_{t-1}) is replayed into the next
        push of the same key, so quantization error never accumulates."""
        if self._compress != "fp16" or arr.dtype.kind != "f" \
                or arr.dtype == np.float16:
            return arr
        prev = self._residuals.get(rkey)
        acc = arr + prev if prev is not None else arr
        sent = acc.astype(np.float16)
        self._residuals[rkey] = acc - sent.astype(arr.dtype)
        return sent

    def push(self, key, value, priority=0):
        self._is_recovery = False  # training traffic: bring-up is over
        keys, _ = _key_list(key)
        vals = _val_list(value, len(keys))
        for k, vlist in zip(keys, vals):
            self._push_one(k, self._merge_vals(vlist))

    def _push_one(self, k, merged):
        if self._is_sharded(merged.size):
            flat = merged.reshape(-1)
            # residuals are tracked per (key, range-start): each server
            # sees a consistent error-feedback stream for its shard
            parts = [(cid, self._compress_out((k, lo), flat[lo:hi]))
                     for cid, (lo, hi) in
                     enumerate(self._ranges(merged.size))]
            list(self._client_pool().map(
                lambda p: self._clients[p[0]].push(k, p[1],
                                                   rank=self._rank),
                parts))
        else:
            self._clients[self._server_for(k)].push(
                k, self._compress_out(k, merged), rank=self._rank)

    def push_multi(self, pairs):
        """Fused push of many ``(key, vlist)`` pairs: merge + compress per
        key, group by owning server, then ONE batched ``multi`` RPC per
        server (concurrent across the fleet).  The transport's
        per-envelope idempotency token covers the whole bucket, so
        crash-replay applies it exactly once."""
        self._is_recovery = False
        groups: Dict[int, list] = {}
        big = []
        for k, vlist in pairs:
            merged = self._merge_vals(vlist)
            if self._is_sharded(merged.size):
                big.append((k, merged))  # range-split path, key at a time
                continue
            groups.setdefault(self._server_for(k), []).append(
                ("push", k, self._compress_out(k, merged), self._rank))
        items = list(groups.items())
        if len(items) == 1:
            self._clients[items[0][0]].multi(items[0][1])
        elif items:
            list(self._client_pool().map(
                lambda it: self._clients[it[0]].multi(it[1]), items))
        for k, merged in big:
            self._push_one(k, merged)

    @staticmethod
    def _write_out(arr, olist):
        """Write a pulled host array into the destination NDArrays (dtype
        cast + destination-sharding preservation, see KVStore.pull)."""
        import jax

        for o in olist:
            data = nd.array(arr, dtype=o.dtype)._data
            if getattr(o._data, "sharding", None) is not None and \
                    data.sharding != o._data.sharding:
                data = jax.device_put(data, o._data.sharding)
            o._set(data)

    def pull(self, key, out=None, priority=0):
        # NOTE: pull must NOT clear _is_recovery — Module bring-up
        # interleaves init/pull per parameter (model.py
        # _initialize_kvstore) before set_optimizer ever runs; only push
        # marks real training traffic.
        keys, _ = _key_list(key)
        outs = _val_list(out, len(keys))
        for k, olist in zip(keys, outs):
            want = olist[0]
            if self._is_sharded(int(np.prod(want.shape))):
                # concurrent per-server pulls: latency is max-of-servers,
                # not sum (the point of the range split; the reference's
                # ps-lite worker overlaps its range requests the same way)
                parts = list(self._client_pool().map(
                    lambda c: c.pull(k), self._clients))
                arr = np.concatenate(
                    [np.asarray(p).reshape(-1) for p in parts]
                ).reshape(want.shape)
            else:
                arr = self._clients[self._server_for(k)].pull(k)
            self._write_out(arr, olist)

    def pull_multi(self, pairs):
        """Fused pull of many ``(key, olist)`` pairs: group by owning
        server, one batched ``multi`` RPC per server (concurrent across
        the fleet), then write destinations."""
        small, big = [], []
        for k, olist in pairs:
            if self._is_sharded(int(np.prod(olist[0].shape))):
                big.append((k, olist))
            else:
                small.append((k, olist))
        groups: Dict[int, list] = {}
        for i, (k, _) in enumerate(small):
            groups.setdefault(self._server_for(k), []).append(i)
        def fetch(item):
            cid, idxs = item
            replies = self._clients[cid].multi(
                [("pull", small[i][0]) for i in idxs])
            return list(zip(idxs, replies))
        items = list(groups.items())
        if len(items) == 1:
            results = fetch(items[0])
        elif items:
            results = [r for rs in self._client_pool().map(fetch, items)
                       for r in rs]
        else:
            results = []
        # one fused host→device transfer for the whole group: a
        # device_put dispatch per key is the measured bottleneck at
        # many-small-key scale, not the wire
        import jax

        hosts, dests = [], []
        for i, arr in results:
            arr = np.asarray(arr)
            for o in small[i][1]:
                hosts.append(arr if arr.dtype == o.dtype
                             else arr.astype(o.dtype))
                dests.append(o)
        for o, data in zip(dests, self._to_device(hosts)):
            if getattr(o._data, "sharding", None) is not None and \
                    data.sharding != o._data.sharding:
                data = jax.device_put(data, o._data.sharding)
            o._set(data)
        for k, olist in big:
            self.pull(k, olist)

    @staticmethod
    def _to_device(hosts):
        """Move a group of host arrays to device with ONE transfer:
        concatenate flat, one device_put, split on device.  Per-array
        device_put (even jax's batched form) costs ~25-40us of dispatch
        per key; the fused path amortizes it across the group."""
        import jax
        import jax.numpy as jnp

        if not hosts:
            return []
        dt = hosts[0].dtype
        if len(hosts) == 1 or any(h.dtype != dt for h in hosts):
            return jax.device_put(hosts)
        flats = [h.reshape(-1) for h in hosts]
        big = jax.device_put(np.concatenate(flats))
        offs = np.cumsum([f.size for f in flats])[:-1].tolist()
        return [p if p.shape == h.shape else p.reshape(h.shape)
                for p, h in zip(jnp.split(big, offs), hosts)]

    def get_num_dead_node(self, node_id=0, timeout=None):
        """Count workers whose heartbeat went stale (reference
        kvstore.get_num_dead_node over ps::Postoffice::GetDeadNodes,
        kvstore_dist.h:151-160).  ``timeout=None`` uses the server's own
        ``MXNET_KVSTORE_HEARTBEAT_TIMEOUT`` default, so callers and the
        barrier dead-peer release agree on who is dead."""
        try:
            return len(self._client.dead_nodes(
                None if timeout is None else float(timeout)))
        except Exception:
            # server unreachable: from this worker's view the service
            # itself is dead
            return 1

    # -- elastic membership -------------------------------------------------
    def membership(self):
        """Live membership view ``{gen, ranks, num_workers}``."""
        return self._client.membership()

    def leave(self):
        """Graceful preemption exit: drop this rank from every server's
        live set so the survivors' barriers and merge rounds re-form
        immediately.  Idempotent; failures are logged, not raised — a
        leaving worker cannot do anything about a dead server."""
        if self._left:
            return
        self._left = True
        for c in self._clients:
            try:
                c.leave(self._rank)
            except Exception as e:
                logging.warning("kvstore leave(rank=%d) failed: %s",
                                self._rank, e)

    def close(self):
        """Tear down the client sockets and any in-process server."""
        try:
            if self._elastic:
                self.leave()
            if self._pool is not None:
                self._pool.shutdown(wait=False)
                self._pool = None
            for c in self._clients:
                c.close()
        finally:
            if self._server is not None:
                self._server.stop()
                self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def set_optimizer(self, optimizer):
        """Ship the pickled optimizer to every server (reference
        kvstore.py:232-255 _send_command_to_servers)."""
        if self._rank == 0:
            # recovery flag travels with the command: the server keeps
            # its live updater (momentum state) when one is installed
            for c in self._clients:
                c.set_optimizer(optimizer, is_recovery=self._is_recovery)
        self._client.barrier(rank=self._rank,
                             is_recovery=self._is_recovery)

    def _barrier(self):
        self._client.barrier(rank=self._rank,
                             is_recovery=self._is_recovery)

    def _send_command_to_servers(self, head, body):
        if head == "stop":
            for c in self._clients:
                c.stop_server()

    def save_optimizer_states(self, fname):
        raise MXNetError("Cannot save states for distributed training")

    def load_optimizer_states(self, fname):
        raise MXNetError("Cannot load states for distributed training")


def install_preemption_handler(kv, checkpoint_fn=None, sig=None,
                               drain_timeout=None, exit_process=True):
    """Install the elastic preemption path on ``sig`` (default SIGTERM):
    drain in-flight comm-engine ops (bounded by
    ``MXNET_KVSTORE_DRAIN_TIMEOUT``), run ``checkpoint_fn`` if given,
    send the ``leave`` RPC so the surviving fleet re-forms immediately,
    and exit 0 — a clean preemption must not look like a crash to
    ``launch.py`` auto-resume.  Returns the handler (tests invoke it
    directly); the signal itself is only hooked from the main thread
    (``signal.signal`` constraint — elsewhere the handler comes back
    uninstalled)."""
    import signal as _signal
    import threading

    if sig is None:
        sig = _signal.SIGTERM
    if drain_timeout is None:
        drain_timeout = float(os.environ.get(
            "MXNET_KVSTORE_DRAIN_TIMEOUT", "30"))
    fired = threading.Event()

    def handler(signum=None, frame=None):
        if fired.is_set():
            return
        fired.set()
        logging.info("preemption signal: draining comm ops "
                     "(%.0fs budget), checkpointing, leaving", drain_timeout)
        try:
            kv.drain(drain_timeout)
        except Exception as e:
            logging.warning("preemption drain failed: %s", e)
        if checkpoint_fn is not None:
            try:
                checkpoint_fn()
            except Exception as e:
                logging.warning("preemption checkpoint failed: %s", e)
        leave = getattr(kv, "leave", None)
        if leave is not None:
            try:
                leave()
            except Exception as e:
                logging.warning("preemption leave failed: %s", e)
        try:
            # flight recorder: the postmortem is the only record of this
            # process's final state once we _exit (no atexit hooks run)
            from . import telemetry as _tm

            _tm.flight_recorder.dump("preemption-sigterm")
        except Exception:
            pass
        if exit_process:
            os._exit(0)

    if threading.current_thread() is threading.main_thread():
        try:
            _signal.signal(sig, handler)
        except (ValueError, OSError):
            pass
    return handler


def create(name="local") -> KVStore:
    """Create a KVStore (reference KVStore::Create, kvstore.cc:17-45).
    'local'/'device' → in-process aggregation (XLA fuses the reduce);
    'dist_sync'/'dist_device_sync' → multi-host SPMD where sync semantics
    come from in-step collectives (jax.distributed + global mesh), so no
    server round-trips; 'dist_async' → the host-side parameter service
    (kvstore_server.py), updater applied on every push."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    name = name.lower()
    if name not in ("local", "local_update_cpu", "local_allreduce_cpu",
                    "local_allreduce_device", "device", "dist_sync",
                    "dist_device_sync", "dist_async", "dist"):
        raise MXNetError("unknown KVStore type %s" % name)
    if name == "dist_async":
        return DistAsyncKVStore(name)
    if name in ("dist_sync", "dist_device_sync", "dist"):
        from .kvstore_dist import DistSyncKVStore

        return DistSyncKVStore(name)
    return KVStore(name)
