"""Localhost kvstore push/pull throughput: sync vs async vs async+bucketed.

The workload the comm engine exists for: MANY SMALL KEYS (a model with
hundreds of bias/gamma/beta tensors), where the synchronous per-key path
pays one full RPC round trip per key, serialized.  Three modes over the
same in-process dist_async server (kvstore_server.py):

* ``sync``         — plain DistAsyncKVStore, blocking push/pull per key
                     (the pre-engine behavior)
* ``async``        — comm_engine.AsyncKVStore, bucketing off: per-key ops
                     overlap via the worker pool + pipelined ServerClient
* ``async_bucket`` — bucketing on: small keys coalesce into fused
                     multi-key RPCs (MXNET_KVSTORE_BUCKET_BYTES)

Emits ONE JSON line as the last stdout line; CPU-only (needs no chip).
"""
import argparse
import json
import os
import sys
import time

# CPU-only by design: the payloads are host numpy round trips; claiming
# the TPU would serialize against a training process for nothing
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# force the in-process server path (a launcher-provided fleet would
# measure that fleet, not the transport)
for _v in ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT", "DMLC_SERVER_URIS",
           "DMLC_ROLE"):
    os.environ.pop(_v, None)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir))


def _mk_store(mode, threads, bucket_bytes):
    from mxnet_tpu.comm_engine import make_async
    from mxnet_tpu.kvstore import DistAsyncKVStore

    kv = DistAsyncKVStore()
    if mode == "sync":
        return kv
    return make_async(kv, num_threads=threads,
                      bucket_bytes=bucket_bytes if mode == "async_bucket"
                      else 0)

def run_mode(mode, keys, key_size, rounds, threads, bucket_bytes):
    """Run ``rounds`` timed push-all/pull-all/wait rounds over ``keys``
    small keys; returns the best round's ops/s (one push or pull of one
    key == one op).  Best-of-N is the timeit convention: the minimum
    time is the workload's cost, the spread is scheduler noise."""
    import numpy as np

    import mxnet_tpu as mx

    kv = _mk_store(mode, threads, bucket_bytes)
    try:
        vals = [mx.nd.array(np.full(key_size, i % 7, dtype=np.float32))
                for i in range(keys)]
        outs = [mx.nd.zeros((key_size,)) for _ in range(keys)]
        for i in range(keys):
            kv.init(i, vals[i])
        best = 0.0
        for rnd in range(rounds + 1):  # round 0: connection+pool warmup
            t0 = time.perf_counter()
            for i in range(keys):
                kv.push(i, vals[i])
            for i in range(keys):
                kv.pull(i, outs[i])
            kv.wait_all()
            elapsed = time.perf_counter() - t0
            if rnd > 0:
                best = max(best, keys * 2 / elapsed)
        return best
    finally:
        kv.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1000,
                    help="number of small keys")
    ap.add_argument("--key-size", type=int, default=64,
                    help="elements per key (float32)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="timed push-all/pull-all rounds per mode")
    ap.add_argument("--threads", type=int, default=4,
                    help="comm-engine worker threads for the async modes")
    ap.add_argument("--bucket-bytes", type=int, default=1 << 16)
    cli = ap.parse_args(argv)

    sync = run_mode("sync", cli.keys, cli.key_size, cli.rounds,
                    cli.threads, cli.bucket_bytes)
    async_ = run_mode("async", cli.keys, cli.key_size, cli.rounds,
                      cli.threads, cli.bucket_bytes)
    bucket = run_mode("async_bucket", cli.keys, cli.key_size, cli.rounds,
                      cli.threads, cli.bucket_bytes)

    record = {
        "metric": "kvstore_pushpull_throughput",
        "value": round(bucket, 1),
        "unit": "ops/s",
        # baseline = the synchronous per-key path this PR replaces
        "vs_baseline": round(bucket / sync, 2) if sync else 0.0,
        "sync_ops_s": round(sync, 1),
        "async_ops_s": round(async_, 1),
        "async_bucket_ops_s": round(bucket, 1),
        "speedup_async": round(async_ / sync, 2) if sync else 0.0,
        "speedup_bucket": round(bucket / sync, 2) if sync else 0.0,
        "keys": cli.keys,
        "key_size": cli.key_size,
        "rounds": cli.rounds,
        "threads": cli.threads,
    }
    print(json.dumps(record))
    return record


if __name__ == "__main__":
    main()
