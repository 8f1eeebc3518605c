"""Sparse parameter plane throughput + worker-memory bench.

Two questions the row-sparse plane exists to answer:

* **rows/s**: how fast can a worker push+pull the touched rows of a
  1M x 64 embedding table versus pushing the equivalent FULL dense
  table through the dense kvstore path each step?
* **worker memory**: how do worker-resident parameter bytes scale as the
  logical table grows?  (Sparse: flat at O(touched); dense: linear.)

Runs entirely on CPU against in-process KVStoreServers (the payloads are
host numpy; claiming a TPU would measure nothing extra).  Emits ONE JSON
line as the last stdout line, like bench_kvstore.py.
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# force the in-process server path (a launcher-provided fleet would
# measure that fleet, not the plane)
for _v in ("DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT", "DMLC_SERVER_URIS",
           "DMLC_ROLE"):
    os.environ.pop(_v, None)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir))


def run_sparse(num_rows, dim, touched, rounds, num_servers):
    """Best-of-N rows/s for one push_rows + pull_rows step of ``touched``
    rows against ``num_servers`` sharded in-process servers."""
    import numpy as np

    from mxnet_tpu.kvstore_server import ServerClient, start_server
    from mxnet_tpu.sparse.plane import SparseParamPlane

    srvs = [start_server(port=0) for _ in range(num_servers)]
    clients = [ServerClient(*s.addr) for s in srvs]
    try:
        plane = SparseParamPlane(clients)
        plane.init_table("emb", num_rows=num_rows, row_shape=(dim,),
                         init=("zeros",))
        rng = np.random.RandomState(7)
        grads = np.ones((touched, dim), dtype=np.float32)
        best = 0.0
        for rnd in range(rounds + 1):  # round 0: connection warmup
            ids = rng.randint(0, num_rows, size=touched).astype(np.int64)
            t0 = time.perf_counter()
            plane.push_rows("emb", ids, grads)
            plane.pull_rows("emb", ids)
            elapsed = time.perf_counter() - t0
            if rnd > 0:
                best = max(best, touched * 2 / elapsed)
        return best
    finally:
        for c in clients:
            try:
                c.stop_server()
            except Exception:
                pass
            c.close()


def run_dense(num_rows, dim, rounds):
    """Best-of-N full-table push+pull throughput expressed in rows/s —
    the cost the sparse plane avoids paying per step."""
    import numpy as np

    from mxnet_tpu.kvstore_server import ServerClient, start_server

    srv = start_server(port=0)
    c = ServerClient(*srv.addr)
    try:
        table = np.zeros((num_rows, dim), dtype=np.float32)
        c.init("emb", table)
        best = 0.0
        for rnd in range(rounds + 1):
            t0 = time.perf_counter()
            c.push("emb", table)
            c.pull("emb")
            elapsed = time.perf_counter() - t0
            if rnd > 0:
                best = max(best, num_rows * 2 / elapsed)
        return best
    finally:
        try:
            c.stop_server()
        except Exception:
            pass
        c.close()


def run_memory_sweep(dim, touched, table_sizes, num_servers):
    """Worker-resident parameter bytes vs logical table size: the sparse
    worker's footprint is its pull buffer (flat); dense is the table."""
    import numpy as np

    from mxnet_tpu.kvstore_server import ServerClient, start_server
    from mxnet_tpu.sparse.plane import SparseParamPlane

    srvs = [start_server(port=0) for _ in range(num_servers)]
    clients = [ServerClient(*s.addr) for s in srvs]
    out = []
    try:
        plane = SparseParamPlane(clients)
        rng = np.random.RandomState(11)
        for n in table_sizes:
            key = "emb_%d" % n
            plane.init_table(key, num_rows=n, row_shape=(dim,),
                             init=("zeros",))
            ids = rng.randint(0, n, size=touched).astype(np.int64)
            got = plane.pull_rows(key, ids)
            out.append({
                "table_rows": n,
                "logical_bytes": n * dim * 4,
                "sparse_worker_bytes": int(got.nbytes),
                "dense_worker_bytes": n * dim * 4,
            })
    finally:
        for c in clients:
            try:
                c.stop_server()
            except Exception:
                pass
            c.close()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000,
                    help="logical table rows (the 1M x 64 headline config)")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--touched", type=int, default=4096,
                    help="distinct rows touched per step")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--dense-rows", type=int, default=100_000,
                    help="dense full-table baseline size (kept smaller "
                    "than --rows so the baseline finishes; rows/s "
                    "normalizes the comparison)")
    cli = ap.parse_args(argv)

    sparse = run_sparse(cli.rows, cli.dim, cli.touched, cli.rounds,
                        cli.servers)
    dense = run_dense(cli.dense_rows, cli.dim, cli.rounds)
    sweep = run_memory_sweep(cli.dim, cli.touched,
                             [10_000, 100_000, cli.rows], cli.servers)

    flat = all(r["sparse_worker_bytes"] == sweep[0]["sparse_worker_bytes"]
               for r in sweep)
    # what each path costs PER STEP: sparse ships the touched rows, dense
    # ships the whole logical table (extrapolated from measured bulk rows/s)
    sparse_step_s = cli.touched * 2 / sparse if sparse else float("inf")
    dense_step_s = cli.rows * 2 / dense if dense else float("inf")
    record = {
        "metric": "sparse_pushpull_rows_per_s",
        "value": round(sparse, 1),
        "unit": "rows/s",
        # speedup of a sparse step over pushing the full table every step
        "vs_baseline": round(dense_step_s / sparse_step_s, 2),
        "sparse_rows_s": round(sparse, 1),
        "dense_fulltable_rows_s": round(dense, 1),
        "sparse_step_ms": round(sparse_step_s * 1e3, 2),
        "dense_fulltable_step_ms": round(dense_step_s * 1e3, 2),
        "table_rows": cli.rows,
        "dim": cli.dim,
        "touched": cli.touched,
        "servers": cli.servers,
        "worker_bytes_flat_vs_table": flat,
        "memory_sweep": sweep,
    }
    print(json.dumps(record))
    return record


if __name__ == "__main__":
    main()
