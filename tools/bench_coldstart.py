"""Cold-start worker: time-to-first-prediction for a serving replica.

One process = one replica lifecycle: build an :class:`InferenceServer`
(a deep-enough MLP that XLA compilation dominates cold start, several
batch buckets so warmup compiles more than one program), then measure
wall time from construction start to the first prediction result.  The
parent runs this twice against one
``MXNET_COMPILE_CACHE_DIR``: the first run compiles and populates the
cache, the second must start warm — hits>0, zero compiles — which is the
PR-10 acceptance measurement.

Prints ONE json line:
  {"ttfp_ms", "warmup_ms", "predict_ms", "out_digest", "cache": {...}}

``out_digest`` hashes the first prediction's bytes so the caller can
assert cache-served outputs are bit-identical to freshly-compiled ones.

Usage: python tools/bench_coldstart.py [--buckets 1,2,4] [--hidden 256]
       (cache dir comes from MXNET_COMPILE_CACHE_DIR; empty = cache off)
"""
import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_symbol(layers, hidden, classes):
    import mxnet_tpu as mx

    net = mx.symbol.Variable("data")
    for i in range(layers):
        net = mx.symbol.FullyConnected(net, name="fc%d" % i,
                                       num_hidden=hidden)
        net = mx.symbol.Activation(net, act_type="relu",
                                   name="relu%d" % i)
    net = mx.symbol.FullyConnected(net, name="head", num_hidden=classes)
    return mx.symbol.SoftmaxOutput(net, name="softmax")


def build_params(layers, feat, hidden, classes, seed=7):
    import numpy as np

    rng = np.random.RandomState(seed)
    params = {}
    d_in = feat
    for i in range(layers):
        params["fc%d_weight" % i] = \
            rng.randn(hidden, d_in).astype(np.float32) * 0.05
        params["fc%d_bias" % i] = np.zeros(hidden, np.float32)
        d_in = hidden
    params["head_weight"] = rng.randn(classes, d_in).astype(np.float32) * 0.05
    params["head_bias"] = np.zeros(classes, np.float32)
    return params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--feat", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--buckets", default="1,2,4")
    cli = ap.parse_args(argv)

    t0 = time.perf_counter()
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache
    from mxnet_tpu.serving.server import InferenceServer

    buckets = tuple(int(b) for b in cli.buckets.split(","))
    symbol = build_symbol(cli.layers, cli.hidden, cli.classes)
    params = build_params(cli.layers, cli.feat, cli.hidden, cli.classes)

    # TTFP clock starts at server construction (includes every bucket's
    # warmup — the compile-or-deserialize cost under test)
    t_build = time.perf_counter()
    server = InferenceServer(symbol, params,
                             {"data": (max(buckets), cli.feat)},
                             buckets=buckets, warmup=True, start=True)
    t_warm = time.perf_counter()
    x = np.arange(cli.feat, dtype=np.float32) / cli.feat
    out = server.predict(data=x)[0]
    t_first = time.perf_counter()
    server.stop()

    print(json.dumps({
        "ttfp_ms": round((t_first - t_build) * 1e3, 1),
        "warmup_ms": round((t_warm - t_build) * 1e3, 1),
        "predict_ms": round((t_first - t_warm) * 1e3, 1),
        "import_ms": round((t_build - t0) * 1e3, 1),
        "buckets": list(buckets),
        "out_digest": hashlib.sha256(
            np.ascontiguousarray(out).tobytes()).hexdigest()[:16],
        "cache": compile_cache.stats(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
