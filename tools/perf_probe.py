"""Performance probe for the fused ResNet-50 train step.

Builds the ResNet-50 Module, runs one step, then lowers
the SAME fused program and reports XLA cost analysis (flops, bytes), HLO op
histogram (how many transposes/copies survived), and measured step time.
Optionally dumps full HLO text and a jax.profiler trace.

Usage:
  python tools/perf_probe.py [--batch-size 256] [--dump-hlo /tmp/hlo.txt]
                             [--dump-scopes /tmp/scopes.json]
                             [--trace /tmp/jax-trace]

``--dump-scopes`` writes {instruction name: scope path}: the way from a device
trace's ``fusion.N`` to the Symbol node (or ``optimizer``) it came from.
"""
import argparse
import collections
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.artifact_io import write_json  # noqa: E402


def build_module(batch):
    import mxnet_tpu as mx
    from examples.image_classification.common import fit
    from examples.image_classification.train_imagenet import get_network

    parser = argparse.ArgumentParser()
    fit.add_fit_args(parser)
    args = parser.parse_args([
        "--network", "resnet-50", "--num-classes", "1000",
        "--image-shape", "3,224,224", "--batch-size", str(batch),
        "--lr", "0.1", "--dtype", "bfloat16", "--benchmark", "1"])
    net = get_network(args)

    shape = (3, 224, 224)
    train = fit.SyntheticIter(shape, 1000, batch, num_batches=200)
    mod = mx.mod.Module(net, context=mx.current_context(),
                        compute_dtype="bfloat16")
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label, for_training=True)
    mod.init_params(initializer=mx.init.Xavier(factor_type="in",
                                               magnitude=2.34))
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1, "wd": 1e-4,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    return mod, train


# re-exported for back-compat: the analysis lives in the shared
# mxnet_tpu.hlo_analysis module
from mxnet_tpu.hlo_analysis import bn_fusion_analysis  # noqa: E402,F401
from mxnet_tpu.hlo_analysis import hlo_op_counts, op_scopes  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--num-steps", type=int, default=20)
    ap.add_argument("--dump-hlo", default=None)
    ap.add_argument("--dump-scopes", default=None)
    ap.add_argument("--trace", default=None)
    cli = ap.parse_args()

    import jax

    mod, train = build_module(cli.batch_size)
    batch = train.next()

    def step():
        mod.forward_backward(batch)
        mod.update()

    t0 = time.time()
    step()
    ex = mod._exec_group.execs[0]
    # flush deferred fused batch so _fused_introspect exists
    mod._flush_fused_pending() if hasattr(mod, "_flush_fused_pending") else None
    compile_s = time.time() - t0

    fn, abstract = getattr(ex, "_fused_introspect", (None, None))
    report = {"batch_size": cli.batch_size, "compile_s": round(compile_s, 1)}
    if fn is not None and hasattr(fn, "lower"):
        # same analysis path StepMonitor uses per compiled executable, so
        # the probe's numbers and live telemetry MFU agree by construction
        from mxnet_tpu import telemetry
        try:
            compiled, info = telemetry.lower_and_analyze(fn, abstract)
            report["xla_flops"] = info.get("flops")
            report["xla_bytes_accessed"] = info.get("bytes_accessed")
        except Exception as e:  # noqa
            report["cost_analysis_error"] = str(e)
            compiled = fn.lower(*abstract).compile()
        hlo = compiled.as_text()
        report["hlo_op_counts"] = hlo_op_counts(
            hlo, interesting=("transpose", "copy", "convolution", "fusion",
                              "custom-call", "all-reduce", "reshape",
                              "bitcast", "dot"))
        # count convs whose operand/result types are bf16
        convs = re.findall(r"= (\S+) convolution\(", hlo)
        report["conv_result_dtypes"] = dict(collections.Counter(
            c.split("[")[0] for c in convs))
        report["bn_fusion"] = bn_fusion_analysis(hlo)
        if cli.dump_hlo:
            with open(cli.dump_hlo, "w") as f:
                f.write(hlo)
        if cli.dump_scopes:
            with open(cli.dump_scopes, "w") as f:
                json.dump(op_scopes(hlo), f, indent=1, sort_keys=True)

    # steady-state timing
    for _ in range(3):
        step()
    ex2 = mod._exec_group.execs[0]
    name = mod._exec_group.param_names[-1]
    ex2.arg_dict[name].asnumpy()
    if cli.trace:
        jax.profiler.start_trace(cli.trace)
    t0 = time.time()
    for _ in range(cli.num_steps):
        step()
    ex2.arg_dict[name].asnumpy()
    dt = time.time() - t0
    if cli.trace:
        jax.profiler.stop_trace()
    report["step_ms"] = round(1000 * dt / cli.num_steps, 2)
    report["img_per_sec"] = round(cli.batch_size * cli.num_steps / dt, 1)
    if report.get("xla_flops"):
        # measured MFU from XLA's own flop count, same denominator as the
        # live telemetry gauge (MXNET_TELEMETRY_PEAK_FLOPS-overridable)
        from mxnet_tpu import telemetry
        peak = telemetry.peak_flops()  # None: device kind has no listed peak
        report["mfu_xla_flops"] = round(
            report["xla_flops"] / (dt / cli.num_steps) / peak, 4) \
            if peak else None
    write_json("perf_probe.json", report)


if __name__ == "__main__":
    main()
