"""Sharding probe — make a GSPMD layout inspectable before burning a run.

Builds a bench model, binds it on a named mesh under partition rules, and
reports:

  * the resolved rule table (which regex claimed each parameter);
  * per-parameter sharding + the per-device HBM estimate vs replicated;
  * the post-SPMD HLO collective mix of the fused train step
    (all-reduce / all-gather / reduce-scatter / collective-permute) — the
    compiled truth of what the layout costs in comms.

The last stdout line is a single JSON record.  CPU-friendly: run with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8 for a simulated mesh.

Usage:
  python tools/shard_probe.py --model transformer --mesh data=-1,model=2 \
      --rules transformer_megatron [--steps 2] [--smoke]
"""
import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")


def build_mlp(batch):
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=256, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(h, name="softmax")
    return net, [("data", (batch, 128))], [("softmax_label", (batch,))]


def build_transformer(batch, seq_len=64, hidden=128, layers=2, heads=4,
                      vocab=512):
    from mxnet_tpu.models.transformer import get_transformer_lm

    net = get_transformer_lm(vocab_size=vocab, num_layers=layers,
                             num_heads=heads, hidden=hidden, seq_len=seq_len,
                             block_q=seq_len, block_k=seq_len)
    return net, [("data", (batch, seq_len))], \
        [("softmax_label", (batch, seq_len))]


def synthetic_batch(mx, data_shapes, label_shapes, vocab=512):
    import numpy as np

    rng = np.random.RandomState(7)
    data = []
    for _, shape in data_shapes:
        data.append(mx.nd.array(
            rng.randint(0, vocab, size=shape).astype(np.float32)))
    label = [mx.nd.array(rng.randint(0, 10, size=s).astype(np.float32))
             for _, s in label_shapes]
    return mx.io.DataBatch(data=data, label=label)


def collective_counts(hlo_text):
    counts = {}
    for op in COLLECTIVES:
        # opcode use sites: "<shape> all-reduce(" (start/done variants of
        # async collectives count toward their base opcode)
        n = len(re.findall(r"\b%s(?:-start)?\(" % re.escape(op), hlo_text))
        if n:
            counts[op] = n
    return counts


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="transformer",
                    choices=("mlp", "transformer"))
    ap.add_argument("--mesh", default="data=-1,model=2",
                    help="mesh layout, e.g. data=-1,model=2")
    ap.add_argument("--rules", default=None,
                    help="preset name (default: transformer_megatron for "
                         "--model transformer, replicated otherwise)")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="minimal run for CI: tiny model, 1 step")
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import sharding

    if args.rules is None:
        args.rules = ("transformer_megatron" if args.model == "transformer"
                      else "replicated")
    if args.smoke:
        args.steps = 1

    mesh = sharding.build_mesh(args.mesh)
    rules = sharding.as_rules(args.rules)
    if args.model == "mlp":
        net, data_shapes, label_shapes = build_mlp(args.batch_size)
    else:
        net, data_shapes, label_shapes = build_transformer(args.batch_size)

    mod = mx.mod.Module(net, context=mx.current_context())
    mod.bind(data_shapes=data_shapes, label_shapes=label_shapes,
             mesh=mesh, partition_rules=rules)
    mod.init_params(initializer=mx.init.Xavier(magnitude=2.0))
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})

    group = mod._exec_group
    executor = group.execs[0]
    shapes = {n: tuple(executor.arg_dict[n].shape) for n in group.param_names}
    shapes.update({n: tuple(executor.aux_dict[n].shape)
                   for n in group.aux_names})
    print("== mesh ==")
    print(sharding.mesh_axes(mesh))
    print("\n== rule table ==")
    print(rules.explain_str(shapes))

    print("\n== per-parameter sharding ==")
    params = {n: executor.arg_dict[n] for n in group.param_names}
    params.update({n: executor.aux_dict[n] for n in group.aux_names})
    for name, arr in sorted(params.items()):
        factor = sharding.spec_shard_factor(
            mesh, group._param_specs.get(name)) \
            if group._param_specs.get(name) is not None else 1
        print("%-28s %-16s %d-way  %s" % (
            name, tuple(arr.shape), factor,
            tuple(group._param_specs.get(name, ()))))
    per_dev, repl = sharding.param_bytes(params.values())
    print("\nper-device param bytes: %d (replicated would be %d, %.2fx)"
          % (per_dev, repl, repl / max(per_dev, 1)))

    batch = synthetic_batch(mx, data_shapes, label_shapes)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        mod.forward_backward(batch)
        mod.update()
    for o in mod.get_outputs():
        o.wait_to_read()
    step_ms = (time.perf_counter() - t0) / max(args.steps, 1) * 1e3

    collectives = {}
    fn, abstract = getattr(executor, "_fused_introspect", (None, None))
    if fn is not None and hasattr(fn, "lower"):
        hlo = fn.lower(*abstract).compile().as_text()
        collectives = collective_counts(hlo)
        print("\n== post-SPMD fused-step collectives ==")
        print(collectives or "(none)")

    record = {
        "probe": "shard",
        "model": args.model,
        "mesh": sharding.mesh_axes(mesh),
        "rules": rules.name,
        "params_sharded_bytes": per_dev,
        "params_replicated_bytes": repl,
        "collectives": collectives,
        "avg_step_ms": round(step_ms, 2),
        "steps": args.steps,
    }
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
