"""Shared training driver for the image-classification examples
(reference: example/image-classification/common/fit.py — kvstore creation,
checkpoint/resume, LR schedule, Speedometer, --benchmark synthetic mode)."""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))

import mxnet_tpu as mx  # noqa: E402


def add_fit_args(parser: argparse.ArgumentParser):
    parser.add_argument("--network", type=str, default="lenet")
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--lr-factor", type=float, default=0.1)
    parser.add_argument("--lr-step-epochs", type=str, default="")
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--optimizer", type=str, default="sgd")
    parser.add_argument("--kv-store", type=str, default="local")
    parser.add_argument("--model-prefix", type=str, default=None)
    parser.add_argument("--load-epoch", type=int, default=None)
    parser.add_argument("--disp-batches", type=int, default=20)
    parser.add_argument("--benchmark", type=int, default=0,
                        help="1 = synthetic data, report img/s only")
    parser.add_argument("--test-io", type=int, default=0,
                        help="1 = run the data iterator alone and report "
                             "IO img/s (reference fit.py:106-116)")
    parser.add_argument("--num-examples", type=int, default=60000)
    parser.add_argument("--num-classes", type=int, default=10)
    parser.add_argument("--image-shape", type=str, default="1,28,28")
    parser.add_argument("--dtype", type=str, default="float32")
    return parser


class SyntheticIter(mx.io.DataIter):
    """--benchmark 1 data source (reference fit.py:106-116): random batch
    repeated, no host pipeline in the loop."""

    def __init__(self, data_shape, label_range, batch_size, num_batches=50):
        super().__init__(batch_size)
        rng = np.random.RandomState(0)
        self._data = mx.nd.array(
            rng.uniform(-1, 1, (batch_size,) + data_shape).astype(np.float32))
        self._label = mx.nd.array(
            rng.randint(0, label_range, (batch_size,)).astype(np.float32))
        self.num_batches = num_batches
        self._cur = 0
        self.provide_data = [mx.io.DataDesc("data",
                                            (batch_size,) + data_shape)]
        self.provide_label = [mx.io.DataDesc("softmax_label", (batch_size,))]

    def reset(self):
        self._cur = 0

    def next(self):
        if self._cur >= self.num_batches:
            raise StopIteration
        self._cur += 1
        return mx.io.DataBatch(data=[self._data], label=[self._label], pad=0)


def _lr_scheduler(args, kv, epoch_size):
    if not args.lr_step_epochs:
        return None
    steps = [int(e) for e in args.lr_step_epochs.split(",") if e]
    begin = args.load_epoch or 0
    steps = [epoch_size * (s - begin) for s in steps
             if epoch_size * (s - begin) > 0]
    if not steps:
        return None
    return mx.lr_scheduler.MultiFactorScheduler(step=steps,
                                                factor=args.lr_factor)


def _compute_dtype(args):
    return args.dtype if args.dtype not in ("float32", None) else None


def benchmark(args, network, num_steps=30, warmup=5):
    """--benchmark mode through the REAL Module path (bind / init_optimizer /
    forward_backward / update / update_metric — the same statements
    BaseModule.fit runs), timing steady-state steps with compile excluded.
    Returns a stats dict; reference equivalent: common/fit.py:106-116
    synthetic-data mode."""
    shape = tuple(int(x) for x in args.image_shape.split(","))
    train = SyntheticIter(shape, args.num_classes, args.batch_size,
                          num_batches=num_steps + warmup)
    mod = mx.mod.Module(network, context=mx.current_context(),
                        compute_dtype=_compute_dtype(args))
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label, for_training=True)
    mod.init_params(initializer=mx.init.Xavier(factor_type="in",
                                               magnitude=2.34))
    opt_params = {"learning_rate": args.lr, "wd": args.wd,
                  "rescale_grad": 1.0 / args.batch_size}
    if args.optimizer in ("sgd", "nag"):
        opt_params["momentum"] = args.momentum
    mod.init_optimizer(kvstore=args.kv_store, optimizer=args.optimizer,
                       optimizer_params=opt_params)
    metric = mx.metric.Accuracy()
    batch = train.next()

    def step():
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)

    def sync():
        # pull one small param: its value depends on every prior update, so
        # this bounds the whole async chain
        name = mod._exec_group.param_names[-1]
        return mod._exec_group.execs[0].arg_dict[name].asnumpy()

    for _ in range(warmup):
        step()
    sync()
    t0 = time.time()
    for _ in range(num_steps):
        step()
    sync()
    dt = time.time() - t0
    final_param = sync()
    acc = metric.get()[1]
    return {"img_per_sec": args.batch_size * num_steps / dt,
            "step_time_ms": 1000.0 * dt / num_steps,
            "batch_size": args.batch_size, "dtype": args.dtype,
            "accuracy": acc,
            "finite": bool(np.all(np.isfinite(final_param)))}


def fit(args, network, data_loader):
    """args: parsed CLI; network: Symbol; data_loader(args, kv) ->
    (train_iter, val_iter_or_None)."""
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)-15s %(message)s")
    if args.benchmark:
        stats = benchmark(args, network)
        print('{"metric": "img_per_sec", "value": %.2f}'
              % stats["img_per_sec"])
        return stats
    kv = mx.kvstore.create(args.kv_store)
    train, val = data_loader(args, kv)

    if getattr(args, "test_io", 0):
        # IO-only throughput: drain the train iterator, no compute in the
        # loop (reference common/fit.py:106-116, the --test-io mode used to
        # prove the decode pipeline can feed the chip)
        tic = time.time()
        n = 0
        for epoch in range(args.num_epochs):
            train.reset()
            for batch in train:
                batch.data[0].wait_to_read()
                n += args.batch_size
                if n % (args.batch_size * args.disp_batches) == 0:
                    logging.info("io-test %d samples, %.1f img/s", n,
                                 n / (time.time() - tic))
        dt = time.time() - tic
        stats = {"io_img_per_sec": n / dt, "samples": n}
        print('{"metric": "io_img_per_sec", "value": %.2f}'
              % stats["io_img_per_sec"])
        return stats

    arg_params = aux_params = None
    begin_epoch = 0
    if args.model_prefix and args.load_epoch is not None:
        network, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
        begin_epoch = args.load_epoch
        logging.info("resumed from %s epoch %d", args.model_prefix,
                     args.load_epoch)

    epoch_size = max(1, args.num_examples // args.batch_size)
    # the current context: the chip when one is attached, else the host
    mod = mx.mod.Module(network, context=mx.current_context(),
                        compute_dtype=_compute_dtype(args))
    logging.info("training on %s", mx.current_context())
    batch_end = [mx.callback.Speedometer(args.batch_size,
                                         args.disp_batches)]
    epoch_end = []
    if args.model_prefix:
        epoch_end.append(mx.callback.do_checkpoint(args.model_prefix))
    opt_params = {"learning_rate": args.lr, "wd": args.wd}
    if args.optimizer in ("sgd", "nag"):
        opt_params["momentum"] = args.momentum
    sched = _lr_scheduler(args, kv, epoch_size)
    if sched is not None:
        opt_params["lr_scheduler"] = sched

    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            begin_epoch=begin_epoch, arg_params=arg_params,
            aux_params=aux_params, optimizer=args.optimizer,
            optimizer_params=opt_params, kvstore=kv,
            eval_metric=mx.metric.Accuracy(),
            batch_end_callback=batch_end, epoch_end_callback=epoch_end,
            initializer=mx.init.Xavier(factor_type="in", magnitude=2.34))
    return mod
