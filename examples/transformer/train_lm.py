"""Train a decoder-only transformer LM through the Module path.

The transformer-family counterpart of train_imagenet.py: real data from a
token .txt corpus (whitespace tokenization) or --benchmark mode with
synthetic tokens, optimized via the fused train step, attention through
the Pallas flash kernels. Beyond-reference model family (the 2017
reference's sequence example is example/rnn/lstm_bucketing.py).

Usage:
  python train_lm.py --benchmark 1 --seq-len 2048 --hidden 1024
  python train_lm.py --data-train corpus.txt --num-epochs 5
"""
import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import mxnet_tpu as mx


def add_args(parser):
    parser.add_argument("--data-train", type=str, default=None)
    parser.add_argument("--vocab-size", type=int, default=32000)
    parser.add_argument("--num-layers", type=int, default=4)
    parser.add_argument("--num-heads", type=int, default=8)
    parser.add_argument("--hidden", type=int, default=512)
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--optimizer", default="adam")
    parser.add_argument("--num-epochs", type=int, default=3)
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--benchmark", type=int, default=0)
    parser.add_argument("--num-steps", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--disp-batches", type=int, default=10)
    return parser


def _corpus_iter(path, vocab_size, seq_len, batch_size):
    """Whitespace-token corpus -> (b, s) windows, next-token labels."""
    with open(path) as f:
        toks = f.read().split()
    vocab = {}
    ids = np.array([vocab.setdefault(t, len(vocab) % vocab_size)
                    for t in toks], np.float32)
    n = (len(ids) - 1) // seq_len
    X = ids[:n * seq_len].reshape(n, seq_len)
    Y = ids[1:n * seq_len + 1].reshape(n, seq_len)
    return mx.io.NDArrayIter(X, Y, batch_size=batch_size, shuffle=True,
                             label_name="softmax_label")


def _synth_iter(vocab_size, seq_len, batch_size, batches):
    rng = np.random.RandomState(0)
    X = rng.randint(0, vocab_size,
                    size=(batches * batch_size, seq_len)).astype(np.float32)
    Y = (X + 1) % vocab_size
    return mx.io.NDArrayIter(X, Y, batch_size=batch_size,
                             label_name="softmax_label")


def benchmark(args, net):
    """Synthetic-token steady-state throughput via the fused Module step."""
    it = _synth_iter(args.vocab_size, args.seq_len, args.batch_size, 1)
    # the current context: the chip when one is attached, else the host
    mod = mx.mod.Module(net, label_names=("softmax_label",),
                        context=mx.current_context(),
                        compute_dtype=args.dtype)
    logging.info("benchmark on %s", mx.current_context())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(initializer=mx.init.Xavier(factor_type="in",
                                               magnitude=2.34))
    mod.init_optimizer(kvstore=args.kv_store, optimizer=args.optimizer,
                       optimizer_params={"learning_rate": args.lr})
    batch = it.next()

    def sync():
        name = mod._exec_group.param_names[-1]
        return mod._exec_group.execs[0].arg_dict[name].asnumpy()

    for _ in range(args.warmup):
        mod.forward_backward(batch)
        mod.update()
    sync()
    t0 = time.time()
    for _ in range(args.num_steps):
        mod.forward_backward(batch)
        mod.update()
    sync()
    dt = time.time() - t0
    toks = args.batch_size * args.seq_len * args.num_steps
    b, s, h, nh, l = (args.batch_size, args.seq_len, args.hidden,
                      args.num_heads, args.num_layers)
    v = args.vocab_size
    # 6ND matmul flops (N = block params + untied lm_head; the input
    # embedding is a gather, not a matmul — counting it would inflate
    # MFU) + the causal attention term, fwd+bwd
    n_params = l * 12 * h * h + v * h
    flops = 6.0 * n_params * toks + l * args.num_steps * \
        (0.5 * 4 * b * nh * s * s * (h // nh)) * 3
    return {"tokens_per_sec": toks / dt, "step_time_ms": dt * 1e3 /
            args.num_steps, "model_tflops": flops / dt / 1e12}


def main():
    args = add_args(argparse.ArgumentParser()).parse_args()
    logging.basicConfig(level=logging.INFO)
    net = mx.models.get_transformer_lm(
        vocab_size=args.vocab_size, num_layers=args.num_layers,
        num_heads=args.num_heads, hidden=args.hidden, seq_len=args.seq_len)
    if args.benchmark:
        stats = benchmark(args, net)
        print({k: round(v, 2) for k, v in stats.items()})
        return
    if args.data_train is None:
        raise SystemExit("--data-train or --benchmark 1 required")
    it = _corpus_iter(args.data_train, args.vocab_size, args.seq_len,
                      args.batch_size)
    mod = mx.mod.Module(net, label_names=("softmax_label",),
                        context=mx.current_context(),
                        compute_dtype=args.dtype)
    logging.info("training on %s", mx.current_context())
    mod.fit(it, num_epoch=args.num_epochs, optimizer=args.optimizer,
            optimizer_params={"learning_rate": args.lr},
            eval_metric=mx.metric.Perplexity(ignore_label=None),
            batch_end_callback=mx.callback.Speedometer(
                args.batch_size, args.disp_batches))


if __name__ == "__main__":
    main()
