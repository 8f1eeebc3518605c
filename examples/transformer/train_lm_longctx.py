"""Long-context LM training: sequence parallelism over a device mesh.

The long-context counterpart of train_lm.py. Activations are sharded
along the SEQUENCE axis of a ('data', 'seq') mesh; attention is
``ring_flash_attention`` (K/V and their gradients ride the ring via
ppermute, per-block compute is the Pallas flash kernel), so per-device
memory is O(seq/n_seq) and context length is bounded by the pod's HBM,
not one chip's. Everything else (matmuls, layernorm, losses) is
position-local, so XLA partitions it along the same axis with no extra
communication beyond the psum for data-parallel gradients.

This is the capability the 2017 reference could not express at all
(its longest-sequence story was bucketing, SURVEY.md §5.7).

Usage (8 virtual devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python train_lm_longctx.py --seq-len 1024 --seq-shards 4 --steps 5
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def build_params(rng, vocab, hidden, heads, layers, seq_len):
    import jax.numpy as jnp

    def glorot(*shape):
        scale = np.sqrt(2.0 / (shape[0] + shape[-1]))
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)

    params = {"embed": glorot(vocab, hidden),
              "pos": glorot(seq_len, hidden) * 0.1,
              "ln_f": {"g": jnp.ones(hidden), "b": jnp.zeros(hidden)},
              "head": glorot(hidden, vocab), "layers": []}
    for _ in range(layers):
        params["layers"].append({
            "ln1": {"g": jnp.ones(hidden), "b": jnp.zeros(hidden)},
            "qkv": glorot(hidden, 3 * hidden),
            "proj": glorot(hidden, hidden),
            "ln2": {"g": jnp.ones(hidden), "b": jnp.zeros(hidden)},
            "fc1": glorot(hidden, 4 * hidden),
            "fc2": glorot(4 * hidden, hidden)})
    return params


def make_step(mesh, heads, block, lr):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.ring import ring_flash_attention

    def ln(x, p):
        m = x.mean(-1, keepdims=True)
        v = ((x - m) ** 2).mean(-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * p["g"] + p["b"]

    def forward(params, tokens):
        b, s = tokens.shape
        h = params["embed"][tokens] + params["pos"][None, :s]
        for lp in params["layers"]:
            a = ln(h, lp["ln1"])
            qkv = a @ lp["qkv"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            d = q.shape[-1] // heads
            split = lambda t: t.reshape(b, s, heads, d)
            att = ring_flash_attention(split(q), split(k), split(v), mesh,
                                       axis="seq", batch_axis="data",
                                       causal=True,
                                       block_q=block, block_k=block)
            h = h + att.reshape(b, s, -1) @ lp["proj"]
            a = ln(h, lp["ln2"])
            h = h + jax.nn.gelu(a @ lp["fc1"]) @ lp["fc2"]
        return ln(h, params["ln_f"]) @ params["head"]

    def loss_fn(params, tokens, labels):
        logits = forward(params, tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return nll.mean()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(params, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        new = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                           params, grads)
        return new, loss

    return step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab-size", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-shards", type=int, default=4)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n_seq = args.seq_shards
    if len(devs) < n_seq:
        raise SystemExit(
            "need %d devices for --seq-shards %d, found %d (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=%d for a virtual mesh)"
            % (n_seq, n_seq, len(devs), n_seq))
    n_data = len(devs) // n_seq
    mesh = Mesh(np.array(devs[:n_data * n_seq]).reshape(n_data, n_seq),
                ("data", "seq"))
    rng = np.random.RandomState(0)
    params = build_params(rng, args.vocab_size, args.hidden, args.heads,
                          args.layers, args.seq_len)
    # deterministic task (+1 mod vocab) so the loss visibly falls
    X = rng.randint(0, args.vocab_size,
                    size=(args.batch * n_data, args.seq_len))
    Y = (X + 1) % args.vocab_size
    data_sh = NamedSharding(mesh, P("data", "seq"))
    tokens = jax.device_put(jnp.asarray(X, jnp.int32), data_sh)
    labels = jax.device_put(jnp.asarray(Y, jnp.int32), data_sh)
    params = jax.device_put(params, NamedSharding(mesh, P()))

    step = make_step(mesh, args.heads, args.block, args.lr)
    losses = []
    t0 = time.time()
    for i in range(args.steps):
        params, loss = step(params, tokens, labels)
        losses.append(float(loss))
        print("step %d loss %.4f" % (i, losses[-1]), flush=True)
    dt = time.time() - t0
    toks = args.batch * n_data * args.seq_len * args.steps
    print("tokens/s %.1f  first->last loss %.4f -> %.4f"
          % (toks / dt, losses[0], losses[-1]))
    return losses


if __name__ == "__main__":
    main()
