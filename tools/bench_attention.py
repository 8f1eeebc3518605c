"""Flash-attention kernel benchmark — the framework's high-MFU path.

Times a jitted causal-attention TRAIN step (fwd + the Pallas backward
kernels) at transformer shapes, reporting achieved TFLOP/s and MFU against
the chip's bf16 peak. Causal attention FLOPs are counted as
0.5 * (4*b*h*s^2*d) forward + 2x that for backward (dQ + dK/dV each
recompute P), i.e. 3x forward — the same accounting PERF.md uses.

Usage: python tools/bench_attention.py [--seq 16384] [--steps 10]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_bench(batch=1, heads=8, head_dim=128, seq=16384, steps=10,
              block_q=512, block_k=1024):
    """Time the causal flash-attention train step; returns the record
    dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.attention import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    b, h, d = batch, heads, head_dim
    s = seq if on_tpu else min(seq, 512)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d), dt) * 0.1
    k = jax.random.normal(key, (b, s, h, d), dt) * 0.1
    v = jax.random.normal(key, (b, s, h, d), dt) * 0.1

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=block_q,
                            block_k=block_k)
        return jnp.mean(o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    chain = jax.jit(lambda q, dq: q + 0 * dq)  # data-dependence between steps

    g = step(q, k, v)
    q = chain(q, g[0])
    np.asarray(q[0, 0, 0, 0])
    t0 = time.time()
    for _ in range(steps):
        g = step(q, k, v)
        q = chain(q, g[0])
    np.asarray(q[0, 0, 0, 0])
    dt_s = (time.time() - t0) / steps

    fwd_flops = 0.5 * 4.0 * b * h * s * s * d  # causal: half the s^2 grid
    total = 3.0 * fwd_flops
    peak = 197e12 if on_tpu else None
    return {
        "metric": "flash_attention_train_tflops",
        "value": round(total / dt_s / 1e12, 2), "unit": "TFLOP/s",
        "seq": s, "batch": b, "heads": h, "head_dim": d,
        "step_ms": round(dt_s * 1e3, 2),
        "mfu": round(total / dt_s / peak, 4) if peak else None,
        "backend": jax.default_backend()}


def run_oracle_bench(batch=1, heads=8, head_dim=128, seq=16384, steps=10):
    """Same train-step timing through jax.experimental.pallas.ops.tpu
    splash attention — the mature upstream TPU kernel, benchmarked as the
    ceiling our kernel is chasing (TPU only; raises elsewhere)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as mask_lib,
    )

    if jax.default_backend() != "tpu":
        raise RuntimeError("splash attention oracle needs a TPU")
    b, h, d, s = batch, heads, head_dim, seq
    key = jax.random.PRNGKey(0)
    # splash layout is [heads, seq, d] per batch entry (vmap over batch)
    q = jax.random.normal(key, (b, h, s, d), jnp.bfloat16) * 0.1
    k = jax.random.normal(key, (b, h, s, d), jnp.bfloat16) * 0.1
    v = jax.random.normal(key, (b, h, s, d), jnp.bfloat16) * 0.1
    mask = mask_lib.MultiHeadMask(
        [mask_lib.CausalMask((s, s)) for _ in range(h)])
    kernel = splash.make_splash_mha_single_device(mask=mask)

    def loss(q, k, v):
        o = jax.vmap(kernel)(q, k, v)
        return jnp.mean(o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    chain = jax.jit(lambda q, dq: q + 0 * dq)
    g = step(q, k, v)
    q = chain(q, g[0])
    np.asarray(q[0, 0, 0, 0])
    t0 = time.time()
    for _ in range(steps):
        g = step(q, k, v)
        q = chain(q, g[0])
    np.asarray(q[0, 0, 0, 0])
    dt_s = (time.time() - t0) / steps
    total = 3.0 * 0.5 * 4.0 * b * h * s * s * d
    return {"metric": "splash_attention_oracle_tflops",
            "value": round(total / dt_s / 1e12, 2), "unit": "TFLOP/s",
            "seq": s, "step_ms": round(dt_s * 1e3, 2),
            "mfu": round(total / dt_s / 197e12, 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--block-q", type=int, default=None,
                    help="pin the q block (default: 512)")
    ap.add_argument("--block-k", type=int, default=None,
                    help="pin the k block (default: 1024)")
    ap.add_argument("--oracle", action="store_true",
                    help="also time upstream splash attention (the "
                         "ceiling reference)")
    cli = ap.parse_args()
    bq = 512 if cli.block_q is None else cli.block_q
    bk = 1024 if cli.block_k is None else cli.block_k
    print(json.dumps(run_bench(
        batch=cli.batch, heads=cli.heads, head_dim=cli.head_dim,
        seq=cli.seq, steps=cli.steps, block_q=bq, block_k=bk)))
    if cli.oracle:
        print(json.dumps(run_oracle_bench(
            batch=cli.batch, heads=cli.heads, head_dim=cli.head_dim,
            seq=cli.seq, steps=cli.steps)))


if __name__ == "__main__":
    main()
