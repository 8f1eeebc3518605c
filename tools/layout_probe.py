"""Microbenchmark: conv train-step (fwd+bwd) in NCHW vs NHWC logical layout
on representative ResNet-50 shapes, pure JAX, bf16.  Quantifies what layout
conversion is worth before touching the framework ops.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.hlo_analysis import peak_flops  # noqa: E402

B = int(sys.argv[1]) if len(sys.argv) > 1 else 256

# (C_in, C_out, H, kernel, stride) — one per ResNet-50 stage flavor
SHAPES = [
    (3, 64, 224, 7, 2),      # stem
    (64, 64, 56, 1, 1),      # 1x1
    (64, 64, 56, 3, 1),      # 3x3 stage1
    (256, 128, 56, 1, 2),    # downsample 1x1
    (128, 128, 28, 3, 1),    # 3x3 stage2
    (256, 256, 14, 3, 1),    # 3x3 stage3
    (512, 512, 7, 3, 1),     # 3x3 stage4
]


def bench(layout):
    total = 0.0
    flops = 0.0
    for ci, co, h, k, s in SHAPES:
        pad = (k - 1) // 2
        if layout == "NCHW":
            x = jnp.zeros((B, ci, h, h), jnp.bfloat16)
            w = jnp.zeros((co, ci, k, k), jnp.bfloat16)
            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NCHW", "OIHW", "NCHW"))
        else:
            x = jnp.zeros((B, h, h, ci), jnp.bfloat16)
            w = jnp.zeros((k, k, ci, co), jnp.bfloat16)
            dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))

        def loss(x, w):
            y = lax.conv_general_dilated(x, w, (s, s), [(pad, pad)] * 2,
                                         dimension_numbers=dn)
            return jnp.sum(y.astype(jnp.float32))

        g = jax.jit(jax.grad(loss, argnums=(0, 1)))
        r = g(x, w)
        jax.block_until_ready(r)
        n = 20
        t0 = time.time()
        for _ in range(n):
            r = g(x, w)
        jax.block_until_ready(r)
        dt = (time.time() - t0) / n
        ho = h // s
        f = 3 * 2 * B * co * ci * k * k * ho * ho  # fwd+bwd ~ 3x fwd MACs*2
        total += dt
        flops += f
        print(f"  {layout} ci={ci} co={co} h={h} k={k} s={s}: "
              f"{dt*1e3:.2f} ms  {f/dt/1e12:.1f} TF/s", flush=True)
    return total, flops


for layout in ("NCHW", "NHWC"):
    t, f = bench(layout)
    print(json.dumps({"layout": layout, "total_ms": round(t * 1e3, 2),
                      "tflops": round(f / t / 1e12, 1),
                      "mfu": round(f / t / peak_flops(), 3)
                      if peak_flops() else None}))
