"""ResNet v1 (He et al. 2015, bottleneck, stride on the 3x3) in plain
jax.numpy, NCHW: the reference family for image classification.

Parameter names: ``stem_conv_weight``, ``stem_bn_gamma/beta``,
``stage{s}_unit{u}_{a,b,c,sc}_conv_weight`` / ``_bn_gamma/beta``,
``fc1_weight`` (classes, 2048), ``fc1_bias``; batch-norm running statistics
``*_bn_moving_mean/var`` are auxiliary (made, never compared: training reads
the batch's own statistics).  Imports nothing of the program under test.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .precision import (_q8, leaf_diff_norms, leaf_slices, seed_key,
                        store_q8)

BN_EPS = 2e-5
BRANCH_GAIN = 0.05


def stages(cfg):
    return list(zip(cfg["units"], cfg["filters"]))


def conv_specs(cfg):
    """[(name, out_ch, in_ch, kernel, stride, pad, out_hw)] of every
    convolution, in order, at the config's image size."""
    hw = int(cfg["image_shape"][1])
    specs = []
    hw = (hw + 2 * 3 - 7) // 2 + 1
    specs.append(("stem", 64, int(cfg["image_shape"][0]), 7, 2, 3, hw))
    hw = (hw + 2 - 3) // 2 + 1
    cin = 64
    for s, (n_units, f) in enumerate(stages(cfg)):
        for u in range(n_units):
            stride = 1 if (s == 0 or u > 0) else 2
            pre = "stage%d_unit%d_" % (s + 1, u + 1)
            out_hw = hw // stride
            specs.append((pre + "a", f // 4, cin, 1, 1, 0, hw))
            specs.append((pre + "b", f // 4, f // 4, 3, stride, 1, out_hw))
            specs.append((pre + "c", f, f // 4, 1, 1, 0, out_hw))
            if u == 0:
                specs.append((pre + "sc", f, cin, 1, stride, 0, out_hw))
            cin, hw = f, out_hw
    return specs


def param_shapes(cfg):
    """({param: shape}, {aux: shape})."""
    params, aux = {}, {}
    for name, co, ci, k, _, _, _ in conv_specs(cfg):
        params[name + "_conv_weight"] = (co, ci, k, k)
        params[name + "_bn_gamma"] = (co,)
        params[name + "_bn_beta"] = (co,)
        aux[name + "_bn_moving_mean"] = (co,)
        aux[name + "_bn_moving_var"] = (co,)
    params["fc1_weight"] = (int(cfg["num_classes"]), cfg["filters"][-1])
    params["fc1_bias"] = (int(cfg["num_classes"]),)
    return params, aux


def make_weights(cfg, seed, layers=None):
    """Seeded float32 weights in one jitted call: He-normal convolutions,
    gains 1 + N(0, 0.1), shifts N(0, 0.1), classifier N(0, 0.01); the last
    gain of every residual branch is BRANCH_GAIN times that (Goyal et al.
    2017 start it at zero): with gains near 1 the seeded 50-layer network
    is chaotic, and a rounding of 0.4 % at the input leaves a gradient that
    has nothing in common with the float32 one.  Returns (params, aux)."""
    pshapes, ashapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shp) in enumerate(sorted(pshapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shp,
                                  jnp.float32)
            if name.endswith("_conv_weight"):
                out[name] = z * np.sqrt(2.0 / (shp[1] * shp[2] * shp[3]))
            elif name.endswith("_c_bn_gamma"):
                out[name] = BRANCH_GAIN * (1.0 + 0.1 * z)
            elif name.endswith("_gamma"):
                out[name] = 1.0 + 0.1 * z
            elif name == "fc1_weight":
                out[name] = 0.01 * z
            else:
                out[name] = 0.1 * z
        aux = {n: (jnp.ones(s, jnp.float32) if n.endswith("_var")
                   else jnp.zeros(s, jnp.float32))
               for n, s in ashapes.items()}
        return out, aux

    return make(seed_key(seed))


def _conv(x, w, stride, pad, prec):
    dn = ("NCHW", "OIHW", "NCHW")
    pads = [(pad, pad), (pad, pad)]
    if prec == "f32":
        return lax.conv_general_dilated(
            x, w, (stride, stride), pads, dimension_numbers=dn,
            precision=lax.Precision.HIGHEST)
    # the lower precisions round the operands and then multiply them at the
    # device's default precision (one bfloat16 pass on a TPU, exact for
    # values already rounded), accumulating in float32
    if prec == "bf16":
        qx, qw, scale = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), 1.0
    else:
        (qx, sx), (qw, sw) = _q8(x), _q8(w)
        scale = sx * sw
    y = lax.conv_general_dilated(
        qx.astype(jnp.float32), qw.astype(jnp.float32), (stride, stride),
        pads, dimension_numbers=dn) * scale
    return y if prec == "bf16" else store_q8(y)


def _bn(x, g, b):
    mean = jnp.mean(x, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), (0, 2, 3), keepdims=True)
    return ((x - mean) * lax.rsqrt(var + BN_EPS) * g.reshape(1, -1, 1, 1)
            + b.reshape(1, -1, 1, 1))


def _cba(x, p, name, stride, pad, prec, act=True):
    y = _bn(_conv(x, p[name + "_conv_weight"], stride, pad, prec),
            p[name + "_bn_gamma"], p[name + "_bn_beta"])
    y = jax.nn.relu(y) if act else y
    # float8 compute stores every activation in float8, as the program's
    # bfloat16 compute stores every one in bfloat16
    return store_q8(y) if prec == "fp8" else y


def _unit(x, p, pre, stride, first, prec):
    y = _cba(x, p, pre + "a", 1, 0, prec)
    y = _cba(y, p, pre + "b", stride, 1, prec)
    y = _cba(y, p, pre + "c", 1, 0, prec, act=False)
    sc = _cba(x, p, pre + "sc", stride, 0, prec, act=False) if first else x
    y = jax.nn.relu(y + sc)
    return store_q8(y) if prec == "fp8" else y


def logits_fn(params, images, cfg, prec="f32"):
    """Class scores (b, classes) of images (b, 3, H, W), training-mode
    batch normalisation."""
    x = _cba(images.astype(jnp.float32), params, "stem", 2, 3, prec)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, (n_units, _) in enumerate(stages(cfg)):
        for u in range(n_units):
            pre = "stage%d_unit%d_" % (s + 1, u + 1)
            stride = 1 if (s == 0 or u > 0) else 2
            unit = jax.checkpoint(_unit, static_argnums=(2, 3, 4, 5))
            x = unit(x, {k: v for k, v in params.items()
                         if k.startswith(pre)}, pre, stride, u == 0, prec)
    x = jnp.mean(x, (2, 3))
    if prec == "f32":
        return jnp.matmul(x, params["fc1_weight"].T,
                          precision=lax.Precision.HIGHEST) + params["fc1_bias"]
    return jnp.matmul(x, params["fc1_weight"].T) + params["fc1_bias"]


def mean_nll(params, images, labels, cfg, prec):
    lp = jax.nn.log_softmax(logits_fn(params, images, cfg, prec), -1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], -1))


def _decayed(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def make_train_step(cfg, opt, prec="f32"):
    """One jitted SGD-with-momentum step of the mean loss:
    (params, mom, images, labels) -> (params, mom, loss, grad norms, grad
    slices), the
    gradient as the optimizer gets it (weight decay added to weights and
    gains)."""
    lr, mu = float(opt["learning_rate"]), float(opt.get("momentum", 0.0))
    wd = float(opt.get("wd", 0.0))

    def step(params, mom, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: mean_nll(p, images, labels, cfg, prec))(params)
        new_p, new_m, gn, gs = {}, {}, {}, {}
        for k in params:
            g = grads[k] + (wd * params[k] if _decayed(k) else 0.0)
            gn[k] = jnp.sqrt(jnp.sum(jnp.square(g)))
            gs[k] = g
            new_m[k] = mu * mom[k] - lr * g
            new_p[k] = params[k] + new_m[k]
        return new_p, new_m, loss, gn, leaf_slices(gs)

    return jax.jit(step, donate_argnums=(0, 1))


def follow_training(cfg, layers, opt, seed, batches, steps=3, prec="f32",
                    devices=None):
    params, _ = make_weights(cfg, seed)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = make_train_step(cfg, opt, prec)
    losses, gnorm, t0 = [], None, time.perf_counter()
    for i in range(steps):
        images, labels = batches[i % len(batches)]
        params, mom, loss, gn, gs = step(params, mom, images, labels)
        losses.append(float(loss))
        print("[reference] step %d at %s: loss %.5f, %.1f s"
              % (i + 1, prec, losses[-1], time.perf_counter() - t0),
              flush=True)
        t0 = time.perf_counter()
        if i == 0:
            gnorm = {k: float(x) for k, x in gn.items()}
            gslice = {k: np.asarray(x) for k, x in gs.items()}
    del mom
    return {"loss": losses, "grad_norm": gnorm, "grad_slice": gslice,
            "delta_norm": delta_norms(cfg, layers, seed, params)}


def delta_norms(cfg, layers, seed, params):
    """Per-leaf norm of ``params`` minus the seeded weights."""
    first, _ = make_weights(cfg, seed)
    return {k: float(x) for k, x in leaf_diff_norms(params, first).items()}


def forward_macs(cfg):
    """Multiply-adds of one image's forward pass: every convolution and the
    classifier, from the shapes."""
    macs = sum(co * ci * k * k * hw * hw
               for _, co, ci, k, _, _, hw in conv_specs(cfg))
    return macs + cfg["filters"][-1] * int(cfg["num_classes"])
