"""GPT-2 style decoder-only LM in plain jax.numpy: the reference family.

Pre-norm blocks (LayerNorm eps 1e-5, exact GELU, learned positions, an
untied output head with a bias), as Cerebras-GPT publishes them.  Parameter
names and layouts are those of a ``[out, in]`` dense checkpoint:

  tok_embed_weight (V, H)     pos_embed_weight (1, P, H)
  layer{i}_ln1_gamma/beta     layer{i}_qkv_weight (3H, H), _bias  [3][heads][hd]
  layer{i}_proj_weight/bias   layer{i}_ln2_gamma/beta
  layer{i}_fc1_weight (I, H)  layer{i}_fc2_weight (H, I)
  ln_f_gamma/beta             lm_head_weight (V, H), lm_head_bias

Imports nothing of the program under test.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from .precision import (einsum, leaf_norms, leaf_slices,
                        seed_key)


def sizes(cfg, layers=None):
    """(vocab, hidden, heads, inner, positions, layers) of a config dict."""
    return (int(cfg["vocab_size"]), int(cfg["n_embd"]), int(cfg["n_head"]),
            int(cfg["n_inner"]), int(cfg["n_positions"]),
            int(cfg["n_layer"] if layers is None else layers))


def param_shapes(cfg, layers=None):
    v, h, _, inner, p, n = sizes(cfg, layers)
    shapes = {"tok_embed_weight": (v, h), "pos_embed_weight": (1, p, h)}
    for i in range(n):
        pre = "layer%d_" % i
        shapes.update({
            pre + "ln1_gamma": (h,), pre + "ln1_beta": (h,),
            pre + "qkv_weight": (3 * h, h), pre + "qkv_bias": (3 * h,),
            pre + "proj_weight": (h, h), pre + "proj_bias": (h,),
            pre + "ln2_gamma": (h,), pre + "ln2_beta": (h,),
            pre + "fc1_weight": (inner, h), pre + "fc1_bias": (inner,),
            pre + "fc2_weight": (h, inner), pre + "fc2_bias": (h,)})
    shapes.update({"ln_f_gamma": (h,), "ln_f_beta": (h,),
                   "lm_head_weight": (v, h), "lm_head_bias": (v,)})
    return shapes


def n_params(cfg, layers=None):
    return sum(int(np.prod(s)) for s in param_shapes(cfg, layers).values())


def _seeded_leaf(key, i, name, shape):
    """Leaf ``i`` (in the order of the sorted names) of the seeded weights."""
    x = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
    return x + 1.0 if name.endswith("_gamma") else x


def make_weights(cfg, seed, layers=None):
    """Seeded float32 weights, made on the default device in one jitted
    call: matrices and embeddings N(0, 0.02), gains 1 + N(0, 0.02), biases
    N(0, 0.02) (no leaf is constant, so no gradient is trivially right)."""
    shapes = param_shapes(cfg, layers)

    @jax.jit
    def make(key):
        return {name: _seeded_leaf(key, i, name, shp)
                for i, (name, shp) in enumerate(sorted(shapes.items()))}

    return make(seed_key(seed))


def _ln(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b


def _dense(x, w, b, prec):
    return einsum("...k,nk->...n", x, w, prec) + b


def _block(x, p, heads, prec):
    b, s, h = x.shape
    hd = h // heads
    y = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = _dense(y, p["qkv_weight"], p["qkv_bias"], prec)
    qkv = qkv.reshape(b, s, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    sc = einsum("bqhd,bkhd->bhqk", q, k, prec) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -jnp.inf)
    att = einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v, prec)
    x = x + _dense(att.reshape(b, s, h), p["proj_weight"], p["proj_bias"],
                   prec)
    y = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    y = jax.nn.gelu(_dense(y, p["fc1_weight"], p["fc1_bias"], prec),
                    approximate=False)
    return x + _dense(y, p["fc2_weight"], p["fc2_bias"], prec)


def logits_fn(params, tokens, heads, layers, prec="f32", remat=False):
    """Logits (b, s, V) of token ids (b, s) at positions 0..s-1."""
    s = tokens.shape[1]
    x = params["tok_embed_weight"][tokens] + params["pos_embed_weight"][0, :s]
    block = functools.partial(_block, heads=heads, prec=prec)
    if remat:
        block = jax.checkpoint(block)
    for i in range(layers):
        pre = "layer%d_" % i
        x = block(x, {k[len(pre):]: v for k, v in params.items()
                      if k.startswith(pre)})
    x = _ln(x, params["ln_f_gamma"], params["ln_f_beta"])
    return _dense(x, params["lm_head_weight"], params["lm_head_bias"], prec)


def mean_nll(params, tokens, labels, heads, layers, prec):
    lg = logits_fn(params, tokens, heads, layers, prec, remat=True)
    lp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def make_train_step(cfg, layers, opt, prec="f32"):
    """One jitted Adam step of the mean next-token loss over a whole batch,
    taken a few sequences at a time (so it fits beside nothing else):
    (params, m, v, tokens, labels, t) -> (params, m, v, loss, grad norms,
    grad slices),
    tokens and labels shaped (blocks, rows in a block, seq).  The gradient
    is the one the optimizer gets: d(mean loss)/d(param)."""
    heads = int(cfg["n_head"])
    b1, b2 = float(opt.get("beta1", 0.9)), float(opt.get("beta2", 0.999))
    eps, lr = float(opt.get("epsilon", 1e-8)), float(opt["learning_rate"])

    def step(params, m, v, tokens, labels, t):
        nb = tokens.shape[0]
        vg = jax.value_and_grad(mean_nll)

        def micro(carry, row):
            loss, grads = carry
            l, g = vg(params, row[0], row[1], heads, layers, prec)
            return (loss + l / nb,
                    jax.tree_util.tree_map(lambda a, c: a + c / nb, grads,
                                           g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(micro, (jnp.float32(0), zero),
                                        (tokens, labels))
        tf = t.astype(jnp.float32)
        lr_t = lr * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            new_m[k] = b1 * m[k] + (1.0 - b1) * grads[k]
            new_v[k] = b2 * v[k] + (1.0 - b2) * jnp.square(grads[k])
            new_p[k] = params[k] - lr_t * new_m[k] / (jnp.sqrt(new_v[k])
                                                      + eps)
        return (new_p, new_m, new_v, loss, leaf_norms(grads),
                leaf_slices(grads))

    return jax.jit(step, donate_argnums=(0, 1, 2))


def follow_training(cfg, layers, opt, seed, batches, steps=3, prec="f32",
                    devices=None):
    """The first ``steps`` optimizer steps from the seeded weights on
    ``batches`` (a list of (tokens, labels) int32 arrays, cycled).
    Over several ``devices`` the same arithmetic runs with one sequence of
    each block on each device (weights replicated): a four-chip cell's
    reference then takes as long as a one-chip cell's.
    Returns {"loss": [...], "grad_norm": {leaf: float}, "grad_slice":
    {leaf: array}, "delta_norm": {leaf: float}}; the gradient is the first
    step's."""
    params = make_weights(cfg, seed, layers)
    rows = len(devices) if devices else 1
    place = lambda x: x  # noqa: E731
    if rows > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices), ("d",))
        params = jax.device_put(params, NamedSharding(mesh, P()))
        place = lambda x: jax.device_put(  # noqa: E731
            x, NamedSharding(mesh, P(None, "d", None)))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = make_train_step(cfg, layers, opt, prec)
    losses, gnorm, t0 = [], None, time.perf_counter()
    for i in range(steps):
        tokens, labels = (place(x.reshape(-1, rows, x.shape[-1]))
                          for x in batches[i % len(batches)])
        params, m, v, loss, gn, gs = step(params, m, v, tokens, labels,
                                          jnp.int32(i + 1))
        losses.append(float(loss))
        print("[reference] step %d at %s: loss %.5f, %.1f s"
              % (i + 1, prec, losses[-1], time.perf_counter() - t0),
              flush=True)
        t0 = time.perf_counter()
        if i == 0:
            gnorm = {k: float(x) for k, x in gn.items()}
            gslice = {k: np.asarray(x) for k, x in gs.items()}
        del gn, gs
    del m, v
    if rows > 1:
        params = jax.device_put(params, devices[0])
    delta = delta_norms(cfg, layers, seed, params)
    return {"loss": losses, "grad_norm": gnorm, "grad_slice": gslice,
            "delta_norm": delta}


@functools.partial(jax.jit, static_argnums=(3, 4))
def _leaf_delta_norm(leaf, key, i, kind, h):
    """Norm of ``leaf`` minus leaf ``i`` of the seeded weights, which is made
    again inside this program and never kept.  ``kind`` is the leaf's name
    without its layer: one program for each kind, not for each leaf."""
    d = leaf.astype(jnp.float32) - _seeded_leaf(key, i, kind, leaf.shape)
    if kind == "qkv_bias":
        d = d.at[h:2 * h].set(0)
    return jnp.sqrt(jnp.sum(jnp.square(d)))


def delta_norms(cfg, layers, seed, params):
    """Per-leaf norm of ``params`` minus the seeded weights, the key third
    of every ``qkv_bias`` left out.  A key bias shifts all the scores of a
    query by one amount, which softmax ignores: its true gradient is zero,
    and Adam turns whatever rounding noise a precision leaves there into a
    full step.  Leaf by leaf: beside a program's live state there is no room
    for a second set of weights (module_train.first_gradient says why that
    matters)."""
    key, h = seed_key(seed), int(cfg["n_embd"])
    names = sorted(param_shapes(cfg, layers))
    return {name: float(_leaf_delta_norm(params[name], key, i,
                                         name.split("_", 1)[1], h))
            for i, name in enumerate(names)}


def make_scorer(cfg, layers, length, prec="f32"):
    """Jitted (params, tokens (1, length)) -> logits (length, V)."""
    heads = int(cfg["n_head"])

    @jax.jit
    def score(params, tokens):
        return logits_fn(params, tokens, heads, layers, prec)[0]

    return score
