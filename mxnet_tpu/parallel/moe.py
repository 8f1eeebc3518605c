"""Expert parallelism — a mixture-of-experts FFN with the expert
dimension sharded over a mesh axis (beyond-reference capability; the
2017 reference has no conditional computation at all).

Exact einsum-dispatch formulation (no capacity dropping): every token's
top-k expert outputs are combined with renormalized gate weights. Experts
live sharded — each device holds E/n expert FFNs and computes them for
the full token stream; the weighted combine is a ``psum`` over the expert
axis, which XLA lowers to an ICI all-reduce. This is the
communication-light exact scheme (tokens replicated, experts sharded);
capacity-based all-to-all dispatch is a drop-in change of the inner
function when token counts outgrow replication.
"""
from __future__ import annotations

import functools


def _gate_combine(x, gate_w, top_k):
    """combine[b, s, E]: renormalized top-k gate weight of each expert for
    each token — the single routing implementation shared by the sharded
    path and the dense oracle."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = jnp.einsum("bsd,de->bse", x, gate_w)
    weights, assign = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return jnp.sum(
        jax.nn.one_hot(assign, gate_w.shape[-1], dtype=x.dtype)
        * weights[..., None], axis=2)


def moe_ffn_reference(x, gate_w, w1, w2, top_k=1, act=None):
    """Dense single-device oracle. x: [b, s, d]; gate_w: [d, E];
    w1: [E, d, h]; w2: [E, h, d]."""
    import jax
    import jax.numpy as jnp

    act = act or jax.nn.gelu
    combine = _gate_combine(x, gate_w, top_k)
    hidden = act(jnp.einsum("bsd,edh->besh", x, w1))
    out = jnp.einsum("besh,ehd->besd", hidden, w2)
    return jnp.einsum("bse,besd->bsd", combine, out)


def _moe_inner(x, gate_w, w1, w2, *, axis, top_k, act):
    import jax
    import jax.numpy as jnp
    from jax import lax

    e_local = w1.shape[0]
    idx = lax.axis_index(axis)
    # routing is computed from the replicated gate everywhere (identical
    # on all shards; avoids a broadcast)
    combine = _gate_combine(x, gate_w, top_k)             # [b, s, E]
    local = lax.dynamic_slice_in_dim(combine, idx * e_local, e_local,
                                     axis=2)              # [b, s, E/n]
    hidden = act(jnp.einsum("bsd,edh->besh", x, w1))
    out = jnp.einsum("besh,ehd->besd", hidden, w2)
    partial = jnp.einsum("bse,besd->bsd", local, out)
    return lax.psum(partial, axis)


def moe_ffn(x, gate_w, w1, w2, mesh, axis: str = "expert", top_k: int = 1,
            act=None):
    """Expert-parallel MoE FFN. ``w1``/``w2`` are sharded on their expert
    dimension over ``axis`` of ``mesh``; ``x``/``gate_w`` replicated.
    Exact — matches ``moe_ffn_reference`` to float tolerance."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    act = act or jax.nn.gelu
    n = mesh.shape[axis]
    if gate_w.shape[-1] != w1.shape[0]:
        raise ValueError(
            "gate has %d experts but w1 has %d"
            % (gate_w.shape[-1], w1.shape[0]))
    if w1.shape[0] % n:
        raise ValueError(
            "experts (%d) must be divisible by mesh axis %r size %d"
            % (w1.shape[0], axis, n))
    inner = functools.partial(_moe_inner, axis=axis, top_k=top_k, act=act)
    fn = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=P())
    return fn(x, gate_w, w1, w2)
