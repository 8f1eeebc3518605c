"""Collective-permute pipeline parallelism over a 'pipe' mesh axis.

TPU-native form of the reference's manual model parallelism: where the
reference pins LSTM layers to GPUs and splices ``_CrossDeviceCopy`` nodes at
the boundaries (src/executor/graph_executor.cc:230-320,
example/model-parallel-lstm/lstm.py:142-205), here every device holds one
stage's parameters (stacked and sharded over 'pipe') and microbatch
activations stream stage-to-stage with ``lax.ppermute`` — the GPipe schedule
compiled into one SPMD program.
"""
from __future__ import annotations

import functools


def _pipeline_inner(params, xs, *, axis, n_stages, n_micro, stage_fn):
    import jax.numpy as jnp
    from jax import lax

    stage = lax.axis_index(axis)
    # local params arrive with a leading stage axis of length 1
    local_params = _tree_squeeze(params)
    n_steps = n_micro + n_stages - 1
    micro_shape = xs.shape[1:]
    # initial carries must be typed varying over the pipe axis (shard_map
    # VMA typing — the loop outputs depend on stage-varying params)
    state0 = lax.pcast(jnp.zeros(micro_shape, xs.dtype), (axis,),
                       to="varying")
    out0 = lax.pcast(jnp.zeros((n_micro,) + micro_shape, xs.dtype), (axis,),
                     to="varying")
    fwd_perm = [(j, j + 1) for j in range(n_stages - 1)]

    def step(carry, t):
        state, outs = carry
        feed = xs[jnp.minimum(t, n_micro - 1)]
        inp = jnp.where(stage == 0, feed, state)
        out = stage_fn(local_params, inp)
        # last stage: record finished microbatch t-(n_stages-1)
        done_idx = t - (n_stages - 1)
        record = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
        idx = jnp.maximum(done_idx, 0)
        outs = jnp.where(
            record,
            outs.at[idx].set(out),
            outs)
        state = lax.ppermute(out, axis, fwd_perm)
        return (state, outs), None

    (_, outs), _ = lax.scan(step, (state0, out0), jnp.arange(n_steps))
    # outputs live only on the last stage; zero elsewhere then psum to
    # replicate them across the pipe axis
    outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
    return lax.psum(outs, axis)


def _tree_squeeze(params):
    import jax

    return jax.tree_util.tree_map(lambda p: p[0], params)


def pipeline_spmd(stage_fn, stage_params, x, mesh, axis: str = "pipe",
                  n_microbatches: int = None):
    """Run ``n_stages`` homogeneous stages as a pipeline over ``axis``.

    ``stage_fn(params_i, act) -> act`` must preserve the activation shape.
    ``stage_params``: pytree whose leaves have leading dim n_stages (sharded
    over ``axis``). ``x``: [batch, ...] global input; split into
    ``n_microbatches`` along batch. Returns [batch, ...] outputs (replicated
    over ``axis``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n_stages = mesh.shape[axis]
    if n_microbatches is None:
        n_microbatches = n_stages
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (batch, n_microbatches))
    xs = jnp.reshape(x, (n_microbatches, batch // n_microbatches) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda _: P(axis), stage_params)
    inner = functools.partial(_pipeline_inner, axis=axis, n_stages=n_stages,
                              n_micro=n_microbatches, stage_fn=stage_fn)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(param_specs, P()), out_specs=P())
    outs = fn(stage_params, xs)
    return jnp.reshape(outs, (batch,) + x.shape[1:])
