"""Sequence/pipeline parallelism over the 8-virtual-device CPU mesh — exact
against single-device oracles (the reference has no such capability; these
are the new first-class components of SURVEY.md §7 step 8)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.test_utils import assert_almost_equal


def _qkv(b=2, s=32, h=4, d=8, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, s, h, d).astype(dtype)
    k = rng.randn(b, s, h, d).astype(dtype)
    v = rng.randn(b, s, h, d).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_local(causal):
    import jax.numpy as jnp

    q, k, v = _qkv()
    mesh = parallel.make_mesh({"seq": 8})
    ref = parallel.local_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=causal)
    out = parallel.ring_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), mesh, causal=causal)
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_matches_local(causal):
    """Ring attention with the Pallas flash kernel as the per-block
    compute — fwd AND custom ring-level vjp vs the dense oracle."""
    import jax
    import jax.numpy as jnp

    q, k, v = _qkv(b=2, s=128, h=2, d=16)
    mesh = parallel.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    qj, kj, vj = (jnp.asarray(t) for t in (q, k, v))
    ref = parallel.local_attention(qj, kj, vj, causal=causal)
    out = parallel.ring_flash_attention(qj, kj, vj, mesh, causal=causal,
                                        block_q=32, block_k=32)
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    g = jax.grad(loss(lambda q, k, v: parallel.ring_flash_attention(
        q, k, v, mesh, causal=causal, block_q=32, block_k=32)),
        argnums=(0, 1, 2))(qj, kj, vj)
    gr = jax.grad(loss(lambda q, k, v: parallel.local_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(qj, kj, vj)
    for a, b in zip(g, gr):
        assert_almost_equal(np.asarray(a), np.asarray(b),
                            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_local(causal):
    import jax.numpy as jnp

    q, k, v = _qkv(h=8)
    mesh = parallel.make_mesh({"seq": 8})
    ref = parallel.local_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=causal)
    out = parallel.ulysses_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), mesh, causal=causal)
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)


def test_ring_attention_2d_mesh_batch_sharded():
    """dp x sp: batch on 'data', sequence on 'seq'."""
    import jax.numpy as jnp

    q, k, v = _qkv(b=4, s=16)
    mesh = parallel.make_mesh({"data": 2, "seq": 4})
    ref = parallel.local_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=True)
    out = parallel.ring_attention(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), mesh, axis="seq",
                                  batch_axis="data", causal=True)
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)


def test_ring_attention_grads_match():
    import jax
    import jax.numpy as jnp

    q, k, v = _qkv(s=16)
    mesh = parallel.make_mesh({"seq": 8})

    def loss_ring(q, k, v):
        return parallel.ring_attention(q, k, v, mesh, causal=True).sum()

    def loss_ref(q, k, v):
        return parallel.local_attention(q, k, v, causal=True).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for a, b in zip(g_ring, g_ref):
        assert_almost_equal(np.asarray(a), np.asarray(b),
                            rtol=1e-4, atol=1e-4)


def test_pipeline_spmd_matches_sequential():
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    n_stages, d, batch = 4, 6, 8
    ws = rng.randn(n_stages, d, d).astype(np.float32) * 0.3
    x = rng.randn(batch, d).astype(np.float32)

    def stage_fn(w, a):
        return jnp.tanh(a @ w)

    import jax

    mesh = parallel.make_mesh({"pipe": 4}, devices=jax.devices()[:4])
    out = parallel.pipeline_spmd(stage_fn, jnp.asarray(ws), jnp.asarray(x),
                                 mesh, axis="pipe", n_microbatches=4)
    ref = x
    for i in range(n_stages):
        ref = np.tanh(ref @ ws[i])
    assert_almost_equal(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_mesh_config_infer():
    mesh = parallel.make_mesh({"data": -1, "model": 2})
    assert mesh.shape["model"] == 2
    assert mesh.shape["data"] * 2 == len(mesh.devices.ravel())


def test_current_mesh_scope():
    mesh = parallel.data_parallel_mesh()
    assert parallel.current_mesh() is None
    with parallel.set_current_mesh(mesh):
        assert parallel.current_mesh() is mesh
    assert parallel.current_mesh() is None


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_expert_parallel_matches_reference(top_k):
    """Expert-parallel MoE FFN (experts sharded over the mesh, psum
    combine) vs the dense single-device oracle — fwd and gradients."""
    import jax
    import jax.numpy as jnp

    mesh = parallel.make_mesh({"expert": 4}, devices=jax.devices()[:4])
    b, s, d, h, E = 2, 6, 8, 16, 8
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
    gw = jnp.asarray(rng.randn(d, E).astype(np.float32)) * 0.5
    w1 = jnp.asarray(rng.randn(E, d, h).astype(np.float32)) * 0.3
    w2 = jnp.asarray(rng.randn(E, h, d).astype(np.float32)) * 0.3
    out = parallel.moe_ffn(x, gw, w1, w2, mesh, top_k=top_k)
    ref = parallel.moe_ffn_reference(x, gw, w1, w2, top_k=top_k)
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-6)
    g = jax.grad(lambda w: jnp.sum(
        parallel.moe_ffn(x, gw, w, w2, mesh, top_k=top_k) ** 2))(w1)
    gr = jax.grad(lambda w: jnp.sum(
        parallel.moe_ffn_reference(x, gw, w, w2, top_k=top_k) ** 2))(w1)
    assert_almost_equal(np.asarray(g), np.asarray(gr),
                        rtol=1e-4, atol=1e-5)


def test_moe_validates_expert_divisibility():
    import jax
    import jax.numpy as jnp

    mesh = parallel.make_mesh({"expert": 4}, devices=jax.devices()[:4])

    x = jnp.zeros((1, 2, 4))
    with pytest.raises(ValueError, match="divisible"):
        parallel.moe_ffn(x, jnp.zeros((4, 6)), jnp.zeros((6, 4, 8)),
                         jnp.zeros((6, 8, 4)), mesh)
    with pytest.raises(ValueError, match="gate has"):
        parallel.moe_ffn(x, jnp.zeros((4, 8)), jnp.zeros((4, 4, 8)),
                         jnp.zeros((4, 8, 4)), mesh)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_vma_typing(monkeypatch, causal):
    """Trace the ring fwd+bwd under shard_map(check_vma=True) — the TPU
    varying-axis checker. Pallas interpret mode itself trips the checker
    (unrelated dynamic_slice issue), so the kernels are swapped for dense
    stand-ins with identical signatures/outputs; what this validates is
    the ring code's own typing: every lax.switch branch (including the
    causal skip branches) and every scan carry must agree."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.parallel import ring
    from jax import shard_map

    def dense_fwd(q, k, v, causal, scale, bq, bk, interpret):
        b, s, h, d = q.shape
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if causal:
            m = jnp.arange(s)[:, None] >= jnp.arange(k.shape[1])[None, :]
            sc = jnp.where(m[None, None], sc, -1e30)
        mx_ = sc.max(-1, keepdims=True)
        p = jnp.exp(sc - mx_)
        l = p.sum(-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", p / l, v).astype(q.dtype)
        lse = (mx_[..., 0] + jnp.log(l[..., 0])).reshape(b * h, s)
        return o, lse

    def dense_bwd(q, k, v, o, lse, do, causal, scale, bq, bk, interpret,
                  pre=None):
        _, vjp = jax.vjp(
            lambda q, k, v: dense_fwd(q, k, v, causal, scale, bq, bk,
                                      interpret)[0], q, k, v)
        return vjp(do)

    monkeypatch.setattr(att, "_flash_forward", dense_fwd)
    monkeypatch.setattr(att, "_flash_backward", dense_bwd)

    mesh = parallel.make_mesh({"seq": 4},
                              devices=jax.devices()[:4])
    q, k, v = (jnp.asarray(t) for t in _qkv(b=1, s=64, h=2, d=8))
    scale = 1.0 / np.sqrt(8)
    kw = dict(axis="seq", vary_axes=("seq",), n_shards=4, causal=causal,
              scale=scale, block_q=16, block_k=16, interpret=True)
    spec = P(None, "seq", None, None)

    def fwd_then_bwd(q, k, v):
        o, lse = ring._ring_flash_fwd(q, k, v, **kw)
        dq, dk, dv = ring._ring_flash_bwd(q, k, v, o, lse,
                                          jnp.ones_like(o), **kw)
        return o, dq, dk, dv

    fn = shard_map(fwd_then_bwd, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=(spec, spec, spec, spec), check_vma=True)
    o, dq, dk, dv = fn(q, k, v)  # raises TypeError on any vma mismatch
    ref = parallel.local_attention(q, k, v, causal=causal)
    assert_almost_equal(np.asarray(o), np.asarray(ref),
                        rtol=1e-4, atol=1e-5)
