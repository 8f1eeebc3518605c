"""Pallas flash-attention kernel vs the dense oracle (interpret mode on the
CPU backend exercises the real kernel logic)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.attention import flash_attention, _reference_attention
from mxnet_tpu.test_utils import assert_almost_equal


def _qkv(b=2, s=128, h=2, d=32, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _reference_attention(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)


def test_flash_gradients_match():
    import jax
    import jax.numpy as jnp

    q, k, v = _qkv(s=64)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32,
                               block_k=32).sum()

    def f_ref(q, k, v):
        return _reference_attention(q, k, v, True, scale).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        assert_almost_equal(np.asarray(a), np.asarray(b),
                            rtol=1e-4, atol=1e-4)


def test_flash_op_registered():
    rng = np.random.RandomState(0)
    q = nd.array(rng.randn(1, 64, 2, 32).astype(np.float32))
    k = nd.array(rng.randn(1, 64, 2, 32).astype(np.float32))
    v = nd.array(rng.randn(1, 64, 2, 32).astype(np.float32))
    out = nd._contrib_FlashAttention(q, k, v, causal=True, block_q=32,
                                     block_k=32)
    ref = _reference_attention(q._data, k._data, v._data, True,
                               1.0 / np.sqrt(32))
    assert_almost_equal(out.asnumpy(), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_in_ulysses():
    """flash kernel as the local attention inside all-to-all sequence
    parallelism."""
    import jax.numpy as jnp

    from mxnet_tpu import parallel

    q, k, v = _qkv(s=128, h=8)
    mesh = parallel.make_mesh({"seq": 8})
    ref = _reference_attention(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]))

    def attn(q, k, v, causal, scale):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=32, block_k=32)

    out = parallel.ulysses_attention(q, k, v, mesh, causal=True,
                                     attn_fn=attn)
    assert_almost_equal(np.asarray(out), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)


def test_flash_backward_kernel_vs_dense_oracle():
    """The Pallas backward kernels (dQ + dK/dV, flash-v2 schedule) must
    match the dense vjp across causal/non-causal, rectangular seqs, and
    bf16 — and they ARE the training path (custom_vjp uses the kernels,
    not the dense oracle)."""
    import jax
    import jax.numpy as jnp

    np.random.seed(0)
    configs = [
        (2, 16, 16, 2, 8, True, jnp.float32, 2e-4),
        (1, 32, 16, 1, 8, False, jnp.float32, 2e-4),
        (2, 24, 24, 2, 4, True, jnp.float32, 2e-4),
        (1, 16, 16, 2, 8, True, jnp.bfloat16, 2e-2),
    ]
    for b, s, sk, h, d, causal, dt, tol in configs:
        q = jnp.asarray(np.random.randn(b, s, h, d).astype("f") * 0.4, dt)
        k = jnp.asarray(np.random.randn(b, sk, h, d).astype("f") * 0.4, dt)
        v = jnp.asarray(np.random.randn(b, sk, h, d).astype("f") * 0.4, dt)

        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=8,
                block_k=8).astype(jnp.float32) ** 2)

        def g(q, k, v):
            return jnp.sum(_reference_attention(
                q, k, v, causal, 1.0 / np.sqrt(d)).astype(jnp.float32) ** 2)

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for name, a, bb in zip("qkv", gf, gg):
            err = float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - bb.astype(jnp.float32))))
            ref = float(jnp.max(jnp.abs(bb.astype(jnp.float32)))) + 1e-6
            assert err / ref < tol, (name, causal, dt, err / ref)


def test_flash_long_sequence_train_step():
    """Long-sequence training step through the kernel path: K/V stream
    block-by-block (nothing whole-sequence is staged in VMEM), so seq
    length is HBM-bound.  16k+ on the TPU chip; a shorter structural run
    on the CPU interpreter."""
    import jax
    import jax.numpy as jnp

    on_tpu = jax.default_backend() == "tpu"
    s = 16384 if on_tpu else 256
    b, h, d = 1, 2, 64
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d), dt) * 0.2
    k = jax.random.normal(key, (b, s, h, d), dt) * 0.2
    v = jax.random.normal(key, (b, s, h, d), dt) * 0.2
    w = jnp.eye(d, dtype=dt)

    def loss(w, q, k, v):
        o = flash_attention(q @ w, k, v, causal=True)
        return jnp.mean(o.astype(jnp.float32) ** 2)

    step = jax.jit(jax.value_and_grad(loss))
    val, grad = step(w, q, k, v)
    gnorm = float(jnp.linalg.norm(grad.astype(jnp.float32)))
    assert np.isfinite(float(val)) and gnorm > 0
    # normalized step so the loss moves resolvably in f32
    val2, _ = step(w - (0.05 / gnorm) * grad.astype(dt), q, k, v)
    assert np.isfinite(float(val2))
    assert float(val2) < float(val)


# (seq_q, seq_k, head_dim, block_q, block_k, causal).  The forward's schedule
# (PR 40): K/V index maps clamped to a q tile's last live block, blocks under
# the diagonal unmasked, square blocks on it worked through in 512 x 512
# tiles with those above it left out, the softmax state a row a sublane.
SCHEDULES = {
    "causal_one_block": (64, 64, 16, 64, 64, True),
    "causal_two_blocks": (64, 64, 16, 32, 32, True),
    "causal_four_blocks": (128, 128, 16, 32, 32, True),
    # a q tile's diagonal crosses four k blocks
    "causal_bq_over_bk": (128, 128, 16, 64, 16, True),
    # a k block is crossed by the diagonals of four q tiles; dead blocks
    # after the clamp in all but the last
    "causal_bq_under_bk": (128, 128, 16, 16, 64, True),
    "causal_more_rows_than_keys": (96, 32, 16, 16, 16, True),
    # keys beyond every row: k tiles with no live q block (dK/dV's clamp)
    "causal_more_keys_than_rows": (32, 96, 16, 16, 16, True),
    "noncausal": (64, 64, 16, 32, 32, False),
    # the ring's off-diagonal shard
    "noncausal_rectangular": (32, 96, 16, 32, 16, False),
    "shorter_than_the_block": (24, 24, 8, 512, 512, True),
    # 512 x 512 tiles inside a block: one block under the diagonal and two
    # on it, each with a tile that is not computed
    "causal_tiles_in_a_block": (2048, 2048, 8, 1024, 1024, True),
    "noncausal_tiles_in_a_block": (1024, 1024, 8, 1024, 1024, False),
    # what a call without block sizes runs: one 2048 block, ten live tiles
    "causal_default_blocks": (2048, 2048, 8, None, None, True),
}


def _schedule_case(name, heads=2):
    import jax.numpy as jnp

    sq, sk, d, bq, bk, causal = SCHEDULES[name]
    rng = np.random.RandomState(len(name))
    q = jnp.asarray(rng.randn(1, sq, heads, d).astype(np.float32))
    k = jnp.asarray(rng.randn(1, sk, heads, d).astype(np.float32))
    v = jnp.asarray(rng.randn(1, sk, heads, d).astype(np.float32))
    return q, k, v, bq, bk, causal


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_flash_forward_schedule_matches_dense(name):
    """Forward output AND logsumexp (the backward's and the ring merge's
    input) of every kind of block the schedule knows."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    q, k, v, bq, bk, causal = _schedule_case(name)
    b, sq, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    (fq, fk), _ = att._resolve(bq, bk, sq, k.shape[1], d, q.dtype, causal)
    o, lse = att._flash_forward(q, k, v, causal, scale, fq, fk, True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.arange(sq)[:, None] >= jnp.arange(k.shape[1]),
                      s, -1e30)
    assert_almost_equal(np.asarray(o), np.asarray(_reference_attention(
        q, k, v, causal, scale)), rtol=2e-5, atol=2e-5)
    assert_almost_equal(
        np.asarray(lse),
        np.asarray(jax.nn.logsumexp(s, axis=-1).reshape(b * h, sq)),
        rtol=2e-5, atol=2e-5)


# (seq, heads, kv_heads, block_q, block_k, window): a group and a band (PR 52)
BANDS = {
    # the sliding-window cell's geometry in small: square blocks of a window
    "window_is_the_block": (128, 4, 1, 32, 32, 32),
    # a band inside one block: the diagonal block takes both tests
    "window_under_a_block": (128, 2, 2, 32, 32, 9),
    # blocks under the diagonal AND inside the band: no mask at all
    "window_of_three_blocks": (160, 6, 2, 32, 32, 96),
    "window_over_the_sequence": (64, 8, 8, 32, 32, 1000),
    # 512 x 512 tiles inside 1024-row blocks, a band of one tile and a half
    "tiles_in_a_banded_block": (2048, 2, 1, 1024, 1024, 768),
    "group_without_a_window": (128, 6, 2, 64, 16, 0),
}


@pytest.mark.parametrize("name", sorted(BANDS))
def test_flash_forward_with_a_group_and_a_band_matches_dense(name):
    """Output AND logsumexp where query heads share a K/V head through the
    index map and a window bands the causal mask: every kind of block the
    band's schedule knows (under the diagonal or on it, inside the band or
    crossed by its far side), and the by-rows operands beside the by-heads
    ones, bit for bit."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    s, h, kv, bq, bk, window = BANDS[name]
    rng = np.random.RandomState(len(name))
    q = jnp.asarray(rng.randn(2, s, h, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, s, kv, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, s, kv, 8).astype(np.float32))
    o, lse = att._flash_forward(q, k, v, True, 0.3, bq, bk, True,
                                window=window)
    kk, vv = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.3
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = (ahead >= 0) & ((ahead < window) if window else True)
    sc = jnp.where(keep, sc, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), vv)
    assert_almost_equal(np.asarray(o), np.asarray(want), rtol=2e-5,
                        atol=2e-5)
    assert_almost_equal(
        np.asarray(lse),
        np.asarray(jax.nn.logsumexp(sc, axis=-1).reshape(2 * h, s)),
        rtol=2e-5, atol=2e-5)
    o_rows, lse_rows = att._flash_forward(q, k, v, True, 0.3, bq, bk, True,
                                          window=window, rows=True)
    np.testing.assert_array_equal(np.asarray(o_rows), np.asarray(o))
    np.testing.assert_array_equal(np.asarray(lse_rows), np.asarray(lse))


@pytest.mark.parametrize("name", [
    "causal_bq_over_bk", "causal_bq_under_bk", "causal_more_keys_than_rows",
    "causal_more_rows_than_keys", "noncausal_rectangular",
    "causal_default_blocks"])
def test_flash_gradients_through_the_schedule(name):
    """The backward kernels read the forward's lse, and their own index
    maps are clamped at the diagonal too: gradients through the public
    call against the dense vjp."""
    import jax
    import jax.numpy as jnp

    q, k, v, bq, bk, causal = _schedule_case(name, heads=1)
    scale = 1.0 / np.sqrt(q.shape[-1])
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape)
                    .astype(np.float32))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=bq,
                                       block_k=bk) * w)

    def f_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal, scale) * w)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        assert_almost_equal(np.asarray(a), np.asarray(b),
                            rtol=1e-4, atol=1e-4)


# (block_q, block_k, seq_q, seq_k, head_dim, dtype, causal) ->
# ((forward block_q, block_k), (backward block_q, block_k))
RESOLVED = {
    # the LM cell's attention: forward and backward defaults differ
    "lm_cell": ((None, None, 2048, 2048, 128, "bfloat16", True),
                ((2048, 2048), (512, 512))),
    # the shape the backward's 512 x 512 was measured at (PERF.md)
    "benched_8192": ((None, None, 8192, 8192, 128, "bfloat16", True),
                     ((2048, 2048), (512, 512))),
    "noncausal": ((None, None, 2048, 2048, 128, "bfloat16", False),
                  ((2048, 2048), (512, 512))),
    # twice the bytes a row: half the rows a forward block
    "float32": ((None, None, 2048, 2048, 128, "float32", True),
                ((1024, 1024), (512, 512))),
    "float32_head_256": ((None, None, 2048, 2048, 256, "float32", True),
                         ((512, 512), (512, 512))),
    # whole tiles only
    "no_wide_block_divides": ((None, None, 1536, 1536, 128, "bfloat16", True),
                              ((512, 512), (512, 512))),
    "no_512_divides": ((None, None, 640, 640, 128, "bfloat16", True),
                       ((128, 128), (128, 128))),
    "under_512": ((None, None, 384, 384, 128, "bfloat16", True),
                  ((384, 384), (384, 384))),
    "more_keys_than_rows": ((None, None, 512, 4096, 128, "bfloat16", False),
                            ((512, 2048), (512, 512))),
    # explicit ints are respected by all three kernels
    "both_explicit": ((256, 128, 2048, 2048, 128, "bfloat16", True),
                      ((256, 128), (256, 128))),
    "q_explicit": ((256, None, 2048, 2048, 128, "bfloat16", True),
                   ((256, 2048), (256, 512))),
    "k_explicit": ((None, 1024, 2048, 2048, 128, "bfloat16", True),
                   ((2048, 1024), (512, 1024))),
    # under a band the forward fetches one tile a step (PR 52: the
    # sliding-window cell's sequence attention, a window of 512)
    "banded": ((None, None, 4096, 4096, 128, "bfloat16", True, 512),
               ((512, 512), (512, 512))),
    "banded_explicit": ((256, 256, 4096, 4096, 128, "bfloat16", True, 512),
                        ((256, 256), (256, 256))),
    # the same cell's 5,120-token scoring program, its causal layers
    "scoring_5120": ((None, None, 5120, 5120, 128, "bfloat16", True),
                     ((1024, 1024), (512, 512))),
}


@pytest.mark.parametrize("name", sorted(RESOLVED))
def test_resolved_blocks(name):
    """Explicit ints are respected; what is left None takes the measured
    defaults: nothing else decides."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    (bq, bk, sq, sk, d, dtype, causal, *window), want = RESOLVED[name]
    assert att._resolve(bq, bk, sq, sk, d, jnp.dtype(dtype), causal,
                        *window) == want
    dq, dk = att.resolve_blocks(bq, bk, sq, sk, head_dim=d, dtype=dtype,
                                causal=causal)
    assert (dq, dk) == want[1]
    if bq is None and bk is None:
        # never bk < bq, which starves the MXU contraction
        assert dk >= dq
