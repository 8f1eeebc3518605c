"""ops/ssm.py and grouped-query attention, against the plainest form of
each: the token-by-token recurrence, a convolution written out, K/V heads
repeated.  Small sizes, float32 unless a case says otherwise; tolerances are
float32 rounding over a few dozen terms (1e-5 relative) unless stated."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import paged, ssm

H, P, N, K = 3, 4, 5, 4          # heads, head size, state size, conv width
C = H * P + 2 * N                # [x | B | C]
SIZES = dict(heads=H, head_dim=P, state=N)
TOL = dict(rtol=2e-5, atol=2e-5)


def _inputs(b, L, seed=0):
    r = np.random.RandomState(seed)
    return dict(xbc=r.randn(b, L, C).astype(np.float32),
                dt=r.randn(b, L, H).astype(np.float32),
                A_log=np.log(r.uniform(1, 16, H)).astype(np.float32),
                D=r.randn(H).astype(np.float32),
                dt_bias=r.randn(H).astype(np.float32))


def _recurrence(xbc, dt, A_log, D, dt_bias, S=None, H=H, P=P, N=N):
    """One sequence, token by token: (y (L, H*P), final state)."""
    S = np.zeros((H, P, N)) if S is None else np.array(S, np.float64)
    A = -np.exp(A_log.astype(np.float64))
    ys = []
    for t in range(xbc.shape[0]):
        x = xbc[t, :H * P].reshape(H, P).astype(np.float64)
        B, Cm = xbc[t, H * P:H * P + N], xbc[t, H * P + N:]
        d = np.log1p(np.exp(dt[t].astype(np.float64) + dt_bias))
        S = np.exp(d * A)[:, None, None] * S \
            + (d[:, None] * x)[:, :, None] * B[None, None, :]
        ys.append((S @ Cm + D[:, None] * x).reshape(-1))
    return np.stack(ys), S


@pytest.mark.parametrize("L,chunk", [(16, 4), (10, 4), (8, 8), (12, 256),
                                     (9, 2), (1, 4)])
def test_scan_is_the_token_by_token_recurrence(L, chunk):
    """Chunk boundaries included: L a multiple of the chunk, not a multiple
    (padded inside the scan), one chunk, chunks of two, one token."""
    a = _inputs(2, L)
    y, S = ssm.ssm_scan(a["xbc"], a["dt"], a["A_log"], a["D"], a["dt_bias"],
                        chunk=chunk, **SIZES)
    for b in range(2):
        want_y, want_S = _recurrence(a["xbc"][b], a["dt"][b], a["A_log"],
                                     a["D"], a["dt_bias"])
        np.testing.assert_allclose(np.asarray(y[b]), want_y, **TOL)
        np.testing.assert_allclose(np.asarray(S[b]), want_S, **TOL)


@pytest.mark.parametrize("length", [1, 2, 3, 5, 8, 11, 16])
def test_right_padded_prompt_leaves_its_unpadded_state_and_tail(length):
    """Positions at or past ``length`` contribute nothing; the tail is read
    at ``length-3 .. length-1`` (zeros before the prompt's start)."""
    a = _inputs(1, 16, seed=length)
    r = np.random.RandomState(1)
    w, bias = r.randn(C, K).astype(np.float32), r.randn(C).astype(np.float32)
    n = np.array([length], np.int32)

    def run(xbc_raw, dt, n):
        conv, tail = ssm.causal_conv(xbc_raw, w, bias, n)
        y, S = ssm.ssm_scan(conv, dt, a["A_log"], a["D"], a["dt_bias"], n,
                            chunk=4, **SIZES)
        return np.asarray(y), np.asarray(S), np.asarray(tail)

    y_pad, S_pad, tail_pad = run(a["xbc"], a["dt"], n)
    y, S, tail = run(a["xbc"][:, :length], a["dt"][:, :length], None)
    np.testing.assert_allclose(S_pad, S, **TOL)
    np.testing.assert_array_equal(tail_pad, tail)
    np.testing.assert_allclose(y_pad[:, :length], y, **TOL)
    want = np.concatenate([np.zeros((K - 1, C), np.float32),
                           a["xbc"][0, :length]])[-(K - 1):]
    np.testing.assert_array_equal(tail[0], want)


@pytest.mark.parametrize("split", [1, 4, 7, 12])
def test_steps_continued_from_a_scan_are_the_whole_recurrence(split):
    """Prefill ``split`` tokens (right-padded to a bucket of 12), write
    state and tail into slot 2 of the planes, decode the rest one token a
    step beside a padded lane on scratch: the outputs and the final state
    are those of the recurrence over the whole sequence."""
    L = 16
    a = _inputs(1, L, seed=split)
    r = np.random.RandomState(2)
    w, bias = r.randn(C, K).astype(np.float32), r.randn(C).astype(np.float32)
    vec = (a["A_log"], a["D"], a["dt_bias"])
    # the whole sequence in one unpadded pass: the oracle
    conv_all, _ = ssm.causal_conv(a["xbc"], w, bias)
    want_y, want_S = _recurrence(np.asarray(conv_all[0]), a["dt"][0], *vec)

    n = np.array([split], np.int32)
    conv, tail = ssm.causal_conv(a["xbc"][:, :12], w, bias, n)
    y, S = ssm.ssm_scan(conv, a["dt"][:, :12], *vec, n, chunk=4, **SIZES)
    np.testing.assert_allclose(np.asarray(y[0, :split]), want_y[:split],
                               **TOL)
    states = jnp.zeros((4, H, P, N)).at[2].set(S[0])
    tails = jnp.zeros((4, K - 1, C)).at[2].set(tail[0])
    slot = jnp.array([2, 0], jnp.int32)
    for t in range(split, L):
        row = jnp.stack([a["xbc"][0, t], jnp.ones(C)])
        dt = jnp.stack([a["dt"][0, t], jnp.ones(H)])
        conv, tails = ssm.conv_step(row, w, bias, tails, slot)
        y, states = ssm.ssm_step(conv, dt, *vec, states, slot, **SIZES)
        np.testing.assert_allclose(np.asarray(y[0]), want_y[t], **TOL)
    np.testing.assert_allclose(np.asarray(states[2]), want_S, **TOL)
    # the other live slots were never touched
    assert not np.asarray(states[1]).any() and not np.asarray(states[3]).any()


def test_a_step_leaves_scratch_and_a_diverged_neighbour_alone():
    """Lanes parked on slot 0 write nothing there, however many steps they
    ride (several of them summed into one slot would grow it without
    bound); a lane whose inputs are NaN keeps them to itself."""
    a = _inputs(1, 4, seed=9)
    r = np.random.RandomState(9)
    w, bias = r.randn(C, K).astype(np.float32), r.randn(C).astype(np.float32)
    vec = (a["A_log"], a["D"], a["dt_bias"])
    states = jnp.asarray(r.randn(4, H, P, N), jnp.float32)
    tails = jnp.asarray(r.randn(4, K - 1, C), jnp.float32)
    slot = jnp.array([2, 0, 3, 0], jnp.int32)
    row = jnp.stack([a["xbc"][0, 0], a["xbc"][0, 1],
                     jnp.full((C,), jnp.nan), a["xbc"][0, 2]])
    dt = jnp.stack([a["dt"][0, 0], a["dt"][0, 1], jnp.full((H,), jnp.nan),
                    a["dt"][0, 2]])
    conv, tails2 = ssm.conv_step(row, w, bias, tails, slot)
    y, states2 = ssm.ssm_step(conv, dt, *vec, states, slot, **SIZES)
    for before, after in ((states, states2), (tails, tails2)):
        np.testing.assert_array_equal(np.asarray(after[0]),
                                      np.asarray(before[0]))  # scratch
        np.testing.assert_array_equal(np.asarray(after[1]),
                                      np.asarray(before[1]))  # no lane's
        assert np.isfinite(np.asarray(after[2])).all()
        assert np.isnan(np.asarray(after[3])).any()           # its own
    # lane 0 alone in a step gives the same output, to the bit
    conv1, _ = ssm.conv_step(row[:1], w, bias, tails, slot[:1])
    y1, _ = ssm.ssm_step(conv1, dt[:1], *vec, states, slot[:1], **SIZES)
    np.testing.assert_array_equal(np.asarray(y[0]), np.asarray(y1[0]))
    assert np.isfinite(np.asarray(y[1])).all()


def test_causal_conv_is_the_convolution_written_out():
    r = np.random.RandomState(3)
    x = r.randn(2, 9, C).astype(np.float32)
    w, bias = r.randn(C, K).astype(np.float32), r.randn(C).astype(np.float32)
    out, _ = ssm.causal_conv(x, w, bias)
    xp = np.concatenate([np.zeros((2, K - 1, C), np.float32), x], 1)
    for t in range(9):
        pre = sum(xp[:, t + k] * w[:, k] for k in range(K)) + bias
        np.testing.assert_allclose(np.asarray(out[:, t]),
                                   pre / (1 + np.exp(-pre)), **TOL)


@pytest.mark.parametrize("biased", [True, False])
def test_a_plain_convolution_has_no_activation_and_may_have_no_bias(biased):
    """``activation="none"`` (the gated short convolution's) with and
    without a bias, prefill and steps: the written-out sum and nothing after
    it, the tail what the steps go on from."""
    r = np.random.RandomState(4)
    x = r.randn(2, 9, C).astype(np.float32)
    w = r.randn(C, K).astype(np.float32)
    bias = r.randn(C).astype(np.float32) if biased else None
    out, tail = ssm.causal_conv(x, w, bias, activation="none")
    xp = np.concatenate([np.zeros((2, K - 1, C), np.float32), x], 1)
    want = np.stack([sum(xp[:, t + k] * w[:, k] for k in range(K))
                     for t in range(9)], 1) + (bias if biased else 0.0)
    np.testing.assert_allclose(np.asarray(out), want, **TOL)
    # steps from the tail after 6 positions give positions 6, 7, 8
    _, tail6 = ssm.causal_conv(x[:, :6], w, bias, activation="none")
    tails = jnp.zeros((3, K - 1, C)).at[jnp.array([2, 1])].set(tail6)
    slot = jnp.array([2, 1])
    for t in (6, 7, 8):
        y, tails = ssm.conv_step(x[:, t], w, bias, tails, slot, "none")
        np.testing.assert_allclose(np.asarray(y), want[:, t], **TOL)
    np.testing.assert_array_equal(np.asarray(tails[jnp.array([2, 1])]),
                                  np.asarray(tail))
    assert (np.asarray(tails[0]) == 0).all()  # scratch


def test_the_convolution_ops_default_to_what_they_were():
    """No attribute named: SiLU and a bias, under the state-space layers'
    scopes, and a graph's JSON does not mention the new attributes (the
    compile-cache fingerprint of every graph built before they existed).
    ``activation="none"`` / ``no_bias``: one input less, the short
    convolution's own scopes."""
    r = np.random.RandomState(5)
    x = r.randn(1, 6, C).astype(np.float32)
    w, bias = r.randn(C, K).astype(np.float32), r.randn(C).astype(np.float32)
    out, _ = ssm._causal_conv1d(None, {}, x, w, bias)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ssm.causal_conv(x, w, bias)[0]))
    plain = {"activation": "none", "no_bias": True}
    out, _ = ssm._causal_conv1d(None, dict(plain, use_length=True), x, w,
                                jnp.array([4]))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ssm.causal_conv(
            x, w, None, jnp.array([4]), "none")[0]))
    data, weight = mx.sym.Variable("data"), mx.sym.Variable("weight")
    old = mx.sym._contrib_CausalConv1D(data, weight, mx.sym.Variable("bias"),
                                       name="c")
    assert old[0].list_arguments() == ["data", "weight", "bias"]
    assert "activation" not in old.tojson() and "no_bias" not in old.tojson()
    new = mx.sym._contrib_CausalConv1DStep(
        data, weight, mx.sym.Variable("tails"), mx.sym.Variable("slot"),
        name="c", **plain)
    assert new[0].list_arguments() == ["data", "weight", "tails", "slot"]
    with pytest.raises(ValueError, match="invalid value"):
        mx.sym._contrib_CausalConv1D(data, weight, activation="relu")

    def text(fn, *args):
        return jax.jit(fn).lower(*args).as_text(debug_info=True)

    tails, slot = jnp.zeros((2, K - 1, C)), jnp.zeros((1,))
    seq = text(lambda x: ssm._causal_conv1d(None, plain, x, w), x)
    assert "short_conv" in seq and "ssm_scan" not in seq
    lane = text(lambda x, t, s: ssm._causal_conv1d_step(None, plain, x, w, t,
                                                        s), x[:, 0], tails,
                slot)
    assert "short_conv_step" in lane and "ssm_step" not in lane
    assert "ssm_scan" in text(
        lambda x: ssm._causal_conv1d(None, {}, x, w, bias), x)


def test_norms_and_gates():
    r = np.random.RandomState(4)
    x, z = r.randn(3, 8).astype(np.float32), r.randn(3, 8).astype(np.float32)
    g = r.randn(8).astype(np.float32)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    got = mx.nd._contrib_RMSNorm(mx.nd.array(x), mx.nd.array(g)).asnumpy()
    np.testing.assert_allclose(got, want, **TOL)
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    y = x * silu(z)
    want = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * g
    got = mx.nd._contrib_GatedRMSNorm(mx.nd.array(x), mx.nd.array(z),
                                      mx.nd.array(g)).asnumpy()
    np.testing.assert_allclose(got, want, **TOL)
    got = mx.nd._contrib_SiluGate(mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(got, silu(x[:, :4]) * x[:, 4:], **TOL)


def test_scaled_logits_are_float32_whatever_the_operands():
    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(3, 8), jnp.bfloat16)
    table = jnp.asarray(r.randn(11, 8), jnp.bfloat16)
    got = ssm._scaled_logits(None, {"scale": 0.125}, x, table)
    assert got.dtype == jnp.float32
    want = np.asarray(x, np.float32) @ np.asarray(table, np.float32).T / 8
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


# -- grouped-query attention -------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 2), (4, 4), (6, 1)])
def test_dense_attention_groups_are_repeated_kv_heads(heads, kv_heads):
    r = np.random.RandomState(6)
    q = r.randn(2, 7, heads, 8).astype(np.float32)
    k = r.randn(2, 7, kv_heads, 8).astype(np.float32)
    v = r.randn(2, 7, kv_heads, 8).astype(np.float32)
    got = mx.nd._contrib_DenseAttention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), causal=True,
        scale=0.2).asnumpy()
    g = heads // kv_heads
    want = mx.nd._contrib_DenseAttention(
        mx.nd.array(q), mx.nd.array(np.repeat(k, g, 2)),
        mx.nd.array(np.repeat(v, g, 2)), causal=True, scale=0.2).asnumpy()
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 2), (6, 1)])
def test_gather_decode_groups_are_repeated_kv_heads(heads, kv_heads):
    """Planes by K/V heads against planes with every K/V head repeated:
    the same attention, and the same rows written."""
    r = np.random.RandomState(7)
    lanes, ps, pages, hd, g = 3, 4, 9, 8, heads // kv_heads
    q = jnp.asarray(r.randn(lanes, heads, hd), jnp.float32)
    k_new = jnp.asarray(r.randn(lanes, kv_heads, hd), jnp.float32)
    v_new = jnp.asarray(r.randn(lanes, kv_heads, hd), jnp.float32)
    k_pool = jnp.asarray(r.randn(pages, ps, kv_heads, hd), jnp.float32)
    v_pool = jnp.asarray(r.randn(pages, ps, kv_heads, hd), jnp.float32)
    pt = jnp.array([[1, 2, 0], [3, 4, 5], [0, 0, 0]], jnp.int32)
    pos = jnp.array([5, 9, 0], jnp.int32)
    out, k_out, v_out = paged._gather_decode(q, k_new, v_new, k_pool, v_pool,
                                             pt, pos, 0.3)
    rep = lambda a: jnp.repeat(a, g, axis=-2)  # noqa: E731
    want, k_want, v_want = paged._gather_decode(
        q, rep(k_new), rep(v_new), rep(k_pool), rep(v_pool), pt, pos, 0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    np.testing.assert_array_equal(np.asarray(rep(k_out)), np.asarray(k_want))
    np.testing.assert_array_equal(np.asarray(rep(v_out)), np.asarray(v_want))


def test_grouped_planes_take_the_gather_on_every_platform():
    """Planes whose token is BY HEADS, with fewer heads than the query, go
    through the XLA formulation even where the kernel would run: the kernel
    takes groups over a token held as one row (bfloat16; the hybrid
    family's planes since PR 44: tests/test_paged_kernel.py)."""
    assert paged.decode_formulation("tpu", 16, 128, np.float32) == "pallas"
    assert paged.decode_formulation("tpu", 16, 128, np.float32,
                                    kv_heads=16) == "pallas"
    assert paged.decode_formulation("tpu", 32, 128, np.float32,
                                    kv_heads=8) == "xla"
    assert paged.decode_formulation("tpu", 32, 64, jnp.bfloat16,
                                    kv_heads=8) == "xla"
    assert paged.decode_formulation("tpu", 32, 64, jnp.bfloat16,
                                    kv_heads=8, rows=True) == "pallas"


def test_paged_attention_refuses_planes_of_other_heads():
    with pytest.raises(ValueError, match="heads"):
        paged._paged_attention(
            None, {"page_size": 4}, jnp.zeros((2, 4, 8)),
            jnp.zeros((2, 2, 8)), jnp.zeros((2, 2, 8)),
            jnp.zeros((3, 4, 4, 8)), jnp.zeros((3, 4, 4, 8)),
            jnp.zeros((2, 2)), jnp.zeros((2,)))


def test_ops_carry_their_scopes():
    """``ssm_step`` / ``ssm_scan`` reach the compiled text as scopes: what
    the per-layer metrics of the device trace match."""
    a = _inputs(1, 8)
    vec = (a["A_log"], a["D"], a["dt_bias"])

    def scan(x, dt):
        return ssm._ssm_scan(None, dict(SIZES, chunk=4), x, dt, *vec)

    def step(x, dt, states, slot):
        return ssm._ssm_step(None, SIZES, x, dt, *vec, states, slot)

    text = jax.jit(scan).lower(a["xbc"], a["dt"]).as_text(debug_info=True)
    assert "ssm_scan" in text
    text = jax.jit(step).lower(a["xbc"][:, 0], a["dt"][:, 0],
                               jnp.zeros((2, H, P, N)),
                               jnp.zeros((1,))).as_text(debug_info=True)
    assert "ssm_step" in text


# -- the Pallas formulation of the step, in interpret mode -------------------

KERNEL = functools.partial(ssm._kernel_step, interpret=True)
SLOTS = 17


def _step_inputs(lanes, heads, head_dim, state, seed):
    r = np.random.RandomState(seed)
    return dict(
        xbc=r.randn(lanes, heads * head_dim + 2 * state).astype(np.float32),
        dt=r.randn(lanes, heads).astype(np.float32),
        vec=(np.log(r.uniform(1, 16, heads)).astype(np.float32),
             r.randn(heads).astype(np.float32),
             r.randn(heads).astype(np.float32)),
        states=jnp.asarray(r.randn(SLOTS, heads, head_dim, state),
                           jnp.float32))


@pytest.mark.parametrize("heads", [16, 64])
@pytest.mark.parametrize("lanes,parked", [(1, ()), (5, (1, 3)),
                                          (16, (0, 7, 8))])
def test_kernel_step_is_the_routed_step(lanes, parked, heads):
    """The cell's head and state sizes, 16 heads (one block of them) and the
    cell's 64 (two): lanes in permuted slot order, some parked on scratch.
    ``y`` and the lanes' slots equal the XLA formulation's to float32
    rounding (a fused multiply-add; the order of a 128-term sum); scratch
    and every slot no lane names keep their BITS."""
    sizes = dict(heads=heads, head_dim=64, state=128)
    a = _step_inputs(lanes, seed=lanes, **sizes)
    slot = np.random.RandomState(lanes).permutation(
        np.arange(1, SLOTS))[:lanes].astype(np.int32)
    slot[list(parked)] = 0
    args = (a["xbc"], a["dt"], *a["vec"], a["states"], jnp.asarray(slot))
    want_y, want_S = ssm.ssm_step(*args, **sizes)
    y, S = ssm.ssm_step(*args, step=KERNEL, **sizes)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), rtol=1e-6,
                               atol=1e-6)
    for s in set(range(SLOTS)) - set(slot[slot > 0].tolist()):
        np.testing.assert_array_equal(np.asarray(S[s]),
                                      np.asarray(a["states"][s]))
    # a parked lane's output is D x alone, as the other formulation gives
    for lane in parked:
        np.testing.assert_array_equal(np.asarray(y[lane]),
                                      np.asarray(want_y[lane]))


def test_kernel_step_leaves_a_diverged_neighbour_alone():
    """A lane whose state holds ``inf`` and one whose inputs are NaN keep
    them to themselves: their neighbours' slots and outputs stay finite and
    equal the XLA formulation's, scratch keeps its bits."""
    sizes = dict(heads=16, head_dim=64, state=128)
    a = _step_inputs(4, seed=9, **sizes)
    states = a["states"].at[5, 3, 2, 7].set(jnp.inf)
    xbc = jnp.asarray(a["xbc"]).at[2].set(jnp.nan)
    slot = jnp.array([5, 0, 9, 2], jnp.int32)
    args = (xbc, a["dt"], *a["vec"], states, slot)
    want_y, want_S = ssm.ssm_step(*args, **sizes)
    y, S = ssm.ssm_step(*args, step=KERNEL, **sizes)
    assert not np.isfinite(np.asarray(S[5])).all()
    assert np.isnan(np.asarray(S[9])).any() and np.isnan(np.asarray(y[2])).any()
    for lane, s in ((1, 0), (3, 2)):
        assert np.isfinite(np.asarray(y[lane])).all()
        assert np.isfinite(np.asarray(S[s])).all()
        np.testing.assert_allclose(np.asarray(y[lane]),
                                   np.asarray(want_y[lane]), rtol=1e-5,
                                   atol=1e-4)
    np.testing.assert_array_equal(np.asarray(S[0]), np.asarray(states[0]))
    np.testing.assert_allclose(np.asarray(S[2]), np.asarray(want_S[2]),
                               rtol=1e-6, atol=1e-6)


def test_kernel_steps_continued_from_a_scan_are_the_whole_recurrence():
    """Prefill 12 tokens with the scan, write the final state into a slot,
    then 20 tokens one step each through the kernel beside a parked lane:
    outputs and final state are the token-by-token recurrence's over all
    32."""
    sizes = dict(heads=2, head_dim=8, state=128)
    H2, P2, N2, L, split = 2, 8, 128, 32, 12
    r = np.random.RandomState(11)
    xbc = (0.5 * r.randn(1, L, H2 * P2 + 2 * N2)).astype(np.float32)
    dt = r.randn(1, L, H2).astype(np.float32)
    vec = (np.log(r.uniform(1, 16, H2)).astype(np.float32),
           r.randn(H2).astype(np.float32), r.randn(H2).astype(np.float32))
    want_y, want_S = _recurrence(xbc[0], dt[0], *vec, H=H2, P=P2, N=N2)
    _, S = ssm.ssm_scan(xbc[:, :split], dt[:, :split], *vec, chunk=4, **sizes)
    states = jnp.zeros((4, H2, P2, N2)).at[3].set(S[0])
    slot = jnp.array([0, 3], jnp.int32)
    for t in range(split, L):
        row = jnp.stack([jnp.ones(xbc.shape[-1]), xbc[0, t]])
        y, states = ssm.ssm_step(row, jnp.stack([jnp.ones(H2), dt[0, t]]),
                                 *vec, states, slot, step=KERNEL, **sizes)
        np.testing.assert_allclose(np.asarray(y[1]), want_y[t], rtol=1e-4,
                                   atol=1e-4)
    np.testing.assert_allclose(np.asarray(states[3]), want_S, rtol=1e-4,
                               atol=1e-4)
    assert not np.asarray(states[:3]).any()


@pytest.mark.parametrize("platform,head_dim,state,dtype,want", [
    ("tpu", 64, 128, np.float32, "pallas"),     # the cell's shapes
    ("tpu", 8, 256, np.float32, "pallas"),
    ("cpu", 64, 128, np.float32, "xla"),
    ("gpu", 64, 128, np.float32, "xla"),
    ("tpu", 64, 128, jnp.bfloat16, "xla"),      # not a float32 plane
    ("tpu", 64, 64, np.float32, "xla"),         # a row is half a tile
    ("tpu", 4, 128, np.float32, "xla"),         # a head is half a tile
])
def test_step_formulation_is_read_off_the_operands(platform, head_dim, state,
                                                   dtype, want):
    assert ssm.step_formulation(platform, head_dim, state, dtype) == want


def test_the_op_picks_its_formulation_where_the_operands_live():
    """``_contrib_SSMStep`` traced for a TPU lowers the kernel, under the
    scope the XLA formulation carries; on this CPU it lowers none."""
    from mxnet_tpu.ops.interpret import bind

    sizes = dict(heads=2, head_dim=8, state=128)
    a = _step_inputs(3, seed=1, **sizes)

    def step(xbc, dt, states, slot):
        return ssm._ssm_step(None, sizes, xbc, dt, *a["vec"], states, slot)

    args = (a["xbc"], a["dt"], a["states"], jnp.zeros((3,)))
    here = str(jax.make_jaxpr(step)(*args))
    there = str(jax.make_jaxpr(bind(step, "tpu"))(*args))
    assert "pallas_call" not in here
    assert "pallas_call" in there and "name=ssm_step" in there


# -- the Pallas formulation of the scan, in interpret mode -------------------

SCAN = functools.partial(ssm._kernel_scan, interpret=True)
CELL = dict(head_dim=64, state=128)  # both Granite cells' head and state


def _scan_inputs(b, L, heads, dtype=np.float32, seed=0, head_dim=64,
                 state=128):
    r = np.random.RandomState(seed)
    return dict(
        xbc=jnp.asarray(0.5 * r.randn(b, L, heads * head_dim + 2 * state),
                        dtype),
        dt=jnp.asarray(r.randn(b, L, heads), np.float32),
        vec=(np.log(r.uniform(1, 16, heads)).astype(np.float32),
             r.randn(heads).astype(np.float32),
             r.randn(heads).astype(np.float32)))


def _both_scans(a, length, heads, chunk=256, **sizes):
    sizes = dict(CELL, heads=heads, chunk=chunk, **sizes)
    n = None if length is None else jnp.asarray(length, jnp.int32)
    args = (a["xbc"], a["dt"], *a["vec"], n)
    return ssm.ssm_scan(*args, **sizes), ssm.ssm_scan(*args, scan=SCAN,
                                                      **sizes)


# (b, L, heads, lengths): the cells' buckets at a few heads of the cells'
# size (a block is 8 heads: 16 and 24 are two and three blocks), ragged
# lengths on a chunk's edge, inside the first chunk, equal to L, and one
# bucket that is no multiple of the chunk (padded inside the scan)
@pytest.mark.parametrize("b,L,heads,lengths", [
    (1, 64, 8, None), (1, 128, 8, [77]), (1, 256, 16, [256]),
    (2, 512, 8, [256, 300]), (2, 512, 24, [3, 512]),
    (1, 2048, 8, [1300]), (2, 640, 8, [640, 257]), (1, 300, 8, [290]),
])
def test_kernel_scan_is_the_chunked_scan(b, L, heads, lengths):
    """``y`` on the live rows and the final state equal the XLA
    formulation's to float32 rounding (the same sums in another order; the
    products of three bfloat16 pieces are ``highest``'s own); the rows past
    a prompt's length are finite (zeros past its last live chunk)."""
    a = _scan_inputs(b, L, heads, seed=L + heads)
    (want_y, want_S), (y, S) = _both_scans(a, lengths, heads)
    assert y.shape == want_y.shape and y.dtype == want_y.dtype
    assert np.isfinite(np.asarray(y)).all()
    for i in range(b):
        n = L if lengths is None else lengths[i]
        np.testing.assert_allclose(np.asarray(y[i, :n]),
                                   np.asarray(want_y[i, :n]), rtol=2e-5,
                                   atol=2e-4)
        last = -(-n // 256) * 256
        assert not np.asarray(y[i, last:]).any()
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("heads", [64, 128])
def test_kernel_scan_at_the_cells_heads(heads):
    """Every head of Granite-micro (64) and Granite-small (128), bfloat16 as
    the convolution writes it, two chunks with the second half live: the
    state to float32 rounding, ``y`` to the one bfloat16 rounding both forms
    end with."""
    a = _scan_inputs(1, 512, heads, jnp.bfloat16, seed=heads)
    (want_y, want_S), (y, S) = _both_scans(a, [384], heads)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(y[0, :384], np.float32),
                               np.asarray(want_y[0, :384], np.float32),
                               rtol=1e-2, atol=1e-2)
    assert np.isfinite(np.asarray(y, np.float32)).all()


def test_kernel_scan_keeps_a_stale_nan_out_of_a_live_prompt():
    """A padded row that holds NaN past a prompt's last live chunk reaches
    nothing: the chunk is neither fetched nor multiplied."""
    a = _scan_inputs(1, 512, 8, seed=3)
    a["xbc"] = a["xbc"].at[:, 256:].set(jnp.nan)
    (_, _), (y, S) = _both_scans(a, [200], 8)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(S)).all()


def test_kernel_scan_is_the_token_by_token_recurrence():
    """Against the plainest form, not only its sibling: two heads of 64,
    chunks of 16 (whole sublane tiles), a ragged pair."""
    sizes = dict(heads=2, head_dim=64, state=128)
    a = _scan_inputs(2, 48, 2, seed=5)
    n = [48, 21]
    y, S = ssm.ssm_scan(a["xbc"], a["dt"], *a["vec"],
                        jnp.asarray(n, jnp.int32), chunk=16, scan=SCAN,
                        **sizes)
    for i in range(2):
        want_y, want_S = _recurrence(
            np.asarray(a["xbc"][i, :n[i]]), np.asarray(a["dt"][i, :n[i]]),
            *a["vec"], H=2, P=64, N=128)
        np.testing.assert_allclose(np.asarray(y[i, :n[i]]), want_y,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(S[i]), want_S, rtol=1e-4,
                                   atol=1e-4)


def test_kernel_steps_continued_from_a_kernel_scan_are_the_whole_recurrence():
    """As ``test_kernel_steps_continued_from_a_scan...``, the prefill through
    the scan's kernel: 12 tokens right-padded to a bucket of 32 in chunks of
    16, then 20 steps through the step's kernel."""
    sizes = dict(heads=2, head_dim=64, state=128)
    H2, P2, N2, L, split = 2, 64, 128, 32, 12
    a = _scan_inputs(1, L, H2, seed=11)
    xbc, dt, vec = np.asarray(a["xbc"]), np.asarray(a["dt"]), a["vec"]
    want_y, want_S = _recurrence(xbc[0], dt[0], *vec, H=H2, P=P2, N=N2)
    y, S = ssm.ssm_scan(a["xbc"], a["dt"], *vec, jnp.array([split]),
                        chunk=16, scan=SCAN, **sizes)
    np.testing.assert_allclose(np.asarray(y[0, :split]), want_y[:split],
                               rtol=1e-4, atol=1e-4)
    states = jnp.zeros((4, H2, P2, N2)).at[3].set(S[0])
    slot = jnp.array([0, 3], jnp.int32)
    for t in range(split, L):
        row = jnp.stack([jnp.ones(xbc.shape[-1]), xbc[0, t]])
        y, states = ssm.ssm_step(row, jnp.stack([jnp.ones(H2), dt[0, t]]),
                                 *vec, states, slot, step=KERNEL, **sizes)
        np.testing.assert_allclose(np.asarray(y[1]), want_y[t], rtol=1e-4,
                                   atol=1e-4)
    np.testing.assert_allclose(np.asarray(states[3]), want_S, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("platform,L,heads,head_dim,state,dtype,train,want", [
    ("tpu", 2048, 128, 64, 128, jnp.bfloat16, False, "pallas"),  # g4hsmall
    ("tpu", 512, 64, 64, 128, jnp.bfloat16, False, "pallas"),    # g4hmicro
    ("tpu", 64, 64, 64, 128, jnp.bfloat16, False, "pallas"),     # one chunk
    ("tpu", 2432, 128, 64, 128, jnp.bfloat16, False, "pallas"),  # padded
    ("tpu", 512, 64, 64, 128, np.float32, False, "pallas"),
    ("tpu", 512, 8, 128, 128, jnp.bfloat16, False, "pallas"),
    ("tpu", 2048, 128, 64, 128, jnp.bfloat16, True, "xla"),   # a gradient
    ("cpu", 2048, 128, 64, 128, jnp.bfloat16, False, "xla"),
    ("gpu", 2048, 128, 64, 128, jnp.bfloat16, False, "xla"),
    ("tpu", 512, 64, 64, 128, np.float16, False, "xla"),
    ("tpu", 512, 64, 64, 64, jnp.bfloat16, False, "xla"),     # half a tile
    ("tpu", 512, 3, 4, 128, jnp.bfloat16, False, "xla"),      # toy heads
    ("tpu", 512, 7, 64, 128, jnp.bfloat16, False, "xla"),     # no block
    ("tpu", 12, 64, 64, 128, jnp.bfloat16, False, "xla"),     # a ragged chunk
])
def test_scan_formulation_is_read_off_the_operands(platform, L, heads,
                                                   head_dim, state, dtype,
                                                   train, want):
    assert ssm.scan_formulation(platform, L, heads, head_dim, state, dtype,
                                train) == want


def test_the_scan_op_picks_its_formulation_where_the_operands_live():
    """``_contrib_SSMScan`` traced for a TPU lowers the kernel, under the
    scope the XLA formulation carries; on this CPU, and for a graph that is
    being differentiated, it lowers none."""
    from mxnet_tpu.ops.interpret import bind
    from mxnet_tpu.ops.registry import OpContext

    sizes = dict(heads=8, head_dim=64, state=128)
    a = _scan_inputs(1, 256, 8)

    def scan(opctx):
        return lambda xbc, dt: ssm._ssm_scan(opctx, sizes, xbc, dt, *a["vec"])

    here = str(jax.make_jaxpr(scan(None))(a["xbc"], a["dt"]))
    there = str(jax.make_jaxpr(bind(scan(OpContext(False)), "tpu"))(
        a["xbc"], a["dt"]))
    train = str(jax.make_jaxpr(bind(scan(OpContext(True)), "tpu"))(
        a["xbc"], a["dt"]))
    assert "pallas_call" not in here and "pallas_call" not in train
    assert "pallas_call" in there and "name=ssm_scan" in there
