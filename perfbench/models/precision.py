"""Matrix products at a stated precision, for the references and controls.

``f32``   float32 at ``highest``: the reference.
``bf16``  operands rounded to bfloat16, float32 accumulation.
``fp8``   every product takes float8 operands and stores a float8 result:
          operands and result scaled per tensor and rounded to e4m3
          (float32 accumulation between), and in the backward pass the
          incoming gradient of every product rounded to e5m2, as fp8
          training recipes have it.  The step below bfloat16, i.e. the
          control for a configuration that states bfloat16 compute: what
          ``compute_dtype`` one step further down would do; a family whose
          work is not mostly products (ResNet's batch norm, ReLU and
          residual sums) stores those results in float8 too.  (Rounding the
          operands alone is no control: a product over thousands of terms
          averages it to the size of one bfloat16 rounding of the result.)
"""
import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("f32", "bf16", "fp8")


def _q8(x):
    """Per-tensor scaled e4m3 rounding; returns (rounded values, scale).
    The rounding is straight-through for gradients, as fp8 training
    recipes have it: the backward pass sees the rounded values, and the
    gradient passes the rounding unchanged."""
    s = lax.stop_gradient(jnp.max(jnp.abs(x)).astype(jnp.float32)) / 448.0
    s = jnp.maximum(s, 1e-30)
    xs = x.astype(jnp.float32) / s
    q = xs.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    q = xs + lax.stop_gradient(q - xs)
    return q.astype(jnp.bfloat16), s


def store_q8(y):
    """``y`` as a float8 e4m3 store would keep it (per-tensor scaled,
    straight-through), and its incoming gradient as e5m2."""
    q, s = _q8(y)
    return grad_q8(q.astype(jnp.float32) * s)


@jax.custom_vjp
def grad_q8(y):
    """Identity whose incoming gradient is rounded to per-tensor scaled
    float8 e5m2: put on a product's result, it makes the backward products
    take a float8 operand."""
    return y


def _grad_q8_fwd(y):
    return y, None


def _grad_q8_bwd(_, g):
    s = jnp.maximum(jnp.max(jnp.abs(g)).astype(jnp.float32) / 57344.0, 1e-30)
    q = (g.astype(jnp.float32) / s).astype(jnp.float8_e5m2)
    return ((q.astype(jnp.float32) * s).astype(g.dtype),)


grad_q8.defvjp(_grad_q8_fwd, _grad_q8_bwd)


def einsum(spec, a, b, prec):
    """``jnp.einsum(spec, a, b)`` computed at precision ``prec``."""
    if prec == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
    if prec == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if prec == "fp8":
        qa, sa = _q8(a)
        qb, sb = _q8(b)
        return store_q8(jnp.einsum(spec, qa, qb,
                                   preferred_element_type=jnp.float32)
                        * (sa * sb))
    raise ValueError("precision must be one of %s, got %r"
                     % (PRECISIONS, prec))


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31.  The
    ``rbg`` generator: the chip makes a billion normals in about a second
    with it, where the default takes several."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


SLICE = 65536


def leaf_slices(tree):
    """Up to SLICE evenly strided elements of every leaf, as float32: small
    enough to keep on the host while the other side of a comparison runs."""
    out = {}
    for k, v in tree.items():
        flat = v.reshape(-1)
        stride = max(1, flat.shape[0] // SLICE)
        out[k] = flat[::stride][:SLICE].astype(jnp.float32)
    return out


@jax.jit
def leaf_norms(tree):
    """The Euclidean norm of every leaf, in float32."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def leaf_diff_norms(a, b):
    """Per leaf, the norm of ``a`` minus ``b`` (``b``'s leaves)."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(jnp.float32) - b[k])))
            for k in b}
