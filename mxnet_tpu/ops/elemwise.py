"""Elementwise, scalar, broadcast and reduce op families.

Parity surface: the ``MXNET_OPERATOR_REGISTER_{UNARY,BINARY,BINARY_SCALAR,
BINARY_BROADCAST,REDUCE}`` registrations in /root/reference/src/operator/tensor/
(elemwise_unary_op.cc, elemwise_binary_op.cc, elemwise_binary_scalar_op.cc,
elemwise_binary_broadcast_op.cc, broadcast_reduce_op.h, elemwise_sum.h).
Implementation is pure jax.numpy — XLA fuses these into surrounding matmuls,
which is the TPU-native replacement for the reference's mshadow expression
templates and the tuned CUDA reduce kernels (broadcast_reduce-inl.cuh).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .param import Param
from .registry import register

# ---------------------------------------------------------------------------
# Unary ops
# ---------------------------------------------------------------------------


def _round_away(x):
    return jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)


def _gamma(x):
    from jax.scipy.special import gamma as _g

    return _g(x)


_UNARY = {
    "abs": jnp.abs,
    "arccos": jnp.arccos,
    "arccosh": jnp.arccosh,
    "arcsin": jnp.arcsin,
    "arcsinh": jnp.arcsinh,
    "arctan": jnp.arctan,
    "arctanh": jnp.arctanh,
    "ceil": jnp.ceil,
    "cos": jnp.cos,
    "cosh": jnp.cosh,
    "degrees": jnp.degrees,
    "exp": jnp.exp,
    "expm1": jnp.expm1,
    "fix": jnp.trunc,
    "floor": jnp.floor,
    "gamma": _gamma,
    "gammaln": lambda x: jax.scipy.special.gammaln(x),
    "log": jnp.log,
    "log10": jnp.log10,
    "log1p": jnp.log1p,
    "log2": jnp.log2,
    "negative": jnp.negative,
    "radians": jnp.radians,
    "rint": jnp.rint,
    "round": _round_away,
    "rsqrt": lambda x: jax.lax.rsqrt(x),
    "sigmoid": jax.nn.sigmoid,
    "sign": jnp.sign,
    "sin": jnp.sin,
    "sinh": jnp.sinh,
    "sqrt": jnp.sqrt,
    "square": jnp.square,
    "tan": jnp.tan,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    # transformer-era additions (post-0.9 mxnet names; the model zoo's
    # transformer family uses gelu)
    "erf": lambda x: jax.scipy.special.erf(x),
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
}


def _register_unary(name, jfn, aliases=()):
    @register(name, inputs=("data",), aliases=aliases, hint=name.lstrip("_"))
    def _fn(opctx, attrs, x, _jfn=jfn):
        return _jfn(x)


for _name, _jfn in _UNARY.items():
    _register_unary(_name, _jfn)


@register("_copy", aliases=("identity", "_copyto"), hint="copy")
def _copy(opctx, attrs, x):
    return x


@register("_CrossDeviceCopy", hint="crossdevicecopy")
def _cross_device_copy(opctx, attrs, x):
    """Identity at the op level: the reference splices this node at ctx
    boundaries (src/operator/cross_device_copy.cc) and its engine moves the
    bytes; here the executor's placement map compiles the device transfer
    (jax.device_put) into the step, so graphs loaded from reference JSON
    that contain this node run unchanged."""
    return x


def _broadcast_fun_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    out = list(d)
    out[attrs["axis"]] = attrs["size"]
    return in_shapes, [tuple(out)], []


@register("_broadcast", params={"axis": Param(int, required=True),
                                "size": Param(int, required=True)},
          infer_shape=_broadcast_fun_infer, hint="broadcastfun")
def _broadcast_fun(opctx, attrs, x):
    """Registered NDArray function ``_broadcast`` (reference
    src/ndarray/ndarray.cc:898: "Broadcast array in the given axis to the
    given size"; the size-1 axis expands).  Call with keyword params:
    ``mx.nd._broadcast(x, axis=0, size=4)``."""
    axis, size = attrs["axis"], attrs["size"]
    shape = list(x.shape)
    shape[axis] = size
    return jnp.broadcast_to(x, tuple(shape))


@register("BlockGrad", aliases=("stop_gradient",), hint="blockgrad")
def _block_grad(opctx, attrs, x):
    return jax.lax.stop_gradient(x)


def _make_loss_fn():
    @jax.custom_vjp
    def _ml(x, grad_scale):
        return x

    def _fwd(x, grad_scale):
        return x, (jnp.shape(x), x.dtype, grad_scale)

    def _bwd(res, ct):
        shape, dtype, grad_scale = res
        # Reference semantics (make_loss, elemwise_unary_op.cc): the backward
        # value is grad_scale regardless of the head gradient.
        del ct
        return jnp.full(shape, grad_scale, dtype), None

    _ml.defvjp(_fwd, _bwd)
    return _ml


_make_loss_impl = _make_loss_fn()


@register("make_loss", params={"grad_scale": Param(float, 1.0)}, hint="make_loss")
def _make_loss(opctx, attrs, x):
    return _make_loss_impl(x, attrs.get("grad_scale", 1.0))


@register("softmax", params={"axis": Param(int, -1), "temperature": Param("float-or-none", None)})
def _softmax(opctx, attrs, x):
    t = attrs.get("temperature")
    if t:
        x = x / t
    return jax.nn.softmax(x, axis=attrs.get("axis", -1))


@register("log_softmax", params={"axis": Param(int, -1), "temperature": Param("float-or-none", None)})
def _log_softmax(opctx, attrs, x):
    t = attrs.get("temperature")
    if t:
        x = x / t
    return jax.nn.log_softmax(x, axis=attrs.get("axis", -1))


@register("smooth_l1", params={"scalar": Param(float, 1.0)})
def _smooth_l1(opctx, attrs, x):
    # f(x) = 0.5 (sx)^2 if |x| < 1/s^2 else |x| - 0.5/s^2
    # (reference: elemwise_unary_op.cc smooth_l1, used by RCNN examples)
    s = attrs.get("scalar", 1.0)
    s2 = s * s
    absx = jnp.abs(x)
    return jnp.where(absx < 1.0 / s2, 0.5 * s2 * x * x, absx - 0.5 / s2)


# ---------------------------------------------------------------------------
# Binary elementwise (same-shape) + comparison
# ---------------------------------------------------------------------------

_BINARY = {
    "elemwise_add": jnp.add,
    "elemwise_sub": jnp.subtract,
    "elemwise_mul": jnp.multiply,
    "elemwise_div": jnp.divide,
    "_grad_add": jnp.add,
    "_power": jnp.power,
    "_maximum": jnp.maximum,
    "_minimum": jnp.minimum,
    "_hypot": jnp.hypot,
    "_mod": jnp.mod,
}

_BINARY_ALIASES = {
    "elemwise_add": ("_add", "_plus", "_Plus"),
    "elemwise_sub": ("_sub", "_minus", "_Minus"),
    "elemwise_mul": ("_mul", "_Mul"),
    "elemwise_div": ("_div", "_Div"),
    "_power": ("_Power", "pow"),
    "_maximum": ("_Maximum",),
    "_minimum": ("_Minimum",),
    "_mod": ("_Mod",),
}

_COMPARE = {
    "_equal": jnp.equal,
    "_not_equal": jnp.not_equal,
    "_greater": jnp.greater,
    "_greater_equal": jnp.greater_equal,
    "_lesser": jnp.less,
    "_lesser_equal": jnp.less_equal,
}


def _register_binary(name, jfn, aliases=(), compare=False):
    @register(name, inputs=("lhs", "rhs"), aliases=aliases, hint=name.lstrip("_"))
    def _fn(opctx, attrs, lhs, rhs, _jfn=jfn, _cmp=compare):
        out = _jfn(lhs, rhs)
        if _cmp:
            # Reference comparison ops keep the input dtype (pre-bool era).
            out = out.astype(jnp.result_type(lhs, rhs))
        return out


for _name, _jfn in _BINARY.items():
    _register_binary(_name, _jfn, _BINARY_ALIASES.get(_name, ()))
for _name, _jfn in _COMPARE.items():
    _register_binary(_name, _jfn, (_name[1:].title().replace("_", ""),), compare=True)


# ---------------------------------------------------------------------------
# Scalar variants
# ---------------------------------------------------------------------------

_SCALAR_SPEC = {"scalar": Param(float, required=True)}

_SCALAR = {
    "_plus_scalar": lambda x, s: x + s,
    "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x,
    "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s,
    "_rdiv_scalar": lambda x, s: s / x,
    "_power_scalar": lambda x, s: jnp.power(x, s),
    "_rpower_scalar": lambda x, s: jnp.power(s, x),
    "_maximum_scalar": lambda x, s: jnp.maximum(x, s),
    "_minimum_scalar": lambda x, s: jnp.minimum(x, s),
    "_hypot_scalar": lambda x, s: jnp.hypot(x, s),
    "_mod_scalar": lambda x, s: jnp.mod(x, s),
    "_rmod_scalar": lambda x, s: jnp.mod(s, x),
    "_equal_scalar": lambda x, s: (x == s).astype(x.dtype),
    "_not_equal_scalar": lambda x, s: (x != s).astype(x.dtype),
    "_greater_scalar": lambda x, s: (x > s).astype(x.dtype),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(x.dtype),
    "_lesser_scalar": lambda x, s: (x < s).astype(x.dtype),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(x.dtype),
}

_SCALAR_ALIASES = {
    "_plus_scalar": ("_PlusScalar",),
    "_minus_scalar": ("_MinusScalar",),
    "_rminus_scalar": ("_RMinusScalar",),
    "_mul_scalar": ("_MulScalar",),
    "_div_scalar": ("_DivScalar",),
    "_rdiv_scalar": ("_RDivScalar",),
    "_power_scalar": ("_PowerScalar",),
    "_rpower_scalar": ("_RPowerScalar",),
    "_maximum_scalar": ("_MaximumScalar",),
    "_minimum_scalar": ("_MinimumScalar",),
}


def _register_scalar(name, jfn, aliases=()):
    @register(name, inputs=("data",), params=dict(_SCALAR_SPEC), aliases=aliases,
              hint=name.lstrip("_"))
    def _fn(opctx, attrs, x, _jfn=jfn):
        return _jfn(x, attrs["scalar"])


for _name, _jfn in _SCALAR.items():
    _register_scalar(_name, _jfn, _SCALAR_ALIASES.get(_name, ()))


# ---------------------------------------------------------------------------
# Broadcast binary family
# ---------------------------------------------------------------------------

_BROADCAST = {
    "broadcast_add": jnp.add,
    "broadcast_sub": jnp.subtract,
    "broadcast_mul": jnp.multiply,
    "broadcast_div": jnp.divide,
    "broadcast_mod": jnp.mod,
    "broadcast_power": jnp.power,
    "broadcast_maximum": jnp.maximum,
    "broadcast_minimum": jnp.minimum,
    "broadcast_hypot": jnp.hypot,
}

_BROADCAST_CMP = {
    "broadcast_equal": jnp.equal,
    "broadcast_not_equal": jnp.not_equal,
    "broadcast_greater": jnp.greater,
    "broadcast_greater_equal": jnp.greater_equal,
    "broadcast_lesser": jnp.less,
    "broadcast_lesser_equal": jnp.less_equal,
}

_BROADCAST_ALIASES = {
    "broadcast_add": ("broadcast_plus",),
    "broadcast_sub": ("broadcast_minus",),
}

for _name, _jfn in _BROADCAST.items():
    _register_binary(_name, _jfn, _BROADCAST_ALIASES.get(_name, ()))
for _name, _jfn in _BROADCAST_CMP.items():
    _register_binary(_name, _jfn, compare=True)


def _infer_broadcast_axis(attrs, in_shapes):
    (ishape,) = in_shapes
    if ishape is None:
        return in_shapes, [None], []
    axes = attrs.get("axis") or ()
    sizes = attrs.get("size") or ()
    if isinstance(axes, int):
        axes = (axes,)
    if isinstance(sizes, int):
        sizes = (sizes,)
    out = list(ishape)
    for ax, sz in zip(axes, sizes):
        out[ax] = sz
    return in_shapes, [tuple(out)], []


@register("broadcast_axis", params={"axis": Param("shape", ()), "size": Param("shape", ())},
          aliases=("broadcast_axes",), infer_shape=_infer_broadcast_axis)
def _broadcast_axis(opctx, attrs, x):
    axes = attrs.get("axis") or ()
    sizes = attrs.get("size") or ()
    if isinstance(axes, int):
        axes = (axes,)
    if isinstance(sizes, int):
        sizes = (sizes,)
    shape = list(x.shape)
    for ax, sz in zip(axes, sizes):
        shape[ax] = sz
    return jnp.broadcast_to(x, tuple(shape))


def _infer_broadcast_to(attrs, in_shapes):
    (ishape,) = in_shapes
    if ishape is None:
        return in_shapes, [None], []
    tgt = list(attrs.get("shape") or ())
    for i, s in enumerate(tgt):
        if s == 0:
            tgt[i] = ishape[i]
    return in_shapes, [tuple(tgt)], []


@register("broadcast_to", params={"shape": Param("shape", ())},
          infer_shape=_infer_broadcast_to)
def _broadcast_to(opctx, attrs, x):
    tgt = list(attrs.get("shape") or ())
    for i, s in enumerate(tgt):
        if s == 0:
            tgt[i] = x.shape[i]
    return jnp.broadcast_to(x, tuple(tgt))


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

_REDUCE_SPEC = {
    "axis": Param("shape-or-none", None),
    "keepdims": Param(bool, False),
    "exclude": Param(bool, False),
}


def _norm_axis(attrs, ndim):
    axis = attrs.get("axis")
    if axis is None or axis == ():
        return None
    if isinstance(axis, int):
        axis = (axis,)
    axis = tuple(a % ndim for a in axis)
    if attrs.get("exclude"):
        axis = tuple(i for i in range(ndim) if i not in axis)
    return axis


def _reduce_out_shape(ishape, axis, keepdims):
    if axis is None:
        return (1,) * len(ishape) if keepdims else ()
    out = list(ishape)
    for a in sorted(axis, reverse=True):
        if keepdims:
            out[a] = 1
        else:
            del out[a]
    return tuple(out)


def _make_reduce_infer():
    def infer(attrs, in_shapes):
        (ishape,) = in_shapes
        if ishape is None:
            return in_shapes, [None], []
        axis = _norm_axis(attrs, len(ishape))
        return in_shapes, [_reduce_out_shape(ishape, axis, attrs.get("keepdims", False))], []

    return infer


_REDUCE = {
    "sum": jnp.sum,
    "mean": jnp.mean,
    "prod": jnp.prod,
    "nansum": jnp.nansum,
    "nanprod": jnp.nanprod,
    "max": jnp.max,
    "min": jnp.min,
}

_REDUCE_ALIASES = {"sum": ("sum_axis",), "max": ("max_axis",), "min": ("min_axis",)}


def _register_reduce(name, jfn, aliases=()):
    @register(name, inputs=("data",), params=dict(_REDUCE_SPEC), aliases=aliases,
              infer_shape=_make_reduce_infer(), hint=name)
    def _fn(opctx, attrs, x, _jfn=jfn):
        axis = _norm_axis(attrs, x.ndim)
        return _jfn(x, axis=axis, keepdims=attrs.get("keepdims", False))


for _name, _jfn in _REDUCE.items():
    _register_reduce(_name, _jfn, _REDUCE_ALIASES.get(_name, ()))


_ARG_SPEC = {"axis": Param("int-or-none", None), "keepdims": Param(bool, False)}


def _register_argreduce(name, jfn):
    def infer(attrs, in_shapes):
        (ishape,) = in_shapes
        if ishape is None:
            return in_shapes, [None], []
        axis = attrs.get("axis")
        kd = attrs.get("keepdims", False)
        ax = None if axis is None else (axis % len(ishape),)
        return in_shapes, [_reduce_out_shape(ishape, ax, kd)], []

    @register(name, inputs=("data",), params=dict(_ARG_SPEC), infer_shape=infer)
    def _fn(opctx, attrs, x, _jfn=jfn):
        axis = attrs.get("axis")
        # Reference returns float indices (pre-integer-dtype era,
        # broadcast_reduce_op.h) — keep for parity.
        out = _jfn(x, axis=axis)
        if attrs.get("keepdims", False) and axis is not None:
            out = jnp.expand_dims(out, axis)
        return out.astype(jnp.float32 if x.dtype == jnp.float64 else x.dtype)


_register_argreduce("argmax", jnp.argmax)
_register_argreduce("argmin", jnp.argmin)


@register("argmax_channel")
def _argmax_channel(opctx, attrs, x):
    return jnp.argmax(x, axis=1).astype(x.dtype)


@register("norm", infer_shape=lambda attrs, s: (s, [(1,)], []))
def _norm(opctx, attrs, x):
    return jnp.sqrt(jnp.sum(jnp.square(x))).reshape((1,))


# ---------------------------------------------------------------------------
# N-ary sum (ElementWiseSum / add_n — reference src/operator/tensor/elemwise_sum.h)
# ---------------------------------------------------------------------------


@register("add_n", key_var_num_args="num_args", inputs=("data",),
          params={"num_args": Param(int, required=True)},
          aliases=("ElementWiseSum", "_sum"), hint="add_n")
def _add_n(opctx, attrs, *args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out
