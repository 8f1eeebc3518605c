"""Custom-operator subsystem — user-defined ops in Python.

TPU-native redesign of the reference's custom-op machinery
(/root/reference/src/operator/custom/custom.cc and
/root/reference/python/mxnet/operator.py:396-576): the reference calls back
from the C++ engine into Python through C function pointers run with
``ExecType::kAsync``; here the callback rides ``jax.pure_callback`` inside
the jitted graph, and the user-supplied backward is wired in with
``jax.custom_vjp`` (replacing the synthesized ``_backward_Custom`` node).

The host round-trip breaks XLA fusion at the custom-op boundary — same
fundamental cost as the reference's engine→Python hop; documented so users
keep custom ops off the hot path or port them to Pallas.

Also provides the legacy ``PythonOp``/``NDArrayOp`` classes
(reference python/mxnet/operator.py:19-226, registered there as the
``_Native``/``_NDArray`` ops): thin adapters over the same Custom path.
"""
from __future__ import annotations

import itertools
import threading
from typing import Any, Dict, List

import numpy as np

from .base import MXNetError

__all__ = ["CustomOp", "CustomOpProp", "register", "get_prop_class",
           "PythonOp", "NDArrayOp", "NumpyOp"]


class CustomOp(object):
    """Base class for user operators. Subclass and implement
    ``forward``/``backward``; use ``assign`` to honour the write request.

    Deviation from the reference: ``backward`` receives ``in_data``/
    ``out_data`` explicitly (saved as vjp residuals), and one operator
    instance may be shared by executors with identical input shapes — do
    NOT stash per-batch state on ``self`` in ``forward`` for use in
    ``backward``; recompute from the arrays that are passed in."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError()

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError()

    def assign(self, dst, req, src):
        """Write ``src`` into ``dst`` per request type (reference
        python/mxnet/operator.py:433-440)."""
        if req == "null":
            return
        elif req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = dst + src


class CustomOpProp(object):
    """Metadata provider for a custom op (shapes/types/arg lists/state).

    ``need_top_grad``: True when the op needs the gradient from the layer
    above (ordinary op); False for loss layers that are their own gradient
    source (reference python/mxnet/operator.py:442-453)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = bool(need_top_grad)

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return (in_type, [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def need_top_grad(self):
        return self.need_top_grad_

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        raise NotImplementedError()


_prop_registry: Dict[str, type] = {}
_registry_lock = threading.Lock()


def register(reg_name):
    """Decorator: register a ``CustomOpProp`` subclass under ``reg_name`` so
    ``mx.sym.Custom(..., op_type=reg_name)`` / ``mx.nd.Custom`` find it
    (reference python/mxnet/operator.py:576)."""

    def do_register(prop_cls):
        with _registry_lock:
            _prop_registry[reg_name] = prop_cls
            # re-registration under the same name (notebook workflows) must
            # not keep serving cached props of the old class
            for key in [k for k in _prop_cache if k[0] == reg_name]:
                del _prop_cache[key]
        return prop_cls

    return do_register


def get_prop_class(reg_name: str) -> type:
    try:
        return _prop_registry[reg_name]
    except KeyError:
        raise KeyError(
            "Custom op type %r is not registered; call "
            "mx.operator.register(%r) on a CustomOpProp subclass first"
            % (reg_name, reg_name))


# ---------------------------------------------------------------------------
# Bridging into the op registry / jitted graph
# ---------------------------------------------------------------------------

_RESERVED_ATTRS = ("ctx", "name", "op_type")


class _LRU(dict):
    """Tiny bounded cache — bucketing workloads create one entry per shape;
    unbounded growth would pin every CustomOp instance forever."""

    def __init__(self, maxsize=256):
        super(_LRU, self).__init__()
        self._maxsize = maxsize

    def __setitem__(self, key, value):
        if key not in self and len(self) >= self._maxsize:
            del self[next(iter(self))]
        super(_LRU, self).__setitem__(key, value)


_prop_cache: Dict[Any, CustomOpProp] = {}
_op_cache: Dict[Any, CustomOp] = _LRU()
# downstream caches key on this serial, not id(prop): after re-registration
# CPython may recycle a freed prop's address, which would nondeterministically
# serve a stale CustomOp built from the old class
_prop_serial_counter = itertools.count()


def _prop_key(prop) -> int:
    return getattr(prop, "_mx_prop_serial", id(prop))


def _user_kwargs(attrs: Dict[str, Any]) -> Dict[str, str]:
    return {k: v for k, v in attrs.items()
            if k not in _RESERVED_ATTRS and not k.startswith("__")}


def _get_prop(attrs: Dict[str, Any]) -> CustomOpProp:
    op_type = attrs["op_type"]
    kwargs = _user_kwargs(attrs)
    key = (op_type, tuple(sorted(kwargs.items())))
    prop = _prop_cache.get(key)
    if prop is None:
        prop = get_prop_class(op_type)(**kwargs)
        prop._mx_prop_serial = next(_prop_serial_counter)
        _prop_cache[key] = prop
    return prop


def _get_operator(prop: CustomOpProp, in_shapes, in_dtypes) -> CustomOp:
    key = (_prop_key(prop), tuple(map(tuple, in_shapes)),
           tuple(str(d) for d in in_dtypes))
    op = _op_cache.get(key)
    if op is None:
        from .context import cpu

        op = prop.create_operator(cpu(), [list(s) for s in in_shapes],
                                  list(in_dtypes))
        _op_cache[key] = op
    return op


def _to_ndarrays(np_arrays):
    """Wrap host numpy arrays as CPU NDArrays for the user callback (the
    reference hands engine TBlobs to Python as NDArrays)."""
    from .context import cpu
    from .ndarray import array

    return [array(a, ctx=cpu(), dtype=a.dtype) for a in np_arrays]


def _normalize_shapes(prop, in_shapes):
    """Run prop.infer_shape; tolerate the 2-tuple (no-aux) return form."""
    res = prop.infer_shape([list(s) for s in in_shapes])
    if len(res) == 2:
        ishapes, oshapes = res
        ashapes = []
    else:
        ishapes, oshapes, ashapes = res
    return ([tuple(s) for s in ishapes], [tuple(s) for s in oshapes],
            [tuple(s) for s in ashapes])


def _out_struct(prop, main, aux):
    import jax

    in_shapes = [tuple(t.shape) for t in main]
    in_dtypes = [np.dtype(t.dtype) for t in main] or [np.dtype(np.float32)]
    oshapes, odtypes = _out_spec(prop, in_shapes, in_dtypes)
    out_struct = tuple(jax.ShapeDtypeStruct(s, d)
                       for s, d in zip(oshapes, odtypes))
    aux_struct = tuple(jax.ShapeDtypeStruct(tuple(t.shape), np.dtype(t.dtype))
                       for t in aux)
    return out_struct, aux_struct


_out_spec_cache: Dict[Any, Any] = _LRU()


def _out_spec(prop, in_shapes, in_dtypes):
    """(out_shapes, out_dtypes) per (prop, shapes, dtypes) — computed once,
    not per training step."""
    key = (_prop_key(prop), tuple(map(tuple, in_shapes)),
           tuple(str(d) for d in in_dtypes))
    spec = _out_spec_cache.get(key)
    if spec is None:
        _, oshapes, _ = _normalize_shapes(prop, in_shapes)
        try:
            odts = [np.dtype(d) for d in prop.infer_type(list(in_dtypes))[1]]
        except NotImplementedError:
            odts = [np.dtype(in_dtypes[0])] * len(oshapes)
        spec = (oshapes, odts)
        _out_spec_cache[key] = spec
    return spec


def _host_forward(prop, is_train, main_np, aux_np):
    main_np = [np.asarray(a) for a in main_np]
    aux_np = [np.asarray(a) for a in aux_np]
    op = _get_operator(prop, [a.shape for a in main_np],
                       [a.dtype for a in main_np])
    in_nd = _to_ndarrays(main_np)
    aux_nd = _to_ndarrays(aux_np)
    oshapes, odts = _out_spec(prop, [a.shape for a in main_np],
                              [a.dtype for a in main_np])
    out_nd = _to_ndarrays([np.zeros(s, d) for s, d in zip(oshapes, odts)])
    req = ["write"] * len(out_nd)
    op.forward(bool(is_train), req, in_nd, out_nd, aux_nd)
    outs = tuple(o.asnumpy() for o in out_nd)
    auxs = tuple(a.asnumpy() for a in aux_nd)
    return outs + auxs


def _host_backward(prop, out_grad_np, main_np, out_np, aux_np):
    main_np = [np.asarray(a) for a in main_np]
    out_grad_np = [np.asarray(a) for a in out_grad_np]
    out_np = [np.asarray(a) for a in out_np]
    aux_np = [np.asarray(a) for a in aux_np]
    op = _get_operator(prop, [a.shape for a in main_np],
                       [a.dtype for a in main_np])
    in_nd = _to_ndarrays(main_np)
    og_nd = _to_ndarrays(out_grad_np)
    out_nd = _to_ndarrays(out_np)
    aux_nd = _to_ndarrays(aux_np)
    ig_nd = _to_ndarrays([np.zeros(a.shape, a.dtype) for a in main_np])
    req = ["write"] * len(ig_nd)
    op.backward(req, og_nd, in_nd, out_nd, ig_nd, aux_nd)
    return tuple(g.asnumpy() for g in ig_nd)


def _custom_call_eager(prop, is_train, main, aux):
    """Imperative path: direct host execution with no callback machinery —
    works on every platform (the reference's kAsync engine op calling into
    Python, custom-inl.h, without an engine)."""
    import jax.numpy as jnp

    main_np = [np.asarray(t) for t in main]
    aux_np = [np.asarray(t) for t in aux]
    res = _host_forward(prop, is_train, main_np, aux_np)
    return tuple(jnp.asarray(r) for r in res)


def _custom_call(prop, is_train, main, aux):
    """The jit-traceable core: pure_callback forward wrapped in custom_vjp
    whose backward pure_callbacks into the user's ``backward``."""
    import jax

    main = tuple(main)
    aux = tuple(aux)
    out_struct, aux_struct = _out_struct(prop, main, aux)
    n_out = len(out_struct)

    def fwd_cb(*arrs):
        m = arrs[:len(main)]
        a = arrs[len(main):]
        return _host_forward(prop, is_train, m, a)

    @jax.custom_vjp
    def run(main_t, aux_t):
        res = jax.pure_callback(fwd_cb, out_struct + aux_struct,
                                *main_t, *aux_t, vmap_method="sequential")
        return tuple(res[:n_out]), tuple(res[n_out:])

    def run_fwd(main_t, aux_t):
        outs, aux_new = run(main_t, aux_t)
        return (outs, aux_new), (main_t, outs, aux_new)

    def run_bwd(residual, cotangent):
        main_t, outs, aux_new = residual
        out_cot, _aux_cot = cotangent

        def bwd_cb(*arrs):
            og = arrs[:n_out]
            m = arrs[n_out:n_out + len(main_t)]
            o = arrs[n_out + len(main_t):2 * n_out + len(main_t)]
            a = arrs[2 * n_out + len(main_t):]
            return _host_backward(prop, og, m, o, a)

        in_struct = tuple(
            jax.ShapeDtypeStruct(t.shape, t.dtype) for t in main_t)
        grads = jax.pure_callback(bwd_cb, in_struct, *out_cot, *main_t,
                                  *outs, *aux_new, vmap_method="sequential")
        zero_aux = tuple(jax.numpy.zeros(t.shape, t.dtype) for t in aux_new)
        return (tuple(grads), zero_aux)

    run.defvjp(run_fwd, run_bwd)
    outs, aux_new = run(main, aux)
    return outs, aux_new


def _custom_kernel(opctx, attrs, *tensors):
    """Registry kernel for the ``Custom`` op."""
    import jax

    prop = _get_prop(attrs)
    n_args = len(prop.list_arguments())
    main = tensors[:n_args]
    aux = tensors[n_args:]
    if not any(isinstance(t, jax.core.Tracer) for t in tensors):
        # imperative mx.nd.Custom (or NaiveEngine executor): run on host
        # directly, no pure_callback round trip
        return _custom_call_eager(prop, opctx.is_train, main, aux)
    outs, aux_new = _custom_call(prop, opctx.is_train, main, aux)
    return tuple(outs) + tuple(aux_new)


def _custom_infer_shape(attrs, in_shapes):
    if any(s is None for s in in_shapes):
        raise ValueError("Custom op needs all input shapes")
    prop = _get_prop(attrs)
    return _normalize_shapes(prop, in_shapes)


def _register_legacy_callback_stubs():
    """``_Native``/``_NDArray`` nodes carry serialized C function POINTERS
    in the reference's JSON (python/mxnet/operator.py:19-226 pack ctypes
    addresses into the ``info`` attr) — not portable to any other process,
    in the reference either.  Register the names so such graphs LOAD and
    introspect; executing one raises with the porting path."""
    from .base import MXNetError
    from .ops.registry import register as reg_op

    def _make(name):
        @reg_op(name, inputs=("data",), allow_extra_attrs=True,
                hint=name.strip("_").lower())
        def _stub(opctx, attrs, *arrays):
            raise MXNetError(
                "%s carries process-local callback pointers and cannot "
                "execute from a serialized graph; re-create the op with "
                "PythonOp/NDArrayOp.get_symbol or mx.operator.register "
                "(Custom)" % name)

    _make("_Native")
    _make("_NDArray")


_register_legacy_callback_stubs()


def _register_custom_op():
    from .ops.param import Param
    from .ops.registry import register as reg_op

    reg_op(
        "Custom",
        inputs=lambda attrs: list(_get_prop(attrs).list_arguments()),
        num_outputs=lambda attrs: len(_get_prop(attrs).list_outputs()),
        aux=lambda attrs: list(_get_prop(attrs).list_auxiliary_states()),
        params={"op_type": Param(str, required=True,
                                 doc="registered CustomOpProp name")},
        allow_extra_attrs=True,
        infer_shape=_custom_infer_shape,
        output_names=lambda attrs: list(_get_prop(attrs).list_outputs()),
        hint="custom",
    )(_custom_kernel)


# ---------------------------------------------------------------------------
# Legacy PythonOp / NDArrayOp (reference ``_Native`` / ``_NDArray`` ops)
# ---------------------------------------------------------------------------

class PythonOp(object):
    """Base for the legacy numpy-callback op (reference
    python/mxnet/operator.py:19-120, op name ``_Native``). ``get_symbol``
    registers an adapter prop and returns a Custom symbol."""

    _legacy_counter = [0]

    def __init__(self, need_top_grad=True):
        self.info_ = None
        self.need_top_grad_ = bool(need_top_grad)

    # user API (numpy in/out, in-place writes into out arrays)
    def forward(self, in_data, out_data):
        out_data[0][:] = in_data[0]

    def backward(self, out_grad, in_data, out_data, in_grad):
        raise NotImplementedError()

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]]

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def need_top_grad(self):
        return self.need_top_grad_

    def _adapter_prop(self):
        legacy = self

        class _LegacyOp(CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                ins = [np.array(a.asnumpy()) for a in in_data]
                outs = [np.array(a.asnumpy()) for a in out_data]
                legacy.forward(in_data=ins, out_data=outs)
                for dst, src in zip(out_data, outs):
                    self.assign(dst, "write", src)

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                ogs = [np.array(a.asnumpy()) for a in out_grad]
                ins = [np.array(a.asnumpy()) for a in in_data]
                outs = [np.array(a.asnumpy()) for a in out_data]
                igs = [np.array(a.asnumpy()) for a in in_grad]
                legacy.backward(out_grad=ogs, in_data=ins, out_data=outs,
                                in_grad=igs)
                for dst, src in zip(in_grad, igs):
                    self.assign(dst, "write", src)

        class _LegacyProp(CustomOpProp):
            def __init__(self):
                super(_LegacyProp, self).__init__(
                    need_top_grad=legacy.need_top_grad())

            def list_arguments(self):
                return legacy.list_arguments()

            def list_outputs(self):
                return legacy.list_outputs()

            def infer_shape(self, in_shape):
                res = legacy.infer_shape(in_shape)
                return res if len(res) == 3 else (res[0], res[1], [])

            def create_operator(self, ctx, in_shapes, in_dtypes):
                return _LegacyOp()

        return _LegacyProp

    def get_symbol(self, *args, **kwargs):
        from . import symbol

        PythonOp._legacy_counter[0] += 1
        reg_name = "_legacy_python_op_%d" % PythonOp._legacy_counter[0]
        register(reg_name)(self._adapter_prop())
        kwargs["op_type"] = reg_name
        return symbol.Custom(*args, **kwargs)


class NDArrayOp(PythonOp):
    """Legacy NDArray-callback op (reference python/mxnet/operator.py:122-226,
    op name ``_NDArray``): forward/backward receive NDArrays."""

    def forward(self, in_data, out_data):
        out_data[0][:] = in_data[0]

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def _adapter_prop(self):
        legacy = self

        class _LegacyOp(CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                legacy.forward(in_data=in_data, out_data=out_data)

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                legacy.backward(out_grad=out_grad, in_data=in_data,
                                out_data=out_data, in_grad=in_grad)

        class _LegacyProp(CustomOpProp):
            def __init__(self):
                super(_LegacyProp, self).__init__(
                    need_top_grad=legacy.need_top_grad())

            def list_arguments(self):
                return legacy.list_arguments()

            def list_outputs(self):
                return legacy.list_outputs()

            def infer_shape(self, in_shape):
                res = legacy.infer_shape(in_shape)
                return res if len(res) == 3 else (res[0], res[1], [])

            def declare_backward_dependency(self, out_grad, in_data,
                                            out_data):
                return legacy.declare_backward_dependency(
                    out_grad, in_data, out_data)

            def create_operator(self, ctx, in_shapes, in_dtypes):
                return _LegacyOp()

        return _LegacyProp


#: reference alias — numpy-based op
NumpyOp = PythonOp

_register_custom_op()

# refresh the generated op surfaces (symbol/ndarray codegen ran at their
# import time, before Custom existed in the registry)
from . import ndarray as _nd_mod  # noqa: E402
from . import symbol as _sym_mod  # noqa: E402

_nd_mod._init_ops()
_sym_mod._init_symbol_module()
