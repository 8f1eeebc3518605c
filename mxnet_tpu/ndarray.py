"""NDArray — the imperative n-dim array over ``jax.Array``.

TPU-native redesign of /root/reference/include/mxnet/ndarray.h:33-374 +
src/ndarray/ndarray.cc.  The reference NDArray is a ref-counted chunk whose
every mutation is pushed to the dependency engine; here the "engine" is JAX's
async dispatch — every op returns immediately with a future-backed
``jax.Array``; ``wait_to_read`` ≈ ``block_until_ready`` (ndarray.h:153-168).
Mutation keeps MXNet surface semantics (``a[:] = x``, ``a += b``, ``out=``)
by rebinding the underlying immutable buffer on the same Python object, so
holders of the NDArray (executors, optimizers) observe updates.

The whole ``mx.nd.<op>`` function surface is generated from the op registry
at import, mirroring the reference's import-time codegen from the C op
registry (python/mxnet/_ctypes/ndarray.py:165-200).

Save/load keeps the reference's binary ``.params`` format bit-for-bit
(src/ndarray/ndarray.cc:633-714: magic 0x112, TShape uint32s, Context two
int32s, mshadow type flag, raw buffer; dmlc vector<string> keys).
"""
from __future__ import annotations

import struct
from typing import Dict, List, Optional, Union

import numpy as np

from .base import MXNetError, mx_real_t
from .context import Context, current_context
from .ops import OpContext, registered_ops
from .ops.param import _np_dtype
from . import random as _random

_pyslice = slice  # op autogen shadows builtins (slice/sum/max/...) at module level
_pyabs = abs

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concatenate", "load", "save", "imdecode", "onehot_encode",
           "waitall", "moveaxis"]


def _default_ctx(ctx) -> Context:
    return ctx if ctx is not None else current_context()


def _as_jax(x, ctx=None, dtype=None):
    import jax
    import jax.numpy as jnp

    if isinstance(x, NDArray):
        data = x._data
    elif isinstance(x, np.ndarray):
        data = jnp.asarray(x)
    elif isinstance(x, (int, float, np.generic)):
        data = jnp.asarray(x, dtype or mx_real_t)
    else:
        # Python lists/tuples default to float32 like the reference's
        # nd.array (python/mxnet/ndarray.py array(): dtype=float32 unless
        # the source carries its own dtype).
        data = jnp.asarray(x, dtype or mx_real_t)
    if dtype is not None:
        dt = _np_dtype(dtype) if isinstance(dtype, str) else dtype
        if data.dtype != dt:
            data = data.astype(dt)
    return data


# Hook installed by comm_engine: called with the NDArray before any host
# read so an in-flight async kvstore pull targeting it completes first
# (the reference engine's WaitToRead dependency, threaded_engine.h).
_async_read_guard = None


class NDArray:
    """n-dim array on a device context (reference: include/mxnet/ndarray.h)."""

    __slots__ = ("_data", "_ctx", "writable")

    def __init__(self, data, ctx: Optional[Context] = None, writable: bool = True):
        import jax.numpy as jnp

        if isinstance(data, NDArray):
            data = data._data
        elif isinstance(data, np.ndarray) or np.isscalar(data):
            data = jnp.asarray(data)
        self._data = data
        self._ctx = _default_ctx(ctx)
        self.writable = writable

    # -- properties --------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self) -> Context:
        return self._ctx

    @property
    def ctx(self) -> Context:
        return self._ctx

    @property
    def handle(self):
        return self  # parity shim: C-handle == the object itself

    # -- sync / host transfer ---------------------------------------------
    def wait_to_read(self):
        """Block until the async value is materialised (ndarray.h:153-160).
        When an async kvstore pull targets this array, also block until that
        pull lands (the engine's WaitToRead contract, comm_engine.py)."""
        g = _async_read_guard
        if g is not None:
            g(self)
        self._data.block_until_ready()

    def wait_to_write(self):
        g = _async_read_guard
        if g is not None:
            g(self)
        self._data.block_until_ready()

    def asnumpy(self) -> np.ndarray:
        g = _async_read_guard
        if g is not None:
            g(self)
        x = self._data
        # multi-process (global-mesh) arrays: a fully-replicated array has a
        # complete local copy on every process — read that; a sharded global
        # array has no local materialization and the caller should use the
        # executor-group accessors that return the process-local slice
        if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
            if getattr(x, "is_fully_replicated", False):
                return np.asarray(x.addressable_shards[0].data)
            raise MXNetError(
                "array is sharded across processes; use the module/executor "
                "accessors (get_outputs) for the process-local slice")
        return np.asarray(x)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def astype(self, dtype) -> "NDArray":
        if isinstance(dtype, str):
            dtype = _np_dtype(dtype)
        return NDArray(self._data.astype(dtype), self._ctx)

    # -- copies / context moves -------------------------------------------
    def copy(self) -> "NDArray":
        return NDArray(self._data, self._ctx)

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        """Copy into a destination array or context (reference CopyFromTo,
        ndarray.cc:250-328 — device-pair dispatch is jax.device_put here)."""
        import jax

        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError("shape mismatch in copyto")
            other._set(jax.device_put(self._data, other._ctx.jax_device())
                       .astype(other.dtype))
            return other
        ctx = Context(other)
        return NDArray(jax.device_put(self._data, ctx.jax_device()), ctx)

    def as_in_context(self, context: Context) -> "NDArray":
        if context == self._ctx:
            return self
        return self.copyto(context)

    def _set(self, data):
        if not self.writable:
            raise MXNetError("trying to write to a readonly NDArray")
        self._data = data

    # -- shape ops (zero-copy in XLA; reference ndarray.h:286-352) ---------
    def reshape(self, shape) -> "NDArray":
        if isinstance(shape, int):
            shape = (shape,)
        from .ops.matrix import _reshape_target

        return NDArray(self._data.reshape(_reshape_target(self.shape, shape)), self._ctx)

    def broadcast_to(self, shape) -> "NDArray":
        """Broadcast along extent-1 axes to ``shape`` (reference
        ndarray.py broadcast_to). A shorter current shape is left-padded
        with 1s like the reference; 0 in the target keeps the input
        extent (the registered op's convention — this method delegates
        to it so the two surfaces cannot diverge)."""
        shape = tuple(int(d) for d in shape)
        cur = self
        if len(self.shape) < len(shape):
            cur = self.reshape(
                (1,) * (len(shape) - len(self.shape)) + self.shape)
        if len(cur.shape) != len(shape):
            raise ValueError("cannot broadcast %s to lower-rank %s"
                             % (self.shape, shape))
        if any(c != t and c != 1 and t != 0
               for c, t in zip(cur.shape, shape)):
            raise ValueError(
                "cannot broadcast %s to %s (only extent-1 axes "
                "broadcast)" % (self.shape, shape))
        return _invoke("broadcast_to", (cur,), {"shape": shape})

    @property
    def T(self) -> "NDArray":
        return NDArray(self._data.T, self._ctx)

    def slice(self, start, stop) -> "NDArray":
        """Return a sub-array over axis 0.

        DOCUMENTED DEVIATION from the reference: ``Slice``/``__getitem__``
        there return zero-copy aliases of the parent's storage
        (include/mxnet/ndarray.h:286-352) so writes through a slice mutate
        the parent.  ``jax.Array`` is immutable, so slices here are
        independent copies; write into a region with ``a[i:j] = v`` on the
        parent instead.  Covered by tests/unittest/test_ndarray.py.
        """
        return NDArray(self._data[start:stop], self._ctx)

    def __len__(self):
        return self.shape[0]

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data
        return NDArray(self._data[key], self._ctx)

    def __setitem__(self, key, value):
        import jax
        import jax.numpy as jnp

        if isinstance(value, NDArray):
            value = value._data
            if isinstance(value, jax.Array) and \
                    not value.is_fully_addressable and \
                    value.is_fully_replicated:
                # replicated over other processes too: read the local copy
                # (as asnumpy does)
                value = value.addressable_shards[0].data
        elif isinstance(value, np.ndarray):
            value = jnp.asarray(value, self.dtype)
        if isinstance(key, NDArray):
            key = key._data
        if isinstance(key, _pyslice) and key == _pyslice(None):
            if np.isscalar(value):
                new = jnp.full(self.shape, value, self.dtype)
            else:
                value = jnp.asarray(value, self.dtype)
                new = jnp.broadcast_to(value, self.shape)
        else:
            new = self._data.at[key].set(value)
        # a write never moves the array: the new value is committed where
        # the old one lived (jnp.* alone lands on the default device,
        # whatever this array's context says).  Arrays spanning other
        # processes are placed by their owners (executor_group).
        if isinstance(self._data, jax.Array) and \
                self._data.is_fully_addressable:
            new = jax.device_put(new, self._data.sharding)
        self._set(new)

    # -- arithmetic --------------------------------------------------------
    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return _invoke(op, (a, b), {})
        if np.isscalar(other):
            return _invoke(scalar_op, (self,), {"scalar": float(other)})
        raise TypeError("unsupported operand type %s" % type(other))

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binary(o, "broadcast_div", "_rdiv_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_rmod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_rpower_scalar", reverse=True)

    def __neg__(self):
        return _invoke("negative", (self,), {})

    def __abs__(self):
        return _invoke("abs", (self,), {})

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __iadd__(self, o):
        out = self.__add__(o)
        self._set(out._data)
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._set(out._data)
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._set(out._data)
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._set(out._data)
        return self

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple elements "
                         "is ambiguous")

    def __repr__(self):
        return "<NDArray %s @%s>\n%s" % (
            "x".join(str(s) for s in self.shape), self._ctx, self.asnumpy())

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx_type": self._ctx.device_type,
                "ctx_id": self._ctx.device_id, "writable": self.writable}

    def __setstate__(self, state):
        import jax.numpy as jnp

        self._data = jnp.asarray(state["data"])
        self._ctx = Context(state["ctx_type"], state["ctx_id"])
        self.writable = state["writable"]


# ---------------------------------------------------------------------------
# Imperative invoke — the analogue of MXImperativeInvoke
# (/root/reference/src/c_api/c_api_ndarray.cc:323)
# ---------------------------------------------------------------------------


def _is_tensor_arg(v) -> bool:
    """True for tensor-like kwargs (NDArray / ndarray / jax.Array).  numpy
    scalars (``np.float32(2.0)``) carry dtype+shape but are attrs, not
    tensor inputs."""
    if isinstance(v, NDArray):
        return True
    if isinstance(v, np.generic):
        return False
    if isinstance(v, np.ndarray):
        return True
    return hasattr(v, "dtype") and hasattr(v, "shape") and hasattr(v, "ndim")


def _invoke(op_name: str, args, kwargs):
    op = registered_ops()[op_name]
    out = kwargs.pop("out", None)
    kwargs.pop("name", None)
    nd_kwargs = {}
    attrs = {}
    for k, v in kwargs.items():
        if _is_tensor_arg(v):
            nd_kwargs[k] = v
        else:
            attrs[k] = v
    pos_inputs = [a for a in args if a is not None]
    if op.key_var_num_args and op.key_var_num_args not in attrs:
        attrs[op.key_var_num_args] = len(pos_inputs)
    parsed = op.parse_attrs(attrs)
    names = op.input_names(parsed) + op.aux_names(parsed)
    inputs = list(pos_inputs)
    if nd_kwargs:
        slot = {n: a for n, a in zip(names, inputs)}
        slot.update(nd_kwargs)
        inputs = [slot[n] for n in names if n in slot]
    ctx = None
    for a in inputs:
        if isinstance(a, NDArray):
            ctx = a.context
            break
    if ctx is None:
        ctx_attr = parsed.get("ctx")
        if ctx_attr:
            dt, _, di = str(ctx_attr).partition("(")
            ctx = Context(dt, int(di.rstrip(")")) if di else 0)
        else:
            ctx = current_context()
    jarrs = [a._data if isinstance(a, NDArray) else _as_jax(a) for a in inputs]
    n_aux = len(op.aux_names(parsed))
    aux_in = tuple(jarrs[len(jarrs) - n_aux:]) if n_aux else ()
    main_in = jarrs[: len(jarrs) - n_aux] if n_aux else jarrs
    opctx = OpContext(is_train=False,
                      rng=_random.next_key() if op.stochastic else None)
    outs, aux_updates = op.apply(opctx, parsed, main_in, aux_in)
    # write aux updates back (engine-mutation parity for aux states)
    if n_aux:
        for holder, new in zip(inputs[len(inputs) - n_aux:], aux_updates):
            if isinstance(holder, NDArray):
                holder._set(new)
    results = [NDArray(o, ctx) for o in outs]
    from .base import env as _env

    if _env("MXNET_ENGINE_TYPE") == "NaiveEngine":
        # NaiveEngine debug contract: synchronous execution, block after
        # every op (reference src/engine/naive_engine.cc — executes on push)
        for r in results:
            r._data.block_until_ready()
    if out is not None:
        outs_t = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs_t, results):
            dst._set(src._data.astype(dst.dtype) if dst.dtype != src.dtype else src._data)
        return out
    if len(results) == 1:
        return results[0]
    return results


def _make_imperative(op_name: str, op):
    def fn(*args, **kwargs):
        return _invoke(op_name, args, kwargs)

    fn.__name__ = op_name
    fn.__doc__ = op.doc or "Auto-generated imperative wrapper for op %s" % op_name
    return fn


def _init_ops():
    g = globals()
    for name, op in registered_ops().items():
        fn = _make_imperative(name, op)
        g[name] = fn
        if name.startswith("_") or name in __all__:
            continue
        __all__.append(name)


# ---------------------------------------------------------------------------
# Creation functions
# ---------------------------------------------------------------------------


def array(source_array, ctx=None, dtype=None) -> NDArray:
    import jax
    import jax.numpy as jnp

    carries_dtype = isinstance(source_array, (NDArray, np.ndarray, np.generic))
    if isinstance(source_array, NDArray):
        arr = source_array._data
    else:
        arr = np.asarray(source_array)
    if dtype is None:
        if not carries_dtype:
            dtype = mx_real_t  # python lists default to float32 (reference array())
        elif arr.dtype == np.float64:
            dtype = mx_real_t  # reference defaults to float32
        elif arr.dtype == np.int64:
            dtype = np.int32
        else:
            dtype = arr.dtype
    if isinstance(dtype, str):
        dtype = _np_dtype(dtype)
    ctx = _default_ctx(ctx)
    data = jax.device_put(jnp.asarray(arr, dtype), ctx.jax_device())
    return NDArray(data, ctx)


def empty(shape, ctx=None, dtype=mx_real_t) -> NDArray:
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=mx_real_t) -> NDArray:
    import jax
    import jax.numpy as jnp

    if isinstance(shape, int):
        shape = (shape,)
    if isinstance(dtype, str):
        dtype = _np_dtype(dtype)
    ctx = _default_ctx(ctx)
    return NDArray(jax.device_put(jnp.zeros(shape, dtype), ctx.jax_device()), ctx)


def ones(shape, ctx=None, dtype=mx_real_t) -> NDArray:
    import jax
    import jax.numpy as jnp

    if isinstance(shape, int):
        shape = (shape,)
    if isinstance(dtype, str):
        dtype = _np_dtype(dtype)
    ctx = _default_ctx(ctx)
    return NDArray(jax.device_put(jnp.ones(shape, dtype), ctx.jax_device()), ctx)


def full(shape, val, ctx=None, dtype=mx_real_t) -> NDArray:
    import jax.numpy as jnp

    if isinstance(shape, int):
        shape = (shape,)
    if isinstance(dtype, str):
        dtype = _np_dtype(dtype)
    return NDArray(jnp.full(shape, val, dtype), _default_ctx(ctx))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=mx_real_t) -> NDArray:
    import jax.numpy as jnp

    if isinstance(dtype, str):
        dtype = _np_dtype(dtype)
    vals = np.arange(start, stop, step) if stop is not None else np.arange(start)
    if repeat > 1:
        vals = np.repeat(vals, repeat)
    return NDArray(jnp.asarray(vals, dtype), _default_ctx(ctx))


def moveaxis(tensor, source, destination) -> NDArray:
    import jax.numpy as jnp

    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor.context)


def concatenate(arrays, axis=0, always_copy=True) -> NDArray:
    import jax.numpy as jnp

    assert arrays, "arrays must not be empty"
    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis),
                   arrays[0].context)


def onehot_encode(indices, out) -> NDArray:
    return _invoke("_onehot_encode", (indices, out), {"out": out})


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3, mean=None):
    """Decode a JPEG/PNG buffer via the registered ``_imdecode`` op
    (reference python/mxnet/ndarray.py imdecode -> _imdecode NDArray
    function, ndarray.cc:796+): CHW float32 output, optional crop box and
    CHW mean subtraction — the reference's layout contract."""
    if isinstance(str_img, NDArray):
        buf = str_img
    else:
        data = str_img if isinstance(str_img, (bytes, bytearray)) \
            else bytes(str_img)
        buf = array(np.frombuffer(data, dtype=np.uint8))
    mean_arr = mean if mean is not None else array(
        np.zeros((0,), np.float32))
    x0, y0, x1, y1 = clip_rect if clip_rect else (0, 0, 0, 0)
    res = _invoke("_imdecode", (mean_arr, buf),
                  {"index": index, "x0": x0, "y0": y0, "x1": x1, "y1": y1,
                   "c": channels, "size": 0})
    if out is not None:
        # reference Imdecode writes into slice ``index`` of a 4-D batch
        # buffer (ndarray.cc: ret->Slice(index, index+1)); a 3-D out is
        # filled whole
        if out.ndim == 4:
            out[index:index + 1] = res.reshape((1,) + res.shape)
        else:
            out[:] = res
        return out
    return res


def waitall():
    """Block until all async work completes (reference: Engine WaitForAll via
    MXNDArrayWaitAll).  Blocks on every live ``jax.Array`` — the actual set of
    outstanding async results — plus the effects token stream."""
    import jax

    for a in jax.live_arrays():
        a.block_until_ready()
    jax.effects_barrier()


# ---------------------------------------------------------------------------
# Save / load — reference .params binary format, bit-for-bit
# (src/ndarray/ndarray.cc:633-714)
# ---------------------------------------------------------------------------

_MAGIC = 0x112
# mshadow type flags (mshadow/base.h enum order).  bfloat16 has NO flag in the
# reference enum: bf16 arrays are widened to float32 and saved as flag 0 so the
# file stays readable by the reference implementation (documented deviation —
# dtype is not round-tripped for bf16).
_TYPE_FLAG = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3, "int32": 4,
              "int8": 5, "int64": 6}
_FLAG_TYPE = {v: k for k, v in _TYPE_FLAG.items()}


def _save_one(f, arr: NDArray):
    shape = arr.shape
    f.write(struct.pack("<I", len(shape)))
    if shape:
        f.write(struct.pack("<%dI" % len(shape), *shape))
    if len(shape) == 0:
        return
    dev_type = arr.context.device_typeid
    f.write(struct.pack("<ii", dev_type, arr.context.device_id))
    host = arr.asnumpy()
    dtype_name = str(np.dtype(host.dtype)) if host.dtype.kind != "V" else "bfloat16"
    if dtype_name not in _TYPE_FLAG:
        # bf16 (and any other type outside the reference enum) is widened to
        # float32 and declared as flag 0 so the payload matches the header.
        host = host.astype(np.float32)
        dtype_name = "float32"
    f.write(struct.pack("<i", _TYPE_FLAG[dtype_name]))
    f.write(host.tobytes())


def _load_one(f) -> NDArray:
    (ndim,) = struct.unpack("<I", f.read(4))
    shape = struct.unpack("<%dI" % ndim, f.read(4 * ndim)) if ndim else ()
    if ndim == 0:
        return NDArray(np.zeros(()), cpu_ctx())
    dev_type, dev_id = struct.unpack("<ii", f.read(8))
    (type_flag,) = struct.unpack("<i", f.read(4))
    if type_flag == 7:
        # legacy compat: earlier versions of THIS framework wrote bf16 arrays
        # with invented flag 7 and a float32-widened payload; read them as
        # float32.  (Upstream MXNet >=1.6 uses 7 for kBool, which the 0.9
        # reference this targets never emits.)
        dtype_name = "float32"
    elif type_flag not in _FLAG_TYPE:
        # guessing an element size here would desynchronize the stream and
        # silently corrupt every subsequent array in the container
        raise MXNetError("unknown mshadow type flag %d in .params file"
                         % type_flag)
    else:
        dtype_name = _FLAG_TYPE[type_flag]
    np_dtype = np.dtype(dtype_name)
    count = int(np.prod(shape))
    buf = f.read(count * np_dtype.itemsize)
    host = np.frombuffer(buf, dtype=np_dtype).reshape(shape)
    # Preserve the stored dtype exactly (reference NDArray::Load keeps the
    # type flag; array()'s float64->float32 default coercion must not apply).
    # 64-bit dtypes need JAX x64 mode; without it warn instead of silently
    # downcasting (TPUs have no native f64 — set JAX_ENABLE_X64=1 on CPU).
    import jax

    if np_dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        import warnings

        warnings.warn(
            "loading %s array as %s: JAX x64 mode is disabled "
            "(set JAX_ENABLE_X64=1 to preserve 64-bit dtypes)"
            % (dtype_name, "float32" if np_dtype.kind == "f" else "int32"))
        np_dtype = np.dtype(np.float32 if np_dtype.kind == "f" else np.int32)
        host = host.astype(np_dtype)
    return array(host, dtype=np_dtype)


def cpu_ctx():
    from .context import cpu

    return cpu()


def _save_stream(f, data) -> None:
    """Write a .params container to any binary file object (the writer
    half of :func:`_load_stream`)."""
    if isinstance(data, NDArray):
        data = [data]
    names: List[str] = []
    arrays: List[NDArray] = []
    if isinstance(data, dict):
        for k, v in data.items():
            names.append(k)
            arrays.append(v)
    else:
        arrays = list(data)
    f.write(struct.pack("<QQ", _MAGIC, 0))
    f.write(struct.pack("<Q", len(arrays)))
    for arr in arrays:
        _save_one(f, arr)
    f.write(struct.pack("<Q", len(names)))
    for n in names:
        nb = n.encode("utf-8")
        f.write(struct.pack("<Q", len(nb)))
        f.write(nb)


def save(fname: str, data, checksum: bool = False,
         op: str = "params.write") -> None:
    """Save NDArrays in the reference's .params container format.

    Local paths are written atomically (tmp + fsync + ``os.replace``,
    filesystem.atomic_write): a crash mid-save can no longer leave a torn
    file that shadows the previous good one.  ``checksum`` additionally
    writes a CRC32 sidecar (checkpoint saves use this so discovery can
    reject silently-corrupted files)."""
    from .filesystem import atomic_write, local_path

    lp = local_path(fname)
    if lp is not None:
        atomic_write(lp, lambda f: _save_stream(f, data),
                     checksum=checksum, op=op)
        return
    from .filesystem import open_uri

    with open_uri(fname, "wb") as f:
        _save_stream(f, data)


def _load_stream(f):
    """Read a .params container from any binary file object."""
    magic, _res = struct.unpack("<QQ", f.read(16))
    if magic != _MAGIC:
        raise MXNetError("Invalid NDArray file format (magic %#x)" % magic)
    (n,) = struct.unpack("<Q", f.read(8))
    arrays = [_load_one(f) for _ in range(n)]
    (nk,) = struct.unpack("<Q", f.read(8))
    names = []
    for _ in range(nk):
        (ln,) = struct.unpack("<Q", f.read(8))
        names.append(f.read(ln).decode("utf-8"))
    if names:
        return dict(zip(names, arrays))
    return arrays


def load(fname: str):
    """Load a .params container; returns dict if names present, else list."""
    with open(fname, "rb") as f:
        return _load_stream(f)


_init_ops()
