"""``mx.rtc`` — runtime compilation of user kernel SOURCE STRINGS.

Parity: the reference compiles raw CUDA C strings with NVRTC at runtime and
launches them on NDArrays (/root/reference/src/common/mxrtc.cc:117-135,
python/mxnet/rtc.py).  The TPU-native equivalent compiles a PALLAS kernel
from source text at runtime: the string defines a function
``kernel(<in_ref...>, <out_ref...>)`` over Pallas Refs; it is compiled on
first call and dispatched on NDArrays with the same ``__call__`` shape as
the reference's MXRtc.

    krnl = mx.rtc.MXRtc("axpy", [("x", x), ("y", y)], [("out", out)], '''
    def kernel(x_ref, y_ref, out_ref):
        out_ref[...] = 2.0 * x_ref[...] + y_ref[...]
    ''')
    krnl.push([x, y], [out])

For registering kernels as named graph ops (trainable, custom vjp) use
``mx.register_pallas_op`` — MXRtc is the imperative escape hatch.
"""
from __future__ import annotations

import textwrap
from typing import List, Sequence, Tuple

from .base import MXNetError
from .ops.interpret import interpret_for

__all__ = ["MXRtc"]


class MXRtc:
    """Compile ``kernel_src`` (Python/Pallas source) at runtime and run it
    imperatively on NDArrays.

    Parameters mirror the reference MXRtc: ``name``, ``inputs`` and
    ``outputs`` as (name, NDArray) prototype pairs fixing rank/dtype, and
    the kernel source string.  The reference's grid/block launch dims are
    derived automatically here (whole-array blocks); pass ``grid`` and
    Pallas ``in_specs``/``out_specs`` through ``**pallas_kwargs`` for tiled
    launches.
    """

    def __init__(self, name: str, inputs: Sequence[Tuple[str, object]],
                 outputs: Sequence[Tuple[str, object]], kernel_src: str,
                 **pallas_kwargs):
        self.name = name
        self._in_protos = [(n, tuple(a.shape)) for n, a in inputs]
        self._out_protos = [(n, tuple(a.shape), a.dtype)
                            for n, a in outputs]
        self._pallas_kwargs = dict(pallas_kwargs)
        src = textwrap.dedent(kernel_src)
        srcfile = "<mx.rtc:%s>" % name
        scope = {}
        try:
            exec(compile(src, srcfile, "exec"), scope)
        except Exception as e:
            raise MXNetError("rtc kernel %r failed to compile: %s"
                             % (name, e))
        fn = scope.get("kernel")
        if fn is None:
            # accept a single function DEFINED in the source under any name
            # (imported callables don't count — reference kernels are named
            # by the user)
            fns = [v for v in scope.values()
                   if callable(v) and
                   getattr(getattr(v, "__code__", None), "co_filename",
                           None) == srcfile]
            if len(fns) != 1:
                raise MXNetError(
                    "rtc kernel source must define exactly one function "
                    "(preferably named 'kernel')")
            fn = fns[0]
        self._kernel = fn
        self._compiled = {}  # interpret flag -> jitted call

    def _build(self, interpret):
        import jax
        from jax.experimental import pallas as pl

        out_shape = [jax.ShapeDtypeStruct(shape, dtype)
                     for _, shape, dtype in self._out_protos]
        call = pl.pallas_call(
            self._kernel,
            out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
            interpret=interpret,
            **self._pallas_kwargs)
        return jax.jit(lambda *a: call(*a))

    def push(self, ins, outs, grid_dims=None, block_dims=None):
        """Run the kernel (reference MXRtc.push signature; the launch dims
        are accepted for API parity — Pallas derives its own grid unless
        one was supplied at construction)."""
        from . import ndarray as nd

        if len(ins) != len(self._in_protos):
            raise MXNetError(
                "rtc %r expects %d inputs, got %d"
                % (self.name, len(self._in_protos), len(ins)))
        for arr, (pname, shape) in zip(ins, self._in_protos):
            if tuple(arr.shape) != shape:
                raise MXNetError(
                    "rtc %r input %s shape %s does not match prototype %s"
                    % (self.name, pname, tuple(arr.shape), shape))
        if len(outs) != len(self._out_protos):
            raise MXNetError(
                "rtc %r expects %d outputs, got %d"
                % (self.name, len(self._out_protos), len(outs)))
        for out, (pname, shape, dtype) in zip(outs, self._out_protos):
            if tuple(out.shape) != shape:
                raise MXNetError(
                    "rtc %r output %s shape %s does not match prototype %s"
                    % (self.name, pname, tuple(out.shape), shape))
        vals = [a._data if isinstance(a, nd.NDArray) else a for a in ins]
        # compiled on a tpu device, interpreted elsewhere: decided by where
        # the operands live (ops/interpret.py)
        interpret = interpret_for("rtc:%s" % self.name, vals)
        if interpret not in self._compiled:
            self._compiled[interpret] = self._build(interpret)
        result = self._compiled[interpret](*vals)
        if not isinstance(result, (list, tuple)):
            result = [result]
        for out, res in zip(outs, result):
            out._set(res)
        return outs

    __call__ = push
