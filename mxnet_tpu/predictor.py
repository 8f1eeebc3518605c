"""Predictor — the serving/inference path.

TPU-native redesign of the reference C predict API
(/root/reference/src/c_api/c_predict_api.cc:41-280: load symbol JSON +
param blob -> filter arg/aux dicts -> InferShape -> static bind -> SetInput/
Forward/GetOutput) plus the amalgamation deployment story
(/root/reference/amalgamation/README.md:1-14).  Two artifacts:

  * ``Predictor`` — loads a checkpoint (symbol JSON + ``.params``), binds a
    static inference executor (no grads), and serves ``forward()``.
  * ``Predictor.export(path)`` / ``load_exported(path)`` — ahead-of-time
    compilation via ``jax.export``: the whole jitted forward (params baked
    in) serialized as a portable StableHLO artifact, reloadable without the
    model-building Python code — the amalgamation equivalent.
"""
from __future__ import annotations

import io
import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import profiler as _prof
from .base import MXNetError
from .context import Context, current_context

__all__ = ["Predictor", "load_exported"]

_EXPORT_MAGIC = b"MXTPUEXP1"


def on_ctx(value, ctx):
    """``value`` as an NDArray living on ``ctx``: the identity for one
    already there (live device arrays are shared, not copied), a copy for
    one elsewhere, an upload for host data."""
    from . import ndarray as nd

    return value.as_in_context(ctx) if isinstance(value, nd.NDArray) \
        else nd.array(value, ctx)


class Predictor:
    """Static bound forward over a trained (symbol, params) checkpoint.

    Parameters
    ----------
    symbol : Symbol | str
        A Symbol, a path to ``prefix-symbol.json``, or a JSON string.
    params : dict | str
        ``{name: NDArray}`` (``arg:``/``aux:`` prefixes allowed, as stored
        by ``save_checkpoint``) or a path to a ``.params`` file.
    input_shapes : dict
        ``{input_name: shape}`` — static shapes, like MXPredCreate's
        input_keys/shape arrays.  An input that ``params`` also holds is
        bound to that array and not to zeros of its own: state another
        owner keeps on the device (the generation engine's KV pool).
    """

    def __init__(self, symbol, params, input_shapes: Dict[str, Sequence[int]],
                 ctx: Optional[Context] = None, dtype=np.float32):
        from . import ndarray as nd
        from . import symbol as sym

        if isinstance(symbol, str):
            if os.path.exists(symbol):
                symbol = sym.load(symbol)
            else:
                symbol = sym.load_json(symbol)
        if isinstance(params, str):
            params = nd.load(params)
        arg_params, aux_params = {}, {}
        for k, v in params.items():
            tp, _, name = k.partition(":")
            if tp == "arg":
                arg_params[name] = v
            elif tp == "aux":
                aux_params[name] = v
            else:
                arg_params[k] = v

        # None: the current context (docs/how_to/deviations.md "Default
        # context") — the chip when one is attached, like Module
        self._ctx = ctx or current_context()
        self._symbol = symbol
        self._input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        self._dtype = np.dtype(dtype)
        # one predictor a bucket: ``bucket`` is its inputs' leading size
        with _prof.Frame("start:bind", "startup", {
                "kind": "predict", "bucket": next(
                    (s[0] for s in self._input_shapes.values() if s), 0)}):
            self._bind(symbol, arg_params, aux_params)

    def _bind(self, symbol, arg_params, aux_params):
        arg_shapes, _, aux_shapes = symbol.infer_shape(**self._input_shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes from the given inputs")
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        # the checkpoint onto the context (nothing moves for what another
        # owner, the engine or the base bucket, already placed there)
        with _prof.Frame("start:params", "startup") as span:
            args, aux = self._place(arg_params, aux_params,
                                    zip(arg_names, arg_shapes), aux_names)
            span.set(**_prof.leaves_bytes(
                list(args.values()) + list(aux.values())))
        self._bound_inputs = set(self._input_shapes) & set(arg_params)
        self._exec = symbol.bind(self._ctx, args, args_grad=None,
                                 grad_req="null", aux_states=aux)
        self._input_names = list(self._input_shapes)

    def _place(self, arg_params, aux_params, arg_shapes, aux_names):
        """The bound arrays on the context: (args, aux)."""
        from . import ndarray as nd

        args = {}
        self._synthesized = set()
        for name, shape in arg_shapes:
            if name in self._input_shapes and name not in arg_params:
                args[name] = nd.zeros(shape, self._ctx, dtype=self._dtype)
            elif name in arg_params:
                if tuple(arg_params[name].shape) != tuple(shape):
                    raise MXNetError(
                        "param %s shape %s does not match inferred %s"
                        % (name, arg_params[name].shape, shape))
                # reshape() passes live device NDArrays: share, don't copy
                args[name] = on_ctx(arg_params[name], self._ctx)
            else:
                # reference MXPredCreate allocates missing args without
                # initializing them (c_predict_api.cc:190-195); we
                # zero-fill for determinism — loss labels in a saved
                # training symbol bind as zeros at inference
                args[name] = nd.zeros(shape, self._ctx, dtype=self._dtype)
                self._synthesized.add(name)
        aux = {}
        for name in aux_names:
            if name not in aux_params:
                raise MXNetError("missing auxiliary state %r" % name)
            aux[name] = on_ctx(aux_params[name], self._ctx)
        return args, aux

    @classmethod
    def from_checkpoint(cls, prefix, epoch, input_shapes, ctx=None,
                        dtype=np.float32):
        """Build a predictor straight from ``save_checkpoint`` files
        (``prefix-symbol.json`` + ``prefix-%04d.params`` — the file pair
        MXPredCreate consumes in the reference)."""
        return cls("%s-symbol.json" % prefix,
                   "%s-%04d.params" % (prefix, epoch),
                   input_shapes, ctx=ctx, dtype=dtype)

    # -- MXPredSetInput / MXPredForward / MXPredGetOutput parity ----------
    def set_input(self, name, value):
        if name not in self._input_shapes:
            raise MXNetError("unknown input %r" % name)
        self._exec.arg_dict[name][:] = value

    def forward(self, **inputs):
        for name, value in inputs.items():
            self.set_input(name, value)
        self._exec.forward(is_train=False)
        return self.get_outputs()

    def get_output(self, index):
        return self._exec.outputs[index]

    def get_outputs(self):
        return list(self._exec.outputs)

    def reshape(self, input_shapes):
        """Re-bind for new static input shapes (MXPredReshape,
        c_predict_api.cc:150-210).  Inputs not named keep their current
        shapes, matching the reference."""
        # synthesized (zero-filled) args are per-shape scratch, not model
        # params: drop them so the new bind re-synthesizes at its shapes.
        # Of the inputs only those bound to a given array go along.
        params = {("arg:%s" % k): v for k, v in self._exec.arg_dict.items()
                  if k not in self._synthesized
                  and (k not in self._input_shapes
                       or k in self._bound_inputs)}
        params.update({("aux:%s" % k): v
                       for k, v in self._exec.aux_dict.items()})
        merged = dict(self._input_shapes)
        merged.update({k: tuple(v) for k, v in input_shapes.items()})
        return Predictor(self._symbol, params, merged, self._ctx,
                         self._dtype)

    # -- AOT export (amalgamation equivalent) -----------------------------
    def export(self, path):
        """Serialize the jitted forward (params baked in) as a portable
        ``jax.export`` StableHLO artifact + output metadata."""
        import jax
        from jax import export as jexport

        plan = self._exec._plan
        # same stages as the live Executor forward (_get_fwd): mixed-
        # precision cast + ctx-group placement, so the exported program is
        # the program the Predictor serves
        cast = self._exec._cast_fn()
        placement = self._exec._placement
        params = {k: v._data for k, v in self._exec.arg_dict.items()
                  if k not in self._input_shapes}
        aux = {k: v._data for k, v in self._exec.aux_dict.items()}
        input_names = self._input_names

        def serve(*inputs):
            args = dict(params)
            args.update(dict(zip(input_names, inputs)))
            outs, _ = plan.run(cast(args), aux, None, False,
                               placement=placement)
            return tuple(outs)

        abstract = [jax.ShapeDtypeStruct(self._input_shapes[n], self._dtype)
                    for n in input_names]
        exported = jexport.export(
            jax.jit(self._exec._bound(serve)))(*abstract)
        blob = exported.serialize()
        meta = json.dumps({
            "inputs": [[n, list(self._input_shapes[n]), str(self._dtype)]
                       for n in input_names],
            "outputs": self._symbol.list_outputs()}).encode()
        with open(path, "wb") as f:
            f.write(_EXPORT_MAGIC)
            f.write(len(meta).to_bytes(8, "little"))
            f.write(meta)
            f.write(blob)
        return path


class _ExportedPredictor:
    """Reloaded AOT artifact: callable without the original model code."""

    def __init__(self, exported, meta):
        self._exported = exported
        self._meta = meta
        self.input_names = [m[0] for m in meta["inputs"]]
        self.output_names = meta["outputs"]

    def forward(self, **inputs):
        import jax.numpy as jnp

        vals = []
        for name, shape, dtype in self._meta["inputs"]:
            if name not in inputs:
                raise MXNetError("missing input %r" % name)
            vals.append(jnp.asarray(np.asarray(inputs[name], dtype=dtype)))
        return list(self._exported.call(*vals))


def load_exported(path):
    """Reload an artifact written by ``Predictor.export`` (the other half of
    the amalgamation story: deploy-time needs only this loader)."""
    from jax import export as jexport

    with open(path, "rb") as f:
        magic = f.read(len(_EXPORT_MAGIC))
        if magic != _EXPORT_MAGIC:
            raise MXNetError("%s is not an exported predictor artifact" % path)
        mlen = int.from_bytes(f.read(8), "little")
        meta = json.loads(f.read(mlen).decode())
        blob = f.read()
    exported = jexport.deserialize(blob)
    return _ExportedPredictor(exported, meta)
