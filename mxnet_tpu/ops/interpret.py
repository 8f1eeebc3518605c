"""Where a Pallas kernel runs: compiled or interpreted, whole or per shard
— decided by where its operands live, never by the process's default
backend.

A process on a machine with a chip still owns CPU devices (``mx.cpu()``
graphs, the explicit-CPU comparison in ``chip_smoke.py``), so
``jax.default_backend()`` says nothing about the program being traced.
The platform is read, in order, from

1. the call's explicit ``interpret=`` argument;
2. concrete operands (``x.devices()``) — eager ``mx.nd`` calls;
3. the scope an :class:`~mxnet_tpu.executor.Executor` enters around every
   program it traces (:func:`bound_to` the platform of its context) —
   operands are tracers there;
4. the default device, where a bare ``jax.jit`` places its work.

A kernel on a ``tpu`` device is compiled by Mosaic; anywhere else it runs
in Pallas interpret mode and says so once (``logging.info``).

An Executor also says, around a program that carries planes from call to
call, whether it donates them (:func:`carrying`, read by
:func:`carried_in_place`): a kernel that takes such a plane where it lies may
then hold it to the device's main memory, which the TPU compiler refuses
(aborts) for a plane it has to copy first.

The same scope carries the device mesh of a data-parallel executor: GSPMD
cannot partition a Mosaic kernel ("Mosaic kernels cannot be automatically
partitioned"), so a kernel traced under a mesh runs per batch shard through
:func:`over_batch_shards`.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import threading

__all__ = ["bound_to", "bind", "platform_of", "interpret_for",
           "over_batch_shards", "carrying", "carried_in_place"]

_scope = threading.local()
_said = set()


@contextlib.contextmanager
def bound_to(platform: str, mesh=None, batch_axis=None):
    """Kernels traced inside run on ``platform`` ("tpu", "cpu", ...) and,
    with ``mesh``, once per shard of ``batch_axis``."""
    old = getattr(_scope, "bound", None)
    _scope.bound = (platform, mesh, batch_axis)
    try:
        yield
    finally:
        _scope.bound = old


def bind(fn, platform: str, mesh=None, batch_axis=None):
    """``fn`` wrapped so its body — traced lazily, at the first call of
    the jit around it — sees :func:`bound_to` these."""
    @functools.wraps(fn)
    def bound(*args, **kwargs):
        with bound_to(platform, mesh, batch_axis):
            return fn(*args, **kwargs)

    return bound


def carrying(fn, donated: bool):
    """``fn``, a program that carries planes, wrapped so its body — traced
    lazily — can ask :func:`carried_in_place` whether they are donated."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        old = getattr(_scope, "donated", False)
        _scope.donated = bool(donated)
        try:
            return fn(*args, **kwargs)
        finally:
            _scope.donated = old

    return traced


def carried_in_place() -> bool:
    """True inside a program whose carried planes are donated: an output
    aliased to one is the plane's own buffer, never a copy."""
    return getattr(_scope, "donated", False)


def over_batch_shards(fn):
    """``fn`` (operands and results all batch-major) as a ``shard_map``
    over the bound mesh's batch axis; ``fn`` itself where no mesh is
    bound.  Interpret-mode Pallas trips the varying-axis checker, so it
    is on for compiled kernels only (as in parallel/ring.py)."""
    _, mesh, axis = getattr(_scope, "bound", None) or (None, None, None)
    if mesh is None:
        return fn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return shard_map(fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                     check_vma=mesh.devices.flat[0].platform == "tpu")


def platform_of(*operands) -> str:
    import jax

    for x in operands:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return next(iter(x.devices())).platform
    scoped = getattr(_scope, "bound", None)
    if scoped is not None:
        return scoped[0]
    default = jax.config.jax_default_device
    if default is not None and not isinstance(default, str):
        return default.platform
    return default or jax.default_backend()


def interpret_for(kernel: str, operands=(), interpret=None) -> bool:
    """The ``interpret=`` a ``pallas_call`` of ``kernel`` should get."""
    if interpret is None:
        platform = platform_of(*operands)
        interpret = platform != "tpu"
    else:
        platform = "explicit interpret=%s" % bool(interpret)
    if interpret and kernel not in _said:
        _said.add(kernel)
        logging.info("%s: operands on %s, not a tpu device — running the "
                     "Pallas kernel in interpret mode", kernel, platform)
    return bool(interpret)
