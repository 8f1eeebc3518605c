"""Device context — maps the reference's Context (include/mxnet/base.h:124-196)
onto JAX devices.

Device types: ``cpu``, ``tpu``, and ``gpu`` as an alias of ``tpu`` so reference
training scripts (``--gpus 0,1``) run unchanged.  ``cpu_pinned`` maps to host
memory.  A Context is hashable, usable as a ``with``-scope (current-context
stack, parity with python/mxnet/context.py), and resolves lazily to a concrete
``jax.Device`` so contexts can be constructed before backends initialise.

Device rule (docs/how_to/deviations.md "Default context"): ``tpu(i)`` /
``gpu(i)`` is the i-th attached accelerator or an error — never a host
device, never a wrapped index — unless the platform was explicitly forced
to the host (``JAX_PLATFORMS=cpu``, as the tests do), where the host's
devices stand in for chips.  The default context is ``tpu(0)`` when an
accelerator is attached and ``cpu(0)`` otherwise.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from . import profiler as _prof
from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError("unknown device type %s" % device_type)
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return self.__repr__()

    # -- JAX resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        ``cpu``/``cpu_pinned`` resolve to a host device (ids wrap: the
        reference gives cpu ids no meaning).  ``gpu``/``tpu`` resolve to
        the ``device_id``-th accelerator or raise — see the module
        docstring for the one exception (platform forced to the host).
        """
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = _local_devices(backend="cpu")
            return devs[self.device_id % len(devs)]
        devs = _accelerators()
        if not devs:
            raise MXNetError(
                "%s: no accelerator is attached (jax.local_devices() = %s). "
                "Use mx.cpu(), or set JAX_PLATFORMS=cpu to let host devices "
                "stand in for chips." % (self, _local_devices()))
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s: device_id out of range, %d %s device(s) attached"
                % (self, len(devs), devs[0].platform))
        return devs[self.device_id]

    # -- with-scope --------------------------------------------------------
    def __enter__(self):
        # None = no scope active on this thread: the process default
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """Parity no-op: XLA owns the device allocator (reference:
        src/storage/pooled_storage_manager.h ReleaseAll)."""

    @classmethod
    def default_ctx(cls) -> "Context":
        """Innermost ``with`` scope of this thread, else the process
        default: the first accelerator when one is attached, the host
        otherwise."""
        scoped = getattr(cls._default_ctx, "value", None)
        if scoped is not None:
            return scoped
        if any(d.platform != "cpu" for d in _local_devices()):
            return Context("tpu", 0)
        return Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of the accelerator device so `--gpus` flags keep working on TPU."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    return Context.default_ctx()


def num_gpus() -> int:
    return num_tpus()


def num_tpus() -> int:
    """Attached accelerators (same rule as ``tpu(i)``: host devices count
    only where the platform was forced to the host)."""
    return len(_accelerators())


_backend_met = False


def _local_devices(backend=None) -> List:
    """``jax.local_devices``; this module's first contact with the
    devices (where nobody reached them before, the backend's start) is the
    ``start:backend`` span of the start-up record."""
    global _backend_met
    import jax

    if _backend_met:
        return jax.local_devices(backend=backend)
    _backend_met = True
    with _prof.Frame("start:backend", "startup") as span:
        devs = jax.local_devices(backend=backend)
        span.set(platform=devs[0].platform, devices=len(devs))
    return devs


def _accelerators() -> List:
    devs = _local_devices()
    chips = [d for d in devs if d.platform != "cpu"]
    if chips:
        return chips
    return devs if _forced_to_host() else []


def _forced_to_host() -> bool:
    """The platform was explicitly forced to the host (``JAX_PLATFORMS=cpu``
    or the equivalent config): a choice, not a fallback."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0].strip().lower() \
        == "cpu"
