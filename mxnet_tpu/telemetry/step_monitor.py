"""StepMonitor — per-step wall time, data-wait, throughput, memory
watermarks, achieved model-MFU, and a recompile detector.

The MFU path is tools/perf_probe.py's introspection hook promoted into the
framework: the fused-step executor records ``_fused_introspect = (fn,
abstract_args)`` on every compile miss, and :func:`lower_and_analyze`
lowers that exact program and reads XLA's own cost analysis — so the flop
count is the compiled program's, not a hand-derived model ("A Learned
Performance Model for TPUs", arxiv 2008.01040, argues this is the number
that matters).  Cost analysis runs once per compiled executable, never on
the per-step path.

The recompile detector fingerprints the batch signature (name, shape,
dtype of every input) feeding the step.  jax.jit retraces silently when a
shape changes — the Python-level jit cache key stays put — so the first
signature per monitor is warmup and any NEW signature after it warns once
with the offending shape diff and bumps ``mxtpu_recompiles_total``.
"""
from __future__ import annotations

import time
import warnings
from typing import Optional

from ..base import env
from ..hlo_analysis import (collective_counts, lower_and_analyze,
                            peak_flops)

__all__ = ["StepMonitor", "RecompileWarning", "peak_flops",
           "lower_and_analyze", "fused_cost_analysis"]


class RecompileWarning(UserWarning):
    """The fused train step recompiled after warmup (shape change)."""


def _fused_analysis(executor):
    """(cost analysis, collective counts) of an executor's last-compiled
    fused step; either may be None, and the counts are None for a step over
    one device.

    When the persistent compile cache primed the step it already carries
    both (read once from the fresh executable on a miss, or from the
    cache-entry metadata on a hit) — use those and skip the
    re-lower+re-compile entirely, which is what keeps a warm-cache cold
    start at zero compiler invocations even with telemetry on.  Otherwise
    the one re-compile serves both."""
    info = getattr(executor, "_fused_cost_info", None)
    if info and info.get("flops"):
        return info, getattr(executor, "_fused_collectives", None)
    fn, abstract = getattr(executor, "_fused_introspect", (None, None))
    compiled, info = lower_and_analyze(fn, abstract)
    counts = None
    if compiled is not None and \
            len(compiled.runtime_executable().local_devices()) > 1:
        counts = collective_counts(compiled.as_text())
    return info, counts


def fused_cost_analysis(executor):
    """Cost analysis of an executor's last-compiled fused step, or None
    (:func:`_fused_analysis`)."""
    return _fused_analysis(executor)[0]


def _batch_signature(data_batch):
    """Hashable fingerprint of the arrays feeding one step."""
    sig = []
    for kind, arrs in (("data", data_batch.data or []),
                       ("label", getattr(data_batch, "label", None) or [])):
        for i, a in enumerate(arrs):
            sig.append(("%s%d" % (kind, i), tuple(a.shape), str(a.dtype)))
    return tuple(sig)


def _sig_diff(old, new):
    """Human-readable shape diff between two batch signatures."""
    old_d = {name: (shape, dt) for name, shape, dt in old}
    new_d = {name: (shape, dt) for name, shape, dt in new}
    parts = []
    for name in sorted(set(old_d) | set(new_d)):
        o, n = old_d.get(name), new_d.get(name)
        if o == n:
            continue
        if o is None:
            parts.append("%s: (new)->%s %s" % (name, n[0], n[1]))
        elif n is None:
            parts.append("%s: %s %s->(gone)" % (name, o[0], o[1]))
        else:
            parts.append("%s: %s->%s" % (
                name, o[0], n[0]) + ("" if o[1] == n[1]
                                     else " [%s->%s]" % (o[1], n[1])))
    return ", ".join(parts)


class StepMonitor:
    """Per-Module training-step telemetry.  Created lazily by Module when
    ``MXNET_TELEMETRY`` is on; the telemetry-off step path never touches
    this class."""

    def __init__(self, telemetry_mod):
        self._tm = telemetry_mod
        reg = telemetry_mod.registry()
        self.c_steps = reg.counter("mxtpu_steps_total",
                                   "Training steps completed.")
        self.c_samples = reg.counter("mxtpu_samples_total",
                                     "Training samples consumed.")
        self.c_data_wait_ms = reg.counter(
            "mxtpu_data_wait_ms_total",
            "Milliseconds the train loop blocked waiting for input batches.")
        self.h_step_ms = reg.histogram("mxtpu_step_time_ms",
                                       "Per-step wall time (ms).",
                                       start=0.25, factor=2.0, count=20)
        self.c_compiles = reg.counter("mxtpu_fused_compiles_total",
                                      "Fused-step executable builds.")
        self.c_recompiles = reg.counter(
            "mxtpu_recompiles_total",
            "Post-warmup step recompiles (shape changes).")
        self.g_last_ms = reg.gauge("mxtpu_step_last_ms",
                                   "Most recent step wall time (ms).")
        self.g_mfu = reg.gauge("mxtpu_step_mfu",
                               "Achieved model FLOP utilization [0,1].")
        self.g_mem_peak = reg.gauge(
            "mxtpu_device_peak_bytes",
            "Device memory high-watermark (bytes), when the backend "
            "reports memory_stats.")
        self._t0 = None
        self._first_t0 = None
        self._last_end = None
        self._steps = 0
        self._samples = 0
        self._step_ms_total = 0.0
        self._data_wait_ms = 0.0
        self._flops_per_step = None
        self._mem_supported = True
        self._sigs = None  # recompile detector state: {sig}, last sig
        self._last_sig = None
        self._mesh_axes = None  # {axis_name: size} when training on a mesh
        telemetry_mod._set_current_monitor(self)

    def note_mesh(self, mesh):
        """Record the device-mesh layout the module trains on (surfaces in
        ``telemetry.summary()`` / BENCH records, next to the byte gauges,
        so a run's parallel layout is part of its record)."""
        if mesh is None:
            self._mesh_axes = None
            return
        self._mesh_axes = {str(name): int(mesh.shape[name])
                           for name in mesh.axis_names}
        self._tm.log_event("mesh", axes=self._mesh_axes)

    # -- per-step hooks (Module.forward_backward / update / fit) ----------
    def note_data_wait(self, seconds):
        ms = seconds * 1e3
        self._data_wait_ms += ms
        self.c_data_wait_ms.inc(ms)

    def note_batch(self, data_batch):
        """Recompile detection: fingerprint this step's input signature."""
        sig = _batch_signature(data_batch)
        if self._sigs is None:  # warmup: the first signature is expected
            self._sigs = {sig}
            self._last_sig = sig
            return
        if sig in self._sigs:
            self._last_sig = sig
            return
        diff = _sig_diff(self._last_sig, sig)
        self._sigs.add(sig)
        self._last_sig = sig
        self.c_recompiles.inc()
        self._tm.log_event("recompile", diff=diff, step=self._steps)
        warnings.warn(
            "training step input shapes changed after warmup — the fused "
            "step recompiles (%s)" % diff, RecompileWarning, stacklevel=3)

    def step_begin(self):
        self._t0 = time.perf_counter()
        if self._first_t0 is None:
            self._first_t0 = self._t0

    def step_end(self, batch_size):
        now = time.perf_counter()
        dur_ms = (now - self._t0) * 1e3 if self._t0 is not None else 0.0
        self._t0 = None
        self._last_end = now
        self._steps += 1
        self._samples += int(batch_size or 0)
        self._step_ms_total += dur_ms
        self.c_steps.inc()
        if batch_size:
            self.c_samples.inc(int(batch_size))
        self.h_step_ms.observe(dur_ms)
        self.g_last_ms.set(dur_ms)
        if self._steps % 10 == 1:
            self._sample_memory()
        self._tm.log_event("step", n=self._steps, dur_ms=round(dur_ms, 3),
                           data_wait_ms=round(self._data_wait_ms, 3))

    def note_compile(self, executor):
        """Compile-miss path: one XLA cost analysis per new executable and,
        for a step over a mesh, its collectives and how many of them the
        compiler runs asynchronously (whether
        ``sharding.collective_compiler_options`` engaged)."""
        self.c_compiles.inc()
        if not env("MXNET_TELEMETRY_MFU", 1, int):
            return
        try:
            info, collectives = _fused_analysis(executor)
        except Exception:
            info = collectives = None
        if collectives:
            reg = self._tm.registry()
            reg.gauge("mxtpu_fused_step_collectives",
                      "Collectives in the compiled fused step under the "
                      "mesh").set(collectives["collectives"])
            reg.gauge("mxtpu_fused_step_collectives_async",
                      "Of those, the ones the compiler runs asynchronously, "
                      "under other work").set(collectives["asynchronous"])
        if info and info.get("flops"):
            self._flops_per_step = float(info["flops"])
            self._tm.log_event("compile", flops=self._flops_per_step,
                               bytes_accessed=info.get("bytes_accessed"))

    # -- derived ----------------------------------------------------------
    def _sample_memory(self):
        if not self._mem_supported:
            return
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
        except Exception:
            stats = None
        if not stats:
            self._mem_supported = False
            return
        peak = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
        if peak:
            self.g_mem_peak.set_max(int(peak))

    @property
    def data_wait_ms_total(self):
        return self._data_wait_ms

    @property
    def flops_per_step(self):
        return self._flops_per_step

    def avg_step_s(self) -> Optional[float]:
        """Steady-state seconds per step: wall clock over all steps (the
        same quantity perf_probe times), not just host dispatch."""
        if self._steps < 1 or self._first_t0 is None:
            return None
        wall = self._last_end - self._first_t0
        if wall <= 0:
            return None
        return wall / self._steps

    def mfu(self) -> Optional[float]:
        step_s = self.avg_step_s()
        if not step_s or not self._flops_per_step:
            return None
        peak = peak_flops()
        if not peak:
            return None  # device kind not in hlo_analysis.DEVICE_PEAKS
        v = self._flops_per_step / step_s / peak
        self.g_mfu.set(v)
        return v

    def report(self) -> dict:
        step_s = self.avg_step_s()
        rep = {
            "steps": self._steps,
            "avg_step_ms": round(step_s * 1e3, 3) if step_s else None,
            "dispatch_ms_avg": round(self._step_ms_total / self._steps, 3)
            if self._steps else None,
            "data_wait_ms_total": round(self._data_wait_ms, 3),
            "data_wait_frac": round(
                self._data_wait_ms / (step_s * 1e3 * self._steps), 4)
            if step_s else None,
            "samples_per_sec": round(self._samples / (step_s * self._steps),
                                     1) if step_s and self._samples else None,
            "flops_per_step": self._flops_per_step,
            "mfu": self.mfu(),
            "recompiles": self.c_recompiles.value,
            "device_peak_bytes": self.g_mem_peak.value or None,
        }
        if self._mesh_axes:
            rep["mesh"] = dict(self._mesh_axes)
        mfu = rep["mfu"]
        if mfu is not None:
            rep["mfu"] = round(mfu, 4)
        # cluster health: when a colocated kvstore server flagged slow
        # ranks this process's summary names them (per-rank counts)
        stragglers = self._straggler_counts()
        if stragglers:
            rep["stragglers"] = stragglers
        return rep

    @staticmethod
    def _straggler_counts():
        import mxnet_tpu.telemetry as _tm

        reg = _tm._registry  # only if the global registry already exists
        if reg is None:
            return None
        c = reg.get("mxtpu_kvsrv_stragglers_total")
        if c is None or not getattr(c, "value", 0):
            return None
        return {str(k): v for k, v in c.snapshot().items()}
