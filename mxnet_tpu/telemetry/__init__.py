"""mxnet_tpu.telemetry — unified observability core.

One shared, thread-safe home for the four instruments that grew up
separately (profiler Frame spans, serving Prometheus counters,
kv.comm_stats, perf_probe's XLA cost analysis):

* a metrics :class:`Registry` (counters / gauges / exponential-bucket
  histograms) with a Prometheus text renderer and a JSONL structured-event
  log (:func:`log_event`);
* a span tracer whose spans from ANY thread (Module step, comm-engine
  workers, kvstore-server RPC handlers, the serving batcher) merge with
  the legacy ``profiler.py`` events into ONE Chrome-trace timeline with
  per-thread tracks (:func:`dump_trace`);
* a :class:`StepMonitor` recording per-step wall time, data-wait,
  throughput, device-memory watermarks and achieved model-MFU (XLA cost
  analysis, once per compiled executable);
* a recompile detector warning — with the offending shape diff — when the
  fused step recompiles after warmup.

Cost model: everything is gated by ``MXNET_TELEMETRY``.  Off (the
default), every hook in the hot path is a single module-global bool read —
no locks, no allocations, mirroring ``faults.fire``'s plan-is-None idiom.
Activate with ``MXNET_TELEMETRY=1`` in the environment or
:func:`enable` in-process.
"""
from __future__ import annotations

import os
import sys
import threading
import weakref
from typing import Optional

from .. import profiler as _prof
from ..base import env, register_env
from . import distributed, flight_recorder, tracer
from .distributed import (FleetAggregator, proc_identity, proc_label,
                          start_pusher, stop_pusher)
from .registry import (Counter, EventLog, Gauge, Histogram, LabeledCounter,
                       Registry)
from .step_monitor import (RecompileWarning, StepMonitor, fused_cost_analysis,
                           lower_and_analyze, peak_flops)

__all__ = [
    "enabled", "enable", "disable", "dump_dir", "registry", "counter",
    "gauge",
    "histogram", "labeled_counter", "log_event", "events", "events_of",
    "event_log",
    "span", "dump_trace", "merged_trace", "validate_trace",
    "render_prometheus", "register_collector", "summary", "startup_report",
    "current_step_monitor", "Registry", "Counter", "Gauge", "Histogram",
    "LabeledCounter", "EventLog", "StepMonitor", "RecompileWarning",
    "peak_flops", "fused_cost_analysis", "lower_and_analyze",
    "distributed", "flight_recorder", "FleetAggregator", "proc_identity",
    "proc_label", "start_pusher", "stop_pusher",
]

register_env("MXNET_TELEMETRY", 0, int,
             "Master switch for the telemetry subsystem (metrics registry, "
             "span capture, StepMonitor, recompile detector). Off: every "
             "hook is one global bool read.")
register_env("MXNET_TELEMETRY_TRACE", 1, int,
             "With telemetry on, capture Frame spans from all threads into "
             "the merged Chrome trace even when the legacy profiler is "
             "stopped (0 keeps only the profiler-run capture path).")
register_env("MXNET_TELEMETRY_TRACE_BUFFER", 65536, int,
             "Max spans kept in the telemetry trace ring buffer.")
register_env("MXNET_TELEMETRY_DIR", "", str,
             "Directory for the JSONL structured-event log "
             "(events.jsonl); empty keeps events in memory only.")
register_env("MXNET_TELEMETRY_DUMP_DIR", "", str,
             "Directory for telemetry artifacts (exit-time trace-*.json, "
             "flight-recorder postmortems when their own dirs are unset); "
             "empty uses <tmpdir>/mxnet_tpu-artifacts — never the cwd.")
register_env("MXNET_TELEMETRY_MFU", 1, int,
             "Run XLA cost analysis once per compiled fused step to "
             "derive achieved MFU (0 skips the per-compile analysis).")
register_env("MXNET_TELEMETRY_PEAK_FLOPS", 0.0, float,
             "MFU denominator in FLOP/s; 0 looks the attached device's "
             "kind up in hlo_analysis.DEVICE_PEAKS (no entry: no MFU).")

# the single hot-path gate: plain module-global read, no locks
_ENABLED = False
_lock = threading.Lock()
_registry: Optional[Registry] = None
_event_log: Optional[EventLog] = None
_collectors = []  # weakrefs to objects exposing render_prometheus()
_current_monitor = None  # weakref to the most recent StepMonitor

span = tracer.span
merged_trace = tracer.merged_trace
validate_trace = tracer.validate_trace
dump_trace = tracer.dump_trace


def enabled() -> bool:
    return _ENABLED


def dump_dir() -> str:
    """Where telemetry artifacts (traces, postmortems without an explicit
    dir) land: ``MXNET_TELEMETRY_DUMP_DIR``, defaulting to a per-tmpdir
    artifacts directory.  Deliberately NEVER the cwd — test and bench
    runs must not litter the working tree."""
    d = env("MXNET_TELEMETRY_DUMP_DIR", "", str)
    if not d:
        import tempfile

        d = os.path.join(tempfile.gettempdir(), "mxnet_tpu-artifacts")
    return d


def registry() -> Registry:
    """The process-global metrics registry (created on first use)."""
    global _registry
    if _registry is None:
        with _lock:
            if _registry is None:
                _registry = Registry()
    return _registry


def counter(name, doc="") -> Counter:
    return registry().counter(name, doc)


def gauge(name, doc="", fn=None) -> Gauge:
    return registry().gauge(name, doc, fn)


def histogram(name, doc="", start=0.5, factor=2.0, count=16) -> Histogram:
    return registry().histogram(name, doc, start, factor, count)


def labeled_counter(name, label, doc="") -> LabeledCounter:
    return registry().labeled_counter(name, label, doc)


def event_log() -> EventLog:
    global _event_log
    if _event_log is None:
        with _lock:
            if _event_log is None:
                d = env("MXNET_TELEMETRY_DIR", "", str)
                path = None
                if d:
                    os.makedirs(d, exist_ok=True)
                    path = os.path.join(d, "events.jsonl")
                _event_log = EventLog(path)
    return _event_log


def log_event(kind, **fields):
    """Append one structured event (no-op while telemetry is off)."""
    if not _ENABLED:
        return None
    return event_log().emit(kind, **fields)


def events(n=None):
    return event_log().tail(n) if _event_log is not None else []


def events_of(kind, n=None):
    """The tail of the structured-event log filtered to one ``kind`` —
    what chaos scenarios and tests assert platform transitions against
    (e.g. ``platform_domain_health``, ``platform_brownout``)."""
    out = [e for e in events() if e.get("kind") == kind]
    return out if n is None else out[-int(n):]


_atexit_hooked = False


def _atexit_flush():
    """Process-exit flush for cluster observability: land one final
    metrics push on the fleet aggregator (short-lived workers would
    otherwise miss the last interval) and, with MXNET_TELEMETRY_DIR set,
    dump this process's trace to ``trace-<role><rank>.json`` so
    ``tools/trace_merge.py`` can stitch the fleet timeline."""
    if not _ENABLED:
        return
    distributed.push_once()
    # trace routing: an explicit MXNET_TELEMETRY_DIR keeps its contract
    # (trace_merge stitches from there); otherwise traces go to the
    # artifacts dump dir — never the cwd
    d = env("MXNET_TELEMETRY_DIR", "", str) or dump_dir()
    if tracer.active():
        try:
            os.makedirs(d, exist_ok=True)
            dump_trace(os.path.join(
                d, "trace-%s.json" % distributed.proc_label()))
        except Exception:
            pass


def enable(trace: Optional[bool] = None) -> None:
    """Turn telemetry on in-process (the env-var path calls this at
    import).  ``trace`` overrides MXNET_TELEMETRY_TRACE."""
    global _ENABLED, _atexit_hooked
    with _lock:
        _ENABLED = True
    if trace is None:
        trace = bool(env("MXNET_TELEMETRY_TRACE", 1, int))
    if trace:
        tracer.start(env("MXNET_TELEMETRY_TRACE_BUFFER", 65536, int))
    # cluster-wide pieces: metrics pusher (only when an aggregator
    # address is configured), crash flight recorder, exit-time flush
    distributed.start_pusher()
    flight_recorder.install_excepthooks()
    if not _atexit_hooked:
        import atexit

        atexit.register(_atexit_flush)
        _atexit_hooked = True


def disable() -> None:
    global _ENABLED, _event_log
    with _lock:
        _ENABLED = False
    tracer.stop()
    distributed.stop_pusher()
    flight_recorder.uninstall_excepthooks()
    if _event_log is not None:
        _event_log.close()
        _event_log = None


def _reset_for_tests() -> None:
    """Drop all global state (registry contents, collectors, monitors)."""
    global _registry, _event_log, _current_monitor
    disable()
    with _lock:
        _registry = None
        _event_log = None
        _current_monitor = None
        del _collectors[:]
    # instrumented modules cache registry handles lazily; stale handles
    # would keep writing to the dropped registry
    for modname, attr in (("mxnet_tpu.io", "_PREFETCH_TELEM"),
                          ("mxnet_tpu.kvstore_server", "_TELEM"),
                          ("mxnet_tpu.compile_cache", "_instruments")):
        m = sys.modules.get(modname)
        if m is not None:
            setattr(m, attr, None)


def _set_current_monitor(mon) -> None:
    global _current_monitor
    _current_monitor = weakref.ref(mon)


def current_step_monitor() -> Optional[StepMonitor]:
    ref = _current_monitor
    return ref() if ref is not None else None


def register_collector(obj) -> None:
    """Include ``obj.render_prometheus()`` in the global metrics render —
    how per-object registries (serving servers, async kvstores) surface
    their series without sharing counters across instances.  Held by
    weakref: dead collectors drop out on the next render."""
    with _lock:
        _collectors.append(weakref.ref(obj))


def render_prometheus() -> str:
    """Prometheus text exposition: global registry + live collectors."""
    parts = [registry().render_prometheus()]
    with _lock:
        refs = list(_collectors)
    alive = []
    for ref in refs:
        obj = ref()
        if obj is None:
            continue
        alive.append(ref)
        try:
            parts.append(obj.render_prometheus())
        except Exception:
            pass
    with _lock:
        _collectors[:] = alive
    return "".join(p if p.endswith("\n") or not p else p + "\n"
                   for p in parts if p)


def startup_report() -> dict:
    """What this process's start was spent on, for an operator: the
    start-up record (``profiler.startup()``) by span name, the wall time
    the spans cover together, and each program's first call beside the
    compile ledger's rows for it (``compile_cache.ledger()``)."""
    # no import statement: a thread may ask while the package is still
    # being imported (the kvstore server's bootstrap), and compile_cache
    # imports this module
    compile_cache = sys.modules["mxnet_tpu.compile_cache"]
    record = _prof.startup()
    spans = {}
    for s in record["spans"]:
        agg = spans.setdefault(s["name"], {"count": 0, "seconds": 0.0})
        agg["count"] += 1
        agg["seconds"] = round(agg["seconds"] + s["end"] - s["start"], 4)
    wall, edge = 0.0, float("-inf")
    for start, end in sorted((s["start"], s["end"])
                             for s in record["spans"]):
        wall += max(0.0, end - max(start, edge))
        edge = max(edge, end)
    programs = {s["id"]: dict(s["args"], seconds=round(
        s["end"] - s["start"], 4)) for s in record["spans"]
        if s["name"] == "start:program"}
    in_service = []
    for row in compile_cache.ledger():
        first = programs.get(row["span_id"])
        if first is not None:
            first[row["phase"] + "_s"] = round(
                first.get(row["phase"] + "_s", 0.0) + row["seconds"], 4)
        elif row["span"] is not None:
            in_service.append(dict(row, seconds=round(row["seconds"], 4)))
    return {"spans": spans, "wall_s": round(wall, 4),
            "dropped": record["dropped"],
            "programs": list(programs.values()),
            # compiles charged to a step: the program changed in service
            "recompiles": in_service,
            "jax_cache": compile_cache.stats()["jax"]}


def summary() -> dict:
    """Compact run summary for embedding in a run's record: non-zero
    counters/gauges from the global registry plus the active StepMonitor
    report, and once the process has built anything what its start was
    spent on (:func:`startup_report`)."""
    out = {}
    if _registry is not None:
        flat = {}
        for name, val in _registry.snapshot().items():
            if isinstance(val, dict):
                n = val.get("count")
                if n:
                    flat[name] = {"count": n,
                                  "sum": round(val.get("sum", 0.0), 3)}
            elif val:
                flat[name] = round(val, 3) if isinstance(val, float) else val
        if flat:
            out["counters"] = flat
    mon = current_step_monitor()
    if mon is not None:
        out["step"] = mon.report()
    if _event_log is not None and _event_log.path:
        out["events_jsonl"] = _event_log.path
    report = startup_report()
    if set(report["spans"]) - {"start:import"}:  # something was built
        out["startup"] = report
    return out


# env activation at import: a process launched with MXNET_TELEMETRY=1 is
# instrumented from its very first step
if env("MXNET_TELEMETRY", 0, int):
    enable()
