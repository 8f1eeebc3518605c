"""Profiler (parity: /root/reference/python/mxnet/profiler.py:10-38 over
src/engine/profiler.{h,cc}).

The reference stamps per-op micros and dumps Chrome trace JSON
(profiler.h:88-109).  Here the heavy lifting is ``jax.profiler`` (XPlane →
TensorBoard/perfetto, the richer superset of a chrome trace); this module
keeps the reference's API shape and ALSO emits a minimal chrome-trace JSON
of python-level step events so ``dump_profile`` output remains loadable in
chrome://tracing.
"""
from __future__ import annotations

import json
import os
import time
import threading
from typing import List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .base import env, register_env

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "pause", "resume", "Frame", "trace_tid"]

_state = {"mode": "symbolic", "filename": "profile.json", "running": False,
          "events": [], "tnames": {}, "jax_trace_dir": None,
          "lock": threading.Lock()}

# Synthetic per-thread track ids.  ``threading.get_ident()`` values are
# recycled by the OS the moment a thread exits, so in a long trace a fresh
# worker (serving batcher, router pool, HTTP handler) can inherit a dead
# comm-engine worker's ident and silently rename its track in the merged
# thread_name metadata.  Handing every thread a monotonically increasing id
# on first use keeps exactly one track per actual thread for the lifetime
# of the process.
_tid_local = threading.local()
_tid_next = [1]


def trace_tid() -> int:
    """This thread's stable trace-track id (never reused across threads)."""
    tid = getattr(_tid_local, "tid", None)
    if tid is None:
        with _state["lock"]:
            tid = _tid_next[0]
            _tid_next[0] += 1
        _tid_local.tid = tid
    return tid

# external span sink installed by mxnet_tpu.telemetry.tracer: when set,
# Frame/record_event deliver each event (plus the recording thread's name)
# there too, so telemetry captures spans without the profiler run state
_sink = None


def _set_sink(fn):
    global _sink
    _sink = fn


def _snapshot_events():
    """Consistent copy of (events, thread-name map) for trace mergers."""
    with _state["lock"]:
        return list(_state["events"]), dict(_state["tnames"])


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Set profiler config (reference profiler.py:10): mode in
    {'symbolic', 'all'}; filename receives the chrome trace on dump."""
    if mode not in ("symbolic", "all"):
        raise ValueError("profiler mode must be 'symbolic' or 'all'")
    _state["mode"] = mode
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """Start/stop profiling (reference profiler.py:22).  'run' also starts a
    jax.profiler trace capturing device (TPU) activity."""
    if state not in ("run", "stop"):
        raise ValueError("profiler state must be 'run' or 'stop'")
    import jax

    if state == "run" and not _state["running"]:
        # mutate under the lock: a Frame closing on another thread must
        # never append into the buffer being replaced
        with _state["lock"]:
            _state["running"] = True
            _state["events"] = []
            _state["tnames"] = {}
        trace_dir = os.path.splitext(_state["filename"])[0] + "_xplane"
        try:
            jax.profiler.start_trace(trace_dir)
            _state["jax_trace_dir"] = trace_dir
        except Exception:
            _state["jax_trace_dir"] = None
    elif state == "stop" and _state["running"]:
        with _state["lock"]:
            _state["running"] = False
        if _state["jax_trace_dir"]:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


def pause():
    with _state["lock"]:
        _state["running"] = False


def resume():
    with _state["lock"]:
        _state["running"] = True


class Frame:
    """Context manager recording one named span: THE span primitive.

    Two sinks, one call site.  Whenever a ``jax.profiler`` session runs (the
    benchmark's ``--trace 1``, ``profiler_set_state("run")``, an operator's
    ``jax.profiler.start_trace``) the span is a host event of that
    session's ``.xplane.pb``, on the clock of the device operations, with
    ``args`` as its stats and the enclosing span of its thread as its
    parent.  While the legacy profiler runs or the telemetry tracer is on,
    it is also an event of the Chrome trace (the python-level analogue of
    OprExecStat, profiler.h:20-42).  With neither, entering and leaving
    read no clock and take no lock: what is left is the annotation's own
    check that no session is active.

    ``args`` is a flat dict of str/int/float.  A value never holds ``,`` or
    ``#``: the trace format cuts a stat there.  The profiler session gets
    the args as they are on entry; the Chrome trace reads them on exit, so
    a caller may attach fields while the span is open."""

    __slots__ = ("name", "category", "args", "_t0", "_ann")

    def __init__(self, name, category="python", args=None):
        self.name = name
        self.category = category
        self.args = args

    def set(self, **fields):
        """Attach fields known only once the span is open (a count of what
        it did), to both sinks."""
        self.args = dict(self.args or {}, **fields)
        self._ann.set_metadata(**fields)

    def __enter__(self):
        self._t0 = time.perf_counter_ns() // 1000 \
            if _state["running"] or _sink is not None else None
        self._ann = _TraceAnnotation(self.name, **(self.args or {}))
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._t0 is None:
            return
        sink = _sink
        if _state["running"] or sink is not None:
            t1 = time.perf_counter_ns() // 1000
            # per-thread id: spans from worker threads (comm engine,
            # serving batcher, kvstore handlers) land on their own tracks
            tid = trace_tid()
            ev = {"name": self.name, "cat": self.category, "ph": "X",
                  "ts": self._t0, "dur": t1 - self._t0, "pid": 0, "tid": tid}
            if self.args:
                ev["args"] = dict(self.args)
            tname = threading.current_thread().name
            if _state["running"]:
                with _state["lock"]:
                    _state["events"].append(ev)
                    _state["tnames"][tid] = tname
            if sink is not None:
                sink(ev, tname)


def record_event(name, t0_us, dur_us, category="op"):
    sink = _sink
    if _state["running"] or sink is not None:
        tid = trace_tid()
        ev = {"name": name, "cat": category, "ph": "X", "ts": t0_us,
              "dur": dur_us, "pid": 0, "tid": tid}
        tname = threading.current_thread().name
        if _state["running"]:
            with _state["lock"]:
                _state["events"].append(ev)
                _state["tnames"][tid] = tname
        if sink is not None:
            sink(ev, tname)


def dump_profile():
    """Write the chrome trace file (reference profiler.py:34 → DumpProfile,
    profiler.h:88).  Safe to call mid-run: pending events are flushed
    under ``_state["lock"]`` whether or not ``profiler_set_state("stop")``
    ever ran."""
    with _state["lock"]:
        payload = {"traceEvents": list(_state["events"]),
                   "displayTimeUnit": "ms"}
    with open(_state["filename"], "w") as f:
        json.dump(payload, f)
    return _state["filename"]


register_env("MXNET_PROFILER_AUTOSTART", 0, int, "Start profiler at import.")
if env("MXNET_PROFILER_AUTOSTART", 0, int):
    profiler_set_state("run")
