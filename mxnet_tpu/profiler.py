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

import functools
import json
import os
import time
import threading
from typing import List, Optional

import jax
from jax.profiler import TraceAnnotation as _TraceAnnotation

from .base import env, register_env

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "pause", "resume", "Frame", "trace_tid", "framed", "first_call",
           "startup", "open_frames", "leaves_bytes"]

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

# The start-up record: every span named ``start:*`` (construction and
# warm-up sites, never a step) stamps its two ends here whether or not
# anything listens, because a process's first minute is over before an
# operator can start a profiler session.  Bounded: a process that builds
# servers all its life (hot swaps) keeps the first STARTUP_SPANS.
STARTUP_SPANS = 512
_startup: List[dict] = []
_startup_dropped = [0]
# the open Frames of each thread, innermost last: a start-up span's parent,
# and the span a compile is charged to (compile_cache's ledger)
_open_local = threading.local()


def open_frames() -> List["Frame"]:
    """This thread's open Frames, outermost first (the live list)."""
    frames = getattr(_open_local, "frames", None)
    if frames is None:
        frames = _open_local.frames = []
    return frames


def _startup_open(name, args, start=None):
    """A new record of the start-up ledger (None once it is full); its
    ``end`` is filled in when the span closes."""
    parent = next((f._rec["id"] for f in reversed(open_frames())
                   if f._rec is not None), None)
    with _state["lock"]:
        if len(_startup) >= STARTUP_SPANS:
            _startup_dropped[0] += 1
            return None
        rec = {"id": len(_startup), "name": name,
               "start": time.perf_counter() if start is None else start,
               "end": None, "thread": threading.current_thread().name,
               "parent": parent, "args": dict(args or {})}
        _startup.append(rec)
    return rec


def stamp(name, start, args=None):
    """Record a start-up span that began at ``start`` (``time.
    perf_counter()``) and ends now: for what runs before this module can be
    imported (``start:import``)."""
    rec = _startup_open(name, args, start)
    if rec is not None:
        rec["end"] = time.perf_counter()


def startup() -> dict:
    """The start-up record: ``spans`` (closed ``start:*`` spans in order of
    entry: ``id``, ``name``, ``start`` and ``end`` in seconds of
    ``time.perf_counter()``, ``thread``, ``parent`` (the ``id`` of the
    enclosing start-up span of its thread, or None), ``args``) and
    ``dropped`` (spans that came after the record was full)."""
    with _state["lock"]:
        return {"spans": [dict(r, args=dict(r["args"])) for r in _startup
                          if r["end"] is not None],
                "dropped": _startup_dropped[0]}


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

    A span named ``start:*`` (construction and warm-up, never a step) is
    all of that and is ALSO always stamped into the bounded start-up record
    (:func:`startup`): two clock reads a span, a few hundred in a
    process's life.

    ``args`` is a flat dict of str/int/float.  A value never holds ``,`` or
    ``#``: the trace format cuts a stat there.  The profiler session gets
    the args as they are on entry; the Chrome trace reads them on exit, so
    a caller may attach fields while the span is open."""

    __slots__ = ("name", "category", "args", "_t0", "_ann", "_rec", "_open")

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
        self._rec = _startup_open(self.name, self.args) \
            if self.name.startswith("start:") else None
        # the list is kept: a span closed on another thread than it was
        # opened on (a generator that moved) leaves the list it is on
        self._open = open_frames()
        self._open.append(self)
        self._ann = _TraceAnnotation(self.name, **(self.args or {}))
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._open.remove(self)
        if self._rec is not None:
            self._rec["args"] = dict(self.args or {})
            self._rec["end"] = time.perf_counter()
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


def leaves_bytes(arrays):
    """``leaves`` and ``bytes`` of some arrays (NDArray, numpy or jax):
    the args of a ``start:params`` span."""
    arrays = list(arrays)
    return {"leaves": len(arrays),
            "bytes": int(sum(a.size * a.dtype.itemsize for a in arrays))}


def framed(name, category="startup"):
    """Decorator: the whole call is one :class:`Frame` (a constructor as
    the root its parts nest in)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with Frame(name, category):
                return fn(*args, **kwargs)
        return inner
    return wrap


class first_call:
    """A freshly built program in its owner's slot until it has run once.

    The FIRST call is a ``start:program`` span (args ``program``, the
    jitted function's name, and ``kind``) from the call through the end of
    the program's first execution: tracing, lowering, the compile or the
    load from the persistent cache, the dispatch and the run.  ``settle``
    puts the program itself into the slot before that call, so after it
    nothing of this wrapper is left on the call path.  Attributes (``lower``,
    ``__name__``, a cached function's ``records``) are the program's."""

    __slots__ = ("_fn", "_kind", "_settle")

    def __init__(self, fn, kind, settle):
        self._fn, self._kind, self._settle = fn, kind, settle

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, *args):
        fn, settle = self._fn, self._settle
        if settle is None:  # a caller that kept the wrapper calls again
            return fn(*args)
        self._settle = None
        settle(fn)
        name = getattr(fn, "__name__", None) or getattr(
            getattr(fn, "_fn", None), "__name__", self._kind)
        with Frame("start:program", "startup",
                   {"program": name, "kind": self._kind}):
            return jax.block_until_ready(fn(*args))


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
