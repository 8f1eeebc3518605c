"""Readers of a data-parallel step's collectives in both forms the TPU
compiler gives them.

A synchronous collective is one operation on the device's ``XLA Ops`` line
(``all-reduce.97``), which ``trace.COLLECTIVE_RE`` matches and
``allreduce_ms_per_step_lm`` reads.  An asynchronous one (what
``mxnet_tpu/sharding/placement.py`` asks for since PR 42) is no operation of
its own: the ring runs as a chain of steps, each inside one compute fusion
whose computation is ``async_collective_fusion.N``, between an
``async-collective-start`` (microseconds) and an ``async-collective-done``,
where the core waits for what the carriers left over.  ``COLLECTIVE_RE``
matches none of these names, so the accepted pair reads only what stayed
synchronous.  The two readers here keep the ring in sight in either form:

``allreduce_wait_ms_per_step``     the core's time held by collectives: the
    synchronous operations and the ``-done`` of the asynchronous ones.  A
    step all of whose collectives are synchronous reads what
    ``allreduce_exposed_ms_per_step_lm`` reads.
``allreduce_carrier_ms_per_step``  the device time of the fusions that carry
    a step of an asynchronous collective: compute and ring together, which
    a trace cannot part.  None where no collective is asynchronous.

A carrier is known by its event's name, which on the TPU is the operation's
whole text and ends in ``calls=%async_collective_fusion.N``; a fusion's
short name (``fusion.1289``) does not tell, which is why this file reads the
events' names itself and not through ``spans.load``.  As every reader: the
run's ``info`` in, a number out, or None where the trace holds nothing for
it (an untraced run, one chip, a rehearsal on the host).
"""
import functools
import os
import re

from perfbench.harness import spans as _spans
from perfbench.harness import trace as _trace

CARRIER_RE = re.compile(r"calls=%?async_collective_fusion")
WAIT_RE = re.compile(r"^async-collective-done")
START_RE = re.compile(r"^[\w\-]*-start")


@functools.lru_cache(maxsize=None)
def kind(name):
    """``"carrier"``, ``"wait"`` or None of a device event's whole name."""
    short = _trace.short_name(name)
    if _trace.ENVELOPE_RE.match(short):
        return None
    if CARRIER_RE.search(name):
        return "carrier"
    if WAIT_RE.match(short) or (_trace.COLLECTIVE_RE.search(short)
                                and not START_RE.match(short)):
        return "wait"
    return None


def seconds_by_kind(devices, window):
    """{"wait": s, "carrier": s}, mean over devices, of ``devices`` {plane:
    [(whole name, start s, end s)]} between the window's edges."""
    t0, t1 = window or (float("-inf"), float("inf"))
    out = {"wait": 0.0, "carrier": 0.0}
    for events in devices.values():
        for name, start, end in events:
            what, a, b = kind(name), max(start, t0), min(end, t1)
            if what and b > a:
                out[what] += b - a
    return {k: v / len(devices) for k, v in out.items()} if devices else out


def raw_ops(path):
    """{device plane: [(whole name, start s, end s)]} of the ``XLA Ops``
    lines of an ``.xplane.pb``."""
    import jax

    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events]
        if ops:
            out[plane.name] = ops
    return out


@functools.lru_cache(maxsize=4)
def _of_path(path):
    return seconds_by_kind(raw_ops(path), _spans.load(path).window)


def _ms_per_step(info, what):
    n = len(info.get("step_ms") or [])
    if not info.get("trace") or not info.get("workload") or not n or \
            info.get("chips", 1) < 2:
        return None
    try:
        path = _trace.find_xplane(os.path.join(
            _spans.ROOT, ".perfbench_trace", info["workload"]))
    except FileNotFoundError:
        return None
    secs = _of_path(path)[what]
    return 1e3 * secs / n if secs > 0 else None


def allreduce_wait_ms_per_step(info):
    return _ms_per_step(info, "wait")


def allreduce_carrier_ms_per_step(info):
    return _ms_per_step(info, "carrier")
