"""The program's own spans and scopes in a run's profiler trace.

``mxnet_tpu.profiler.Frame`` enters a ``jax.profiler.TraceAnnotation``, so a
traced run's ``.xplane.pb`` holds the program's spans (``gen:step``,
``Module.update``, ...) as host events on the clock of the device operations,
with their arguments as stats; ``jax.named_scope`` and a Pallas kernel's
``name`` reach the device plane as each operation's scope (``tf_op``, a stat of
the event's metadata) and name.  This file reads both; the per-layer metrics
with the sources ``program_span`` and (by scope or kernel name)
``device_trace`` are its readers, one two-line file each under
``layer_metrics/``.

An operation's scope is its PROGRAM's: two programs may each hold a
``fusion.73``, so the scope is looked up by the operation's name and the
program whose run on the device's ``XLA Modules`` line it falls in (a run is
named ``jit_decode_b16(<program id>)``, and each operation's metadata carries
that id).  The runs themselves are kept too: a program's own device time is
the duration of its runs.

Events, times and the window (the host span ``bench:window``) are taken as
``trace.load`` and ``trace.reduce`` take them.  ``jax.profiler.ProfileData``
exposes an event's own stats but not its metadata's, where the scope is: that
part is decoded from the file's wire format here (the few fields of XSpace /
XPlane / XEventMetadata / XStat needed; tensorflow and xprof are not
imported).  A reader returns None where the trace holds nothing for it: an
untraced run, a rehearsal on the host (no scopes there), a program without
that span (the parent of the PR that added it).
"""
import bisect
import collections
import functools
import os
import re
import statistics

from perfbench.harness import trace as _trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

Span = collections.namedtuple("Span", "name start end thread stats")
Op = collections.namedtuple("Op", "name start end scope")
# ``modules``: {device plane: [(name, start, end)], by start}, the runs of
# whole programs on the device's ``XLA Modules`` line; empty (a hand-made
# trace: None) where the trace has no such line, as on the host platform
Trace = collections.namedtuple("Trace", "spans devices window modules",
                               defaults=(None,))

NAME_RE = _trace.NAME_RE  # what a program span is named
PROGRAM_ID_RE = re.compile(r"\((\d+)\)$")  # ``jit_decode_b16(<id>)``
# the lane programs, ``jit_decode_b<lanes>``, on the module line
DECODE_MODULE = "jit_decode_b"


# ---------------------------------------------------------------------------
# the scope of a device operation, from the file's wire format
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = buf[i:i + size]
            i += size
        else:
            raise ValueError("wire type %d in an .xplane.pb" % wire)
        yield key >> 3, val


def _map_entry(buf):
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def op_scopes(path):
    """{plane name: {event name: {program id: scope}}} of the device planes of
    an ``.xplane.pb``: the ``tf_op`` stat of each event's metadata, which
    holds the operation's ``op_name`` (``jit(fused_step)/fc1/dot_general:``),
    without the trailing colon, under the metadata's ``program_id`` (None
    where it has none).  The scope is None where the program's operation of
    that name has none."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:  # XSpace.planes
            continue
        name, metas, stat_names = None, [], {}
        for pf, v in _fields(plane):
            if pf == 2:  # XPlane.name
                name = bytes(v).decode()
            elif pf == 4:  # XPlane.event_metadata
                metas.append(_map_entry(v)[1])
            elif pf == 5:  # XPlane.stat_metadata
                sid, meta = _map_entry(v)
                for mf, mv in _fields(meta):
                    if mf == 2:
                        stat_names[sid] = bytes(mv).decode()
        if not name or not name.startswith("/device:TPU:"):
            continue
        ids = {v: k for k, v in stat_names.items()}
        want, want_program = ids.get("tf_op"), ids.get("program_id")
        found = {}  # {event name: {program id: {scope}}}
        for meta in metas:
            ev_name = scope = program = None
            for mf, mv in _fields(meta):
                if mf == 2:  # XEventMetadata.name
                    ev_name = bytes(mv).decode()
                elif mf == 5:  # XEventMetadata.stats
                    stat = dict(_fields(mv))
                    sid = stat.get(1)  # XStat.metadata_id
                    if sid is None:
                        continue
                    if sid == want_program:
                        # uint64_value or int64_value
                        program = stat.get(3, stat.get(4))
                    elif sid == want:
                        if 5 in stat:  # str_value
                            scope = bytes(stat[5]).decode()
                        elif 7 in stat:  # ref_value: a stat_metadata's name
                            scope = stat_names.get(stat[7])
            if ev_name is None or (program is None and not scope):
                continue
            seen = found.setdefault(ev_name, {}).setdefault(program, set())
            if scope:
                seen.add(scope.rsplit(":", 1)[0])
        out[name] = {ev: {prog: next(iter(seen)) if len(seen) == 1 else None
                          for prog, seen in progs.items()}
                     for ev, progs in found.items()}
    return out


def _scope(by_program, program):
    """The scope of an operation that ran in ``program`` (None: not known),
    of ``{program id: scope}``: that program's, or, where the program is not
    known, the one scope every program gives the name."""
    if not by_program:
        return None
    if program in by_program:
        return by_program[program]
    scopes = {s for s in by_program.values() if s}
    return scopes.pop() if len(scopes) == 1 else None


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def _stat(value):
    return value if isinstance(value, (int, float)) else str(value)


@functools.lru_cache(maxsize=4)
def load(path):
    """The program spans of the host plane, the device operations with their
    scopes, the window, and the programs' runs on the devices, of one
    ``.xplane.pb``: once a process."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    scopes = op_scopes(path)
    spans, devices, cpu_ops, modules = [], {}, [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            names = scopes.get(plane.name, {})
            runs = sorted((e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name)
                          for line in plane.lines
                          if line.name == "XLA Modules"
                          for e in line.events)
            starts = [r[0] for r in runs]
            programs = [int(m.group(1)) if m else None for m in
                        (PROGRAM_ID_RE.search(r[2]) for r in runs)]
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    short = _trace.short_name(e.name)
                    if _trace.ENVELOPE_RE.match(short):
                        continue
                    s = e.start_ns * 1e-9
                    # the run the operation started in says whose it is
                    i = bisect.bisect_right(starts, s) - 1
                    program = programs[i] \
                        if i >= 0 and s < runs[i][1] else None
                    ops.append(Op(short, s, s + e.duration_ns * 1e-9,
                                  _scope(names.get(e.name), program)))
            if ops:
                devices[plane.name] = sorted(ops, key=lambda o: o.start)
            if runs:
                modules[plane.name] = [(n, a, b) for a, b, n in runs]
        elif plane.name == "/host:CPU":
            for thread, line in enumerate(plane.lines):
                if "XLAPjRtCpuClient" in line.name:
                    # the host platform's stand-in for a device (as in
                    # trace.load); its events carry no scope
                    for e in line.events:
                        if e.duration_ns > 0 and \
                                not e.name.startswith("Threadpool"):
                            s = e.start_ns * 1e-9
                            cpu_ops.append(Op(e.name, s,
                                              s + e.duration_ns * 1e-9,
                                              None))
                    continue
                for e in line.events:
                    if not NAME_RE.match(e.name):
                        continue
                    s = e.start_ns * 1e-9
                    spans.append(Span(e.name, s, s + e.duration_ns * 1e-9,
                                      thread,
                                      {k: _stat(v) for k, v in e.stats}))
    if not devices and cpu_ops:
        devices["/host:CPU"] = sorted(cpu_ops, key=lambda o: o.start)
    spans.sort(key=lambda s: (s.start, -s.end))
    window = next(((s.start, s.end) for s in spans
                   if s.name == "bench:window"), None)
    return Trace(spans, devices, window, modules)


def of_run(info):
    """The trace of the run whose ``info`` a reader was handed, or None:
    ``run.py`` keeps a traced run's files under
    ``<root>/.perfbench_trace/<workload>``."""
    if not info.get("trace") or not info.get("workload"):
        return None
    try:
        path = _trace.find_xplane(os.path.join(ROOT, ".perfbench_trace",
                                               info["workload"]))
    except FileNotFoundError:
        return None
    return load(path)


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------

def named(trace, name):
    """The spans of that name that start inside the window, by start."""
    t0, t1 = _window(trace)
    return [s for s in trace.spans if s.name == name and t0 <= s.start < t1]


def inside(trace, parents, name=None):
    """The spans (of ``name``, or any) that lie inside one of ``parents`` on
    its thread, the parents themselves left out."""
    out = []
    for s in trace.spans:
        if name is not None and s.name != name:
            continue
        if any(p is not s and p.thread == s.thread and p.start <= s.start
               and s.end <= p.end for p in parents):
            out.append(s)
    return out


def total_s(spans):
    return sum(s.end - s.start for s in spans)


def self_s(trace, span):
    """A span's self time: its duration minus what the spans inside it on
    its thread cover."""
    covered = _trace.union((c.start, c.end)
                           for c in inside(trace, [span]))
    return (span.end - span.start) - sum(b - a for a, b in covered)


def median_ms(spans):
    return 1e3 * statistics.median(s.end - s.start for s in spans) \
        if spans else None


def _window(trace):
    return trace.window or (float("-inf"), float("inf"))


def busy_s(trace):
    """Device busy time in the window, mean over devices (as
    ``trace.reduce`` counts it): one pass over each device's operations,
    which lie by start."""
    t0, t1 = _window(trace)
    total = 0.0
    for ops in trace.devices.values():
        edge = t0  # up to here the device's time is counted
        for o in ops:
            a, b = max(o.start, edge), min(o.end, t1)
            if b > a:
                total += b - a
                edge = b
    return total / len(trace.devices) if trace.devices else 0.0


def module_runs(trace, part):
    """{device plane: [(start, end)], by start} of the whole runs that start
    inside the window of the programs whose name on the device's ``XLA
    Modules`` line holds ``part``; {} where the trace has no such line or no
    such program."""
    t0, t1 = _window(trace)
    out = {}
    for plane, runs in (trace.modules or {}).items():
        mine = [(a, b) for name, a, b in runs if part in name and t0 <= a < t1]
        if mine:
            out[plane] = mine
    return out


def op_s(trace, match):
    """Device seconds in the window, mean over devices, of the operations
    ``match(op)`` picks."""
    t0, t1 = _window(trace)
    total = 0.0
    for ops in trace.devices.values():
        for o in ops:
            a, b = max(o.start, t0), min(o.end, t1)
            if b > a and match(o):
                total += b - a
    return total / len(trace.devices) if trace.devices else 0.0


def in_scope(component):
    """Matches operations traced under ``jax.named_scope(component)``: the
    component stands in the scope path whole, whatever wraps it
    (``transpose(jvp(..))``) or follows it."""
    rx = re.compile(r"(?:^|[/(])%s(?:[/)]|$)" % component)
    # a trace holds millions of operations under a few thousand scopes
    found = functools.lru_cache(maxsize=None)(
        lambda scope: bool(scope and rx.search(scope)))
    return lambda op: found(op.scope)


def kernel(name):
    """Matches the device events of the Pallas kernel of that ``name``: the
    compiler names the custom call after the kernel (``flash_fwd.3``, or
    ``jvp_flash_fwd_.1`` where a transformation wrapped it)."""
    rx = re.compile(r"(?<![A-Za-z0-9])%s(?![A-Za-z0-9])[^:]*:custom-call$"
                    % re.escape(name))
    return lambda op: bool(rx.search(op.name))


# ---------------------------------------------------------------------------
# the readers (``info`` in, a number or None out)
# ---------------------------------------------------------------------------

def _steps(info):
    """(trace, its ``gen:step`` spans) or (None, [])."""
    tr = of_run(info)
    steps = named(tr, "gen:step") if tr else []
    return (tr, steps) if steps else (None, [])


def gen_step_ms_p50(info):
    return median_ms(_steps(info)[1])


def _gen_part_ms_per_step(info, name):
    tr, steps = _steps(info)
    if tr is None:
        return None
    return 1e3 * total_s(inside(tr, steps, name)) / len(steps)


def gen_pool_h2d_ms_per_step(info):
    """One ``device_put`` a step of the lanes' ids, positions, sources and
    page tables (a few KB): no plane crosses, they live on the device."""
    return _gen_part_ms_per_step(info, "gen:pool_h2d")


def gen_pool_d2h_ms_per_step(info):
    """The wait for the step dispatched one iteration back, then its lanes'
    picked ids (32 bytes at 8 lanes): the engine thread's wait on the
    device, not a transfer's time."""
    return _gen_part_ms_per_step(info, "gen:pool_d2h")


def gen_sched_ms_per_step(info):
    """What is left of the engine thread per step: the steps without the
    pool's two trips (so: the dispatch, grow / feed / emit, the step's
    self time) and the admissions without their prefills."""
    tr, steps = _steps(info)
    if tr is None:
        return None
    pool = sum(total_s(inside(tr, steps, n)) for n in
               ("gen:pool_h2d", "gen:pool_d2h"))
    admit = sum(self_s(tr, a) for a in named(tr, "gen:admit"))
    return 1e3 * (total_s(steps) - pool + admit) / len(steps)


def gen_prefill_ms_p50(info):
    tr = of_run(info)
    return median_ms(named(tr, "gen:prefill")) if tr else None


def gen_queue_wait_p50_ms(info):
    tr = of_run(info)
    waits = [float(s.stats["wait_ms"]) for s in named(tr, "gen:queued")
             if "wait_ms" in s.stats] if tr else []
    return statistics.median(waits) if waits else None


def gen_device_ms_per_step(info):
    """The lane program's own time on the device: the mean duration of the
    runs of ``jit_decode_b<lanes>`` on the ``XLA Modules`` line that start in
    the window.  Not the busy time inside ``gen:step`` spans: one step is in
    flight while the engine thread is in ``gen:admit``, outside every
    ``gen:step``.  Beside ``gen_step_ms_p50`` it says whether the device or
    the host sets the pace."""
    tr = of_run(info)
    runs = [b - a for spans in module_runs(tr, DECODE_MODULE).values()
            for a, b in spans] if tr else []
    return 1e3 * statistics.fmean(runs) if runs else None


def _share_pct(info, match):
    tr = of_run(info)
    if tr is None:
        return None
    busy, secs = busy_s(tr), op_s(tr, match)
    return 100.0 * secs / busy if busy > 0 and secs > 0 else None


def gen_paged_attn_share_pct(info):
    return _share_pct(info, in_scope(r"paged_attention(?:_window)?"))


def optimizer_share_pct(info):
    return _share_pct(info, in_scope("optimizer"))


def step_host_ms_p50(info):
    """Median, per step, of ``Module.forward_backward`` + ``Module.update``:
    the program's host side of a training step."""
    tr = of_run(info)
    if tr is None:
        return None
    fb = named(tr, "Module.forward_backward")
    up = named(tr, "Module.update")
    if not fb or not up:
        return None
    return 1e3 * statistics.median(
        (f.end - f.start) + (u.end - u.start) for f, u in zip(fb, up))


def _kernel_ms_per_step(info, name):
    tr = of_run(info)
    n = len(info.get("step_ms") or [])
    if tr is None or not n:
        return None
    secs = op_s(tr, kernel(name))
    return 1e3 * secs / n if secs > 0 else None


def flash_fwd_ms_per_step(info):
    return _kernel_ms_per_step(info, "flash_fwd")


def flash_bwd_dq_ms_per_step(info):
    return _kernel_ms_per_step(info, "flash_bwd_dq")


def flash_bwd_dkv_ms_per_step(info):
    return _kernel_ms_per_step(info, "flash_bwd_dkv")
