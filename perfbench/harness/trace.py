"""From a profiler trace (.xplane.pb) to numbers.

One reduction for every cell and every PR: device busy and idle time, time
by operation name, collectives and the part of them that nothing hides,
kernels' time, and the longest idle gaps with what the host was doing.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per executed operation.  On the host platform (rehearsals and tests) the
PjRt CPU client's thread lines of ``/host:CPU`` stand in for a device.
Host spans (``jax.profiler.TraceAnnotation`` and the profiler's own Python
events) are on the same clock and name the gaps.
"""
import functools
import glob
import os
import re

OPCODE_RE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
# a program span is ``layer:what`` or ``Class.method[:part]``; the runtime's
# own events (``tpu::System::Execute=>Done``, ``PjitFunction(f)``) and the
# host platform's operations (``dot_general.66``) are not
NAME_RE = re.compile(r"^(?!.*\.\d+$)[A-Za-z_]\w*"
                     r"(?:[.:](?!:)[\w\[\]%=\-]+)+$")


@functools.lru_cache(maxsize=None)
def short_name(text):
    """``name:opcode`` of a device event whose name is the operation's whole
    HLO text (``%fusion.3 = f32[..]{..} fusion(..), kind=..``); any other
    name is kept as it is.  (Remembered: a trace holds millions of events of
    a few thousand operations.)"""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    m = OPCODE_RE.search(" " + rest)
    return "%s:%s" % (name, m.group(1)) if m else name


def start_trace(trace_dir):
    """Start the profiler with the Python tracer off: the host's TraceMe
    spans (the runtime's and the benchmark's own) are enough to name the
    gaps, and a traced run stays close to an untraced one."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


COLLECTIVE_RE = re.compile(r"all-reduce|all_reduce|all-gather|all_gather|"
                           r"reduce-scatter|reduce_scatter|collective-permute|"
                           r"all-to-all", re.I)
# wrappers whose duration covers other events of the same line
ENVELOPE_RE = re.compile(r"^(while|conditional|call)([.\d]*)(:\S+)?$")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def load(path):
    """{"devices": {plane name: [(name, start_s, dur_s)]},
    "host": [(name, start_s, dur_s)]} from an .xplane.pb file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host, cpu_dev = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((short_name(e.name), e.start_ns * 1e-9,
                                e.duration_ns * 1e-9) for e in line.events)
            if ops:
                devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events if e.duration_ns > 0]
                if "XLAPjRtCpuClient" in line.name:
                    cpu_dev.extend(e for e in evs
                                   if not e[0].startswith("Threadpool"))
                else:
                    host.extend(evs)
    if not devices and cpu_dev:
        devices["/host:CPU"] = sorted(cpu_dev, key=lambda e: e[1])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def _leaf_events(events):
    """Events without the envelopes (while / call / conditional bodies are
    events of their own on the same line)."""
    return [e for e in events if not ENVELOPE_RE.match(e[0])]


def _host_name(host, a, b):
    """What the host was doing in [a, b].  Of the spans that cover at least
    half of it, the innermost (shortest) span of the program names it,
    followed by the innermost event of the runtime inside that span where
    there is one (``gen:prefill > ExecutePrepare``); where no span of the
    program does, the innermost ``bench:`` span, the benchmark's own; where
    none of those either, the shortest event of any kind.  Where no one span
    covers half, the name whose spans together cover most of it (a quarter
    at least), marked ``mostly``; else ``unannotated``."""
    program, bench, other, total = [], [], [], {}
    for name, s, d in host:
        if s >= b:
            break
        ov = min(s + d, b) - max(s, a)
        if ov <= 0 or name == "bench:window":
            continue
        total[name] = total.get(name, 0.0) + ov
        if ov >= 0.5 * (b - a):
            kind = bench if name.startswith("bench:") else \
                program if NAME_RE.match(name) else other
            kind.append((d, s, name))
    if program:
        d, s, name = min(program)
        inner = [e for e in other if s <= e[1] and e[1] + e[0] <= s + d]
        return "%s > %s" % (name, min(inner)[2]) if inner else name
    if bench or other:
        return min(bench or other)[2]
    if total:
        name = max(total, key=total.get)
        # spans nest, so a name can sum to more than the gap; what counts
        # is that it was there for a quarter of it
        if total[name] >= 0.25 * (b - a):
            return "mostly " + name
    return "unannotated"


def reduce(trace, t0=None, t1=None, top=10):
    """Numbers of a loaded trace between ``t0`` and ``t1`` (seconds on the
    trace's clock; default: the host span ``bench:window`` where the trace
    has one, else first to last device event).

    Returns {"window_s", "busy_s" (mean over devices), "idle_share",
    "by_name" {name: seconds, mean over devices}, "device_ops" [[name, s]],
    "idle_gaps" [[what the host did, s]], "collective_s",
    "collective_exposed_s", "n_devices"}.
    """
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device operations")
    starts = [ev[0][1] for ev in devs.values()]
    ends = [max(s + d for _, s, d in ev) for ev in devs.values()]
    span = [(s, s + d) for name, s, d in trace["host"]
            if name == "bench:window"]
    if t0 is None and t1 is None and span:
        t0, t1 = span[0]
    t0 = min(starts) if t0 is None else t0
    t1 = max(ends) if t1 is None else t1
    n = len(devs)
    busy = coll = exposed = 0.0
    by_name, gaps = {}, []
    for events in devs.values():
        leaves = list(_clip(_leaf_events(events), t0, t1))
        merged = union((a, b) for _, a, b in leaves)
        busy += sum(b - a for a, b in merged)
        for name, a, b in leaves:
            by_name[name] = by_name.get(name, 0.0) + (b - a)
        edge = t0
        for a, b in merged + [(t1, t1)]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
        cs = [(a, b) for name, a, b in leaves if COLLECTIVE_RE.search(name)]
        other = union((a, b) for name, a, b in leaves
                      if not COLLECTIVE_RE.search(name))
        for a, b in union(cs):
            coll += b - a
            hidden = sum(min(b, y) - max(a, x) for x, y in other
                         if min(b, y) > max(a, x))
            exposed += (b - a) - hidden
    window = t1 - t0
    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window, "busy_s": busy / n,
        "idle_share": 1.0 - busy / n / window if window > 0 else None,
        "by_name": {k: v / n for k, v in by_name.items()},
        "device_ops": [[k, v / n] for k, v in ops[:top]],
        "idle_gaps": [[_host_name(trace["host"], a, b), b - a]
                      for a, b in gaps[:top]],
        "collective_s": coll / n, "collective_exposed_s": exposed / n,
        "n_devices": n}


def time_of(reduced, pattern):
    """Seconds (mean over devices) of the operations whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["by_name"].items() if rx.search(k))
