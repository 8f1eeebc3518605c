"""Persistent XLA compilation cache + AOT executable bundles.

ROADMAP item 5: every process used to pay full XLA compilation on
startup — a fresh serving replica warmed every bucket through the
compiler, an elastic fresh-rank joiner recompiled the fused step its
peers were already running, and a hot-swap shadow replica recompiled
before it could flip.  This module makes the compiled executable itself
a durable, content-addressed artifact (the TVM compile-artifact-reuse
idea, arXiv:1802.04799, applied at the XLA executable layer):

* every lowered program the executor stack builds (fused train step,
  forward, forward+backward — and therefore every serving bucket) is
  keyed by a **content fingerprint**: the batch signature of its
  arguments (the StepMonitor recompile detector's machinery), a hash of
  the symbol graph, the static trace knobs (mixed-precision dtype,
  remat, ctx-group placement, grad_req partition, optimizer family and
  hypers), and the stable sharding fingerprint (mesh axes/devices +
  PartitionSpecs);
* on miss the program is lowered and compiled exactly as before, then
  the executable is serialized (``jax.experimental.serialize_executable``)
  into an atomic, CRC-checked cache entry (same tmp+fsync+rename
  discipline as checkpoints);
* on hit the executable deserializes in milliseconds and **no XLA
  compilation happens at all**.

Environment compatibility (jax/jaxlib version, backend, device
kind/count, process count) is recorded in every entry and checked at
load: a mismatched entry is a miss (invalidation), never a crash.  Cache
I/O is a ``faults`` dotted op (``compile_cache.load`` /
``compile_cache.store``) so chaos tests can prove a corrupt or torn
entry degrades to a plain recompile.  Telemetry:
``mxtpu_compile_cache_hits_total`` / ``_misses_total`` /
``_stores_total`` / ``_errors_total`` plus compile-ms vs deserialize-ms
histograms.

AOT bundles (``checkpoint.save_aot_bundle``) re-pack the live entries a
serving process is running into a directory next to the params, with a
warmup manifest — a new replica attaches the bundle as a read-only
cache overlay and its whole warmup is deserialize-only.

Enable with ``MXNET_COMPILE_CACHE_DIR=/path`` (empty default = off: the
executor stack behaves exactly as before).

The compile ledger (always on; it fires once a compile, never a step): a
``jax.monitoring`` listener for the start of JAX's own timed events and one
for their end, registered when this module is imported, turn them into rows
of :func:`ledger`: which program was traced, lowered, compiled or loaded
from JAX's persistent cache, for how long, and inside which of the
program's spans (``profiler.Frame``): a ``start:program`` at start-up, a
``gen:step`` or ``Module.update`` for a recompile in service.
:func:`stats` counts that layer's hits and misses beside this module's own.
"""
from __future__ import annotations

import json
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# bound here, on the importing thread: the ledger's listener runs on
# whatever thread compiles, and a relative import there would wait for a
# package import still in progress (the kvstore server's bootstrap)
from . import profiler as _prof
from . import telemetry as _tm
from .artifact_store import EntryStore, digest_of
from .base import MXNetError, env, register_env

__all__ = [
    "enabled", "cache_dir", "env_fingerprint", "stats", "reset_stats",
    "ledger",
    "maybe_cached", "CachedFunction", "attach_bundle", "detach_bundles",
    "save_bundle", "read_manifest", "ls_entries", "verify_entry", "prune",
    "entry_meta", "MANIFEST_NAME", "ENTRY_SUFFIX",
]

register_env("MXNET_COMPILE_CACHE_DIR", "", str,
             "Directory for the persistent framework-level compilation "
             "cache (serialized XLA executables, content-fingerprint "
             "keyed). Empty disables the cache entirely.")
register_env("MXNET_COMPILE_CACHE_MAX_MB", 2048, int,
             "Size budget for the compile-cache directory; after a store "
             "the oldest entries (by mtime) are pruned until under "
             "budget. <= 0 disables pruning.")
register_env("MXNET_COMPILE_CACHE_STRICT", 0, int,
             "1 makes cache load/store failures raise instead of "
             "degrading to a plain recompile (debugging aid; production "
             "keeps 0: a broken cache must never break the job).")
register_env("MXNET_COMPILE_CACHE_MIN_MS", 0.0, float,
             "Only compilations that took at least this many ms are "
             "stored (0 stores everything). Skips serializing trivial "
             "programs whose recompile is cheaper than the disk entry.")

_MAGIC = b"MXTPUCC1"
_SCHEMA = 1
ENTRY_SUFFIX = ".mxc"
MANIFEST_NAME = "manifest.json"

# on-disk grammar + admin: artifact_store
_STORE = EntryStore(_MAGIC, ENTRY_SUFFIX, "compile-cache", "compile_cache")

_lock = threading.Lock()
# process-wide loaded-executable cache: a hot-swap shadow replica in the
# same process inherits the outgoing replica's executables without even
# touching the disk.  key digest -> (callable, meta)
_mem: Dict[str, Tuple[Any, dict]] = {}
# read-only overlay directories (attached AOT bundles), searched after
# the primary cache dir
_bundles: List[str] = []
_env_fp_cache: Optional[dict] = None


def enabled() -> bool:
    return bool(env("MXNET_COMPILE_CACHE_DIR", "", str))


def active() -> bool:
    """True when executables may come out of (or go into) the cache:
    the on-disk cache is enabled or an AOT bundle overlay is attached.
    The executor stack uses this to build cache-eligible programs
    without buffer donation — XLA's executable deserializer has been
    observed to mis-bind donated (input-output aliased) arguments that
    share a shape, so persisted executables must not rely on it."""
    return enabled() or bool(_bundles)


def cache_dir() -> str:
    return env("MXNET_COMPILE_CACHE_DIR", "", str)


def _strict() -> bool:
    return bool(env("MXNET_COMPILE_CACHE_STRICT", 0, int))


# ---------------------------------------------------------------------------
# telemetry instruments (global registry; cheap even with telemetry off —
# these fire once per executable build, never per step)
# ---------------------------------------------------------------------------

_instruments = None


def _metrics():
    global _instruments
    if _instruments is None:
        reg = _tm.registry()
        _instruments = {
            "hits": reg.counter(
                "mxtpu_compile_cache_hits_total",
                "Executable builds satisfied by deserializing a cache "
                "entry (no XLA compilation)."),
            "misses": reg.counter(
                "mxtpu_compile_cache_misses_total",
                "Executable builds that had to run the XLA compiler."),
            "stores": reg.counter(
                "mxtpu_compile_cache_stores_total",
                "Cache entries written."),
            "errors": reg.counter(
                "mxtpu_compile_cache_errors_total",
                "Cache load/store failures degraded to a recompile "
                "(corrupt entry, torn write, injected fault)."),
            "compile_ms": reg.histogram(
                "mxtpu_compile_ms",
                "XLA compile time per cache-miss executable build (ms).",
                start=1.0, factor=4.0, count=12),
            "deserialize_ms": reg.histogram(
                "mxtpu_compile_cache_deserialize_ms",
                "Executable deserialize time per cache hit (ms).",
                start=0.25, factor=4.0, count=12),
            # JAX's own layer, from the compile ledger's listener
            "jit_hits": reg.counter(
                "mxtpu_jit_cache_hits_total",
                "Programs loaded from JAX's persistent compilation cache."),
            "jit_misses": reg.counter(
                "mxtpu_jit_cache_misses_total",
                "Programs the backend compiled (no persistent-cache entry, "
                "or no persistent cache)."),
        }
        for phase in LEDGER_PHASES:
            _instruments["jit_" + phase] = reg.counter(
                "mxtpu_jit_%s_seconds_total" % phase,
                "Seconds JAX spent in phase %r of building programs (own "
                "time: nested events taken out)." % phase)
    return _instruments


def _log_event(kind, **fields):
    try:
        _tm.log_event(kind, **fields)
    except Exception:
        pass


def stats() -> dict:
    """Compact counters for BENCH / capture records: this module's
    executable cache (the ``.mxc`` layer) at the top level, JAX's
    persistent compilation cache under ``"jax"`` (hits: programs loaded
    from it; misses: programs the backend compiled; seconds by phase)."""
    m = _metrics()
    return {
        "dir": cache_dir() or None,
        "hits": m["hits"].value,
        "misses": m["misses"].value,
        "stores": m["stores"].value,
        "errors": m["errors"].value,
        "compile_ms": round(m["compile_ms"].sum, 1),
        "deserialize_ms": round(m["deserialize_ms"].sum, 1),
        "jax": dict(
            {"hits": m["jit_hits"].value, "misses": m["jit_misses"].value},
            **{phase + "_s": round(m["jit_" + phase].value, 3)
               for phase in LEDGER_PHASES}),
    }


def reset_stats() -> None:
    """Test hook: drop instrument handles (a telemetry registry reset
    leaves stale handles otherwise), the in-memory executable cache and
    the compile ledger."""
    global _instruments
    with _lock:
        _instruments = None
        _mem.clear()
        del _bundles[:]
        _ledger_rows.clear()
        _ledger_programs.clear()


# ---------------------------------------------------------------------------
# the compile ledger: JAX's own compile events as rows
# ---------------------------------------------------------------------------

LEDGER_PHASES = ("trace", "lower", "compile", "load")
LEDGER_PROGRAMS = 256
# the spans a compile is charged to: a program's first call at start-up, a
# step or a prefill in service (innermost first where they nest)
PROGRAM_SPANS = frozenset(("start:program", "gen:step", "gen:prefill",
                           "Module.forward_backward", "Module.update"))
_EVENT_PHASE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile"}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# (program, phase, span, span_id) -> [events, seconds]
_ledger_rows: Dict[tuple, list] = {}
_ledger_programs = set()
_ledger_local = threading.local()


def _on_compile_begin(event, _start, fun_name=None, **_):
    """The ``jax.monitoring`` scalar listener: JAX reports the start of
    each of its timed events.  Events nest (a function jitted inside
    another is traced inside the outer's trace, a constant is compiled
    while tracing): the open ones of a thread are a stack."""
    if event in _EVENT_PHASE:
        stack = getattr(_ledger_local, "stack", None)
        if stack is None:
            stack = _ledger_local.stack = []
        stack.append([event, _program_name(fun_name), 0.0])


def _program_name(fun_name):
    name = str(fun_name or "?")
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


def _on_compile_event(event, secs, fun_name=None, **_):
    """The ``jax.monitoring`` duration listener: an event that ended, as a
    row.  A backend-compile event inside which the persistent cache's
    retrieval event fired (same thread) is a ``load``.  A row holds the
    event's OWN seconds (the events inside it taken out) under the name of
    the outermost open event, so the rows under a span add up to no more
    than the span and a program's rows carry its name, not its parts'."""
    local = _ledger_local
    if event == _RETRIEVAL_EVENT:
        local.loaded = True
        return
    phase = _EVENT_PHASE.get(event)
    if phase is None:
        return
    if phase == "compile" and getattr(local, "loaded", False):
        phase, local.loaded = "load", False
    stack = getattr(local, "stack", None) or []
    program, own = _program_name(fun_name), secs
    if stack and stack[-1][0] == event:
        own = max(secs - stack.pop()[2], 0.0)
        if stack:
            stack[-1][2] += secs
            program = stack[0][1]
    else:  # a start went unheard: start afresh
        del stack[:]
    span = next((f for f in reversed(_prof.open_frames())
                 if f.name in PROGRAM_SPANS), None)
    if span is not None and span.name == "start:program":
        # whatever its first call builds on the way (a constant computed
        # while tracing) is that program's to pay
        program = span.args["program"]
    with _lock:
        if program not in _ledger_programs:
            if len(_ledger_programs) < LEDGER_PROGRAMS:
                _ledger_programs.add(program)
            else:
                program = "other"
        row = _ledger_rows.setdefault(
            (program, phase, span.name if span else None,
             span._rec["id"] if span and span._rec else None), [0, 0.0])
        row[0] += 1
        row[1] += own
    m = _metrics()
    m["jit_" + phase].inc(own)
    if phase in ("compile", "load"):
        m["jit_hits" if phase == "load" else "jit_misses"].inc()


def ledger() -> List[dict]:
    """The compile ledger's rows, in order of their first event:
    ``program`` (under a ``start:program`` that span's program; elsewhere
    the name of the outermost jitted function being built, without JAX's
    ``jit(...)``; ``other`` for what came after ``LEDGER_PROGRAMS``
    names), ``phase`` (``trace``, ``lower``,
    ``compile`` for a backend compile that ran, ``load`` for a retrieval
    from JAX's persistent cache), ``span`` (the innermost open program
    span of the compiling thread, or None for a jit that is not the
    program's), ``span_id`` (the start-up record's id where the span is a
    ``start:program``), ``events`` and ``seconds`` (own time)."""
    with _lock:
        return [{"program": k[0], "phase": k[1], "span": k[2],
                 "span_id": k[3], "events": v[0], "seconds": v[1]}
                for k, v in _ledger_rows.items()]


def _register_listener():
    import jax

    jax.monitoring.register_scalar_listener(_on_compile_begin)
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


_register_listener()


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def env_fingerprint() -> dict:
    """The compatibility envelope an executable is only valid inside:
    jax/jaxlib versions, backend platform, device kind and count, process
    count.  Recorded in every entry and checked at load — any mismatch
    invalidates (a miss, never a crash)."""
    global _env_fp_cache
    if _env_fp_cache is None:
        import jax
        import jaxlib

        devs = jax.devices()
        _env_fp_cache = {
            "schema": _SCHEMA,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            "device_kind": devs[0].device_kind if devs else "none",
            "device_count": len(devs),
            "process_count": jax.process_count(),
        }
    return dict(_env_fp_cache)


def _signature(args) -> dict:
    """The batch-signature half of the key: (shape, dtype) per leaf plus
    the pytree structure (which pins argument names and None slots)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    import numpy as np

    sig = []
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", np.asarray(leaf).dtype))
        sig.append([list(shape), dtype])
    return {"tree": str(treedef), "leaves": sig}


def _arg_devices(args) -> list:
    """Sorted (platform, id) of every device the concrete arguments live
    on — an executable is compiled FOR its devices, so the same graph on
    cpu(0) and tpu(0), or on two replicas' chips, is two entries."""
    import jax

    return sorted({(d.platform, d.id)
                   for leaf in jax.tree_util.tree_leaves(args)
                   if isinstance(leaf, jax.Array) for d in leaf.devices()})


_digest = digest_of


# ---------------------------------------------------------------------------
# entry file format:  MAGIC | u64 meta_len | meta json | pickle(payload)
# with a CRC32 sidecar — artifact_store's grammar
# ---------------------------------------------------------------------------

def _entry_path(d: str, digest: str) -> str:
    return _STORE.entry_path(d, digest)


def entry_meta(path: str) -> dict:
    """Parse just the json header of an entry (no unpickling)."""
    return _STORE.entry_meta(path)


def _write_entry(d: str, digest: str, meta: dict, payload_bytes: bytes,
                 op: str = "compile_cache.store") -> str:
    return _STORE.write_entry(d, digest, meta, payload_bytes, op=op)


def _read_payload(path: str) -> Tuple[dict, bytes]:
    return _STORE.read_payload(path)


def _env_compatible(meta: dict) -> bool:
    return meta.get("env") == env_fingerprint()


# ---------------------------------------------------------------------------
# load / store
# ---------------------------------------------------------------------------

def _read_dirs() -> List[str]:
    d = cache_dir()
    out = [d] if d else []
    with _lock:
        out.extend(_bundles)
    return out


def _load(digest: str):
    """-> (callable, meta) or None.  Every failure mode — missing file,
    CRC mismatch, torn header, unpicklable payload, injected fault —
    degrades to None (a miss) with a structured telemetry event."""
    with _lock:
        hit = _mem.get(digest)
    if hit is not None:
        return hit
    from . import faults
    from .filesystem import verify_crc_sidecar

    for d in _read_dirs():
        path = _entry_path(d, digest)
        if not os.path.exists(path):
            continue
        try:
            faults.fire("compile_cache.load")
            ok = verify_crc_sidecar(path)
            if ok is False:
                raise MXNetError("CRC mismatch")
            meta, payload = _read_payload(path)
            if not _env_compatible(meta):
                _log_event("compile_cache_invalidate", path=path,
                           entry_env=meta.get("env"),
                           current_env=env_fingerprint())
                continue  # stale-version entry: a miss, not an error
            from jax.experimental import serialize_executable as se

            # onto the devices the entry was compiled for: left to its
            # default the loader spreads the executable over EVERY local
            # device, which fails on any host with more than one
            t0 = time.perf_counter()
            loaded = se.deserialize_and_load(
                *pickle.loads(payload), backend=meta["devices"]["platform"],
                execution_devices=_entry_devices(meta))
            ms = (time.perf_counter() - t0) * 1e3
            _metrics()["deserialize_ms"].observe(ms)
            with _lock:
                _mem[digest] = (loaded, meta)
            _log_event("compile_cache_hit", digest=digest, path=path,
                       deserialize_ms=round(ms, 3))
            return loaded, meta
        except Exception as exc:
            _metrics()["errors"].inc()
            _log_event("compile_cache_corrupt", path=path,
                       error=repr(exc)[:300])
            if _strict():
                raise
            continue
    return None


def _entry_devices(meta: dict) -> list:
    """The live ``jax.Device`` objects an entry's meta names."""
    import jax

    by_id = {d.id: d for d in jax.devices(meta["devices"]["platform"])}
    return [by_id[i] for i in meta["devices"]["ids"]]


def _store(digest: str, compiled, meta: dict, compile_ms: float) -> Optional[str]:
    d = cache_dir()
    if not d:
        return None
    min_ms = env("MXNET_COMPILE_CACHE_MIN_MS", 0.0, float)
    if compile_ms < min_ms:
        return None
    try:
        from jax.experimental import serialize_executable as se

        payload = pickle.dumps(se.serialize(compiled))
        path = _write_entry(d, digest, meta, payload)
        _metrics()["stores"].inc()
        _log_event("compile_cache_store", digest=digest, path=path,
                   bytes=len(payload), compile_ms=round(compile_ms, 1))
        budget = env("MXNET_COMPILE_CACHE_MAX_MB", 2048, int)
        if budget > 0:
            prune(d, budget)
        return path
    except Exception as exc:
        _metrics()["errors"].inc()
        _log_event("compile_cache_store_failed", digest=digest,
                   error=repr(exc)[:300])
        if _strict():
            raise
        return None


# ---------------------------------------------------------------------------
# the executor-facing wrapper
# ---------------------------------------------------------------------------

class CachedFunction:
    """Lazy cache-aware stand-in for a ``jax.jit`` callable.

    The first call under each argument signature fingerprints the
    concrete arguments, consults the cache (memory, then the cache dir,
    then attached bundles), and either deserializes the executable
    (hit: no XLA compilation) or AOT-compiles via ``lower().compile()``
    and stores the result.  Subsequent calls with the same signature go
    straight to the loaded executable; a NEW signature re-primes — the
    same retrace-on-shape-change contract as plain ``jax.jit``.  Any
    cache malfunction falls back to the wrapped jit callable, which
    behaves exactly as if the cache never existed.
    """

    __slots__ = ("_fn", "_kind", "_static_key", "_executor", "_by_sig",
                 "records", "digest", "meta", "cost_info", "collectives",
                 "cache_state")

    def __init__(self, fn, kind: str, static_key, executor):
        self._fn = fn
        self._kind = kind
        self._static_key = static_key
        self._executor = executor
        self._by_sig: Dict[Any, Any] = {}
        # one record per primed signature (bundle export reads these):
        # {"digest", "meta", "compiled" (live Compiled on miss else None)}
        self.records: List[dict] = []
        # most-recent prime, for the executor/introspection wiring
        self.digest = None
        self.meta = None
        self.cost_info = None
        self.collectives = None
        self.cache_state = None  # "hit" | "miss" | "bypass"

    # delegation keeps telemetry.lower_and_analyze / perf_probe working
    # against the introspection hook unchanged
    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    @staticmethod
    def _quick_sig(args):
        """Hashable per-call signature — the dispatch key.  Cheap
        (no hashing/serialization): treedef + leaf shapes/dtypes."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(
            (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
            for l in leaves))

    def __call__(self, *args):
        fn = self._by_sig.get(self._quick_sig(args))
        if fn is None:
            fn = self._prime(args)
        return fn(*args)

    def _key_parts(self, args) -> dict:
        ex = self._executor
        plan = ex._plan
        parts = {
            "schema": _SCHEMA,
            "kind": self._kind,
            "static": repr(self._static_key),
            "graph": plan.fingerprint(),
            "compute_dtype": str(ex._compute_dtype),
            "cast_exclude": sorted(ex._cast_exclude),
            "remat": int(env("MXNET_BACKWARD_DO_MIRROR", 0, int) or 0),
            "group2ctx": sorted(
                (g, str(c)) for g, c in ex._group2ctx.items()),
            "sig": _signature(args),
            "devices": _arg_devices(args),
        }
        if ex._shard_mesh is not None:
            from .sharding.mesh import mesh_fingerprint

            parts["shard"] = {
                "mesh": mesh_fingerprint(ex._shard_mesh),
                "specs": sorted((k, str(v))
                                for k, v in ex._shard_specs.items()),
            }
        return parts

    def _register(self, sig, fn, state, digest=None, meta=None,
                  compiled=None):
        self._by_sig[sig] = fn
        self.cache_state = state
        self.digest = digest
        self.meta = meta
        self.cost_info = (meta or {}).get("cost") or None
        self.collectives = (meta or {}).get("collectives") or None
        if digest is not None:
            self.records.append(
                {"digest": digest, "meta": meta, "compiled": compiled})
        return fn

    def _prime(self, args):
        sig = self._quick_sig(args)
        digest = None
        try:
            parts = self._key_parts(args)
            digest = _digest(parts)
            hit = _load(digest)
        except Exception as exc:
            if _strict():
                raise
            _metrics()["errors"].inc()
            _log_event("compile_cache_key_failed", kind=self._kind,
                       error=repr(exc)[:300])
            hit = None
            if digest is None:
                # can't even fingerprint: bypass the cache entirely
                return self._register(sig, self._fn, "bypass")
        if hit is not None:
            loaded, meta = hit
            _metrics()["hits"].inc()
            return self._register(sig, loaded, "hit", digest, meta)
        # miss: compile exactly as the plain jit path would, then store
        _metrics()["misses"].inc()
        try:
            t0 = time.perf_counter()
            compiled = self._fn.lower(*args).compile()
            compile_ms = (time.perf_counter() - t0) * 1e3
        except Exception:
            # AOT lowering unsupported for this program: run the plain
            # jit callable (compiles internally, uncached)
            return self._register(sig, self._fn, "bypass")
        _metrics()["compile_ms"].observe(compile_ms)
        cost = _cost_of(compiled)
        meta = self._build_meta(digest, compile_ms, cost, compiled)
        with _lock:
            _mem[digest] = (compiled, meta)
        _store(digest, compiled, meta, compile_ms)
        return self._register(sig, compiled, "miss", digest, meta, compiled)

    def _build_meta(self, digest, compile_ms, cost, compiled) -> dict:
        ex = self._executor
        devs = compiled.runtime_executable().local_devices()
        mesh_axes = None
        if ex._shard_mesh is not None:
            mesh = ex._shard_mesh
            mesh_axes = {str(n): int(mesh.shape[n]) for n in mesh.axis_names}
        return {
            "digest": digest,
            "kind": self._kind,
            "env": env_fingerprint(),
            "mesh_axes": mesh_axes,
            # what _load hands deserialize_and_load as execution_devices
            "devices": {"platform": devs[0].platform,
                        "ids": [d.id for d in devs]},
            "created": round(time.time(), 3),
            "compile_ms": round(compile_ms, 1),
            "cost": cost,
            # of a program over several devices: its collectives and how
            # many of them run asynchronously, read from the fresh
            # executable as the cost is (the step monitor's gauges)
            "collectives": _collectives_of(compiled) if len(devs) > 1
            else None,
        }


def _cost_of(compiled) -> Optional[dict]:
    from .hlo_analysis import cost_analysis

    return cost_analysis(compiled)


def _collectives_of(compiled) -> Optional[dict]:
    from .hlo_analysis import collective_counts

    try:
        return collective_counts(compiled.as_text())
    except Exception:
        return None


def maybe_cached(fn, kind: str, static_key, executor):
    """Executor hook: wrap a jit callable in a :class:`CachedFunction`
    when the cache is enabled, else return it untouched (the default —
    zero behavior change with no cache dir configured)."""
    if not enabled() and not _bundles:
        return fn
    return CachedFunction(fn, kind, static_key, executor)


# ---------------------------------------------------------------------------
# AOT bundles — a read-only cache overlay saved beside a checkpoint
# ---------------------------------------------------------------------------

def save_bundle(path: str, entries, warmup: Optional[dict] = None) -> str:
    """Write an AOT executable bundle: one cache entry per compiled
    program in ``entries`` (:class:`CachedFunction` wrappers, typically
    every bucket of a serving replica) plus ``manifest.json`` recording
    the warmup recipe and the environment fingerprint.  Entries whose
    executable came from the cache are copied from their source entry
    file; fresh compiles are serialized directly."""
    os.makedirs(path, exist_ok=True)
    from .filesystem import atomic_write

    manifest = {
        "schema": _SCHEMA,
        "env": env_fingerprint(),
        "created": round(time.time(), 3),
        "warmup": warmup or {},
        "entries": [],
    }
    seen = set()
    for wrapper in entries:
        for rec in getattr(wrapper, "records", []) or []:
            digest, meta = rec["digest"], rec["meta"] or {}
            if digest in seen:
                continue
            if rec.get("compiled") is not None:
                from jax.experimental import serialize_executable as se

                payload = pickle.dumps(se.serialize(rec["compiled"]))
            else:
                # executable was itself deserialized: copy its source entry
                src = None
                for d in _read_dirs():
                    p = _entry_path(d, digest)
                    if os.path.exists(p):
                        src = p
                        break
                if src is None:
                    continue
                _, payload = _read_payload(src)
            _write_entry(path, digest, meta, payload)
            seen.add(digest)
            manifest["entries"].append({
                "digest": digest,
                "kind": meta.get("kind"),
                "mesh_axes": meta.get("mesh_axes"),
                "cost": meta.get("cost"),
            })
    atomic_write(os.path.join(path, MANIFEST_NAME),
                 lambda f: f.write(json.dumps(manifest, indent=1,
                                              default=str).encode()),
                 checksum=True, op="compile_cache.store")
    _log_event("compile_cache_bundle_saved", path=path,
               entries=len(manifest["entries"]))
    return path


def read_manifest(path: str) -> dict:
    with open(os.path.join(path, MANIFEST_NAME)) as f:
        return json.load(f)


def attach_bundle(path: str, mesh=None) -> dict:
    """Attach an AOT bundle directory as a read-only cache overlay.

    Refuses LOUDLY (raises :class:`MXNetError`) when the bundle was
    built for a different device topology or — when ``mesh`` is given —
    under different mesh axes: silently serving the wrong executable
    layout is exactly the failure this check exists to stop.  A stale
    jax/jaxlib version is a softer failure: the bundle attaches but
    every entry invalidates at load (plain recompile) with a structured
    event."""
    manifest = read_manifest(path)
    cur = env_fingerprint()
    ent_env = manifest.get("env") or {}
    for k in ("platform", "device_kind", "device_count", "process_count"):
        if ent_env.get(k) != cur.get(k):
            raise MXNetError(
                "AOT bundle %s was built for %s=%r but this process has "
                "%r — refusing the mismatched restore (rebuild the bundle "
                "on this topology or serve without it)"
                % (path, k, ent_env.get(k), cur.get(k)))
    if mesh is not None:
        want = {str(n): int(mesh.shape[n]) for n in mesh.axis_names}
        for e in manifest.get("entries", []):
            axes = e.get("mesh_axes")
            if axes and axes != want:
                raise MXNetError(
                    "AOT bundle entry %s records mesh axes %s but the "
                    "target mesh is %s — refusing the mismatched restore"
                    % (e.get("digest"), axes, want))
    with _lock:
        if path not in _bundles:
            _bundles.append(path)
    _log_event("compile_cache_bundle_attached", path=path,
               entries=len(manifest.get("entries", [])))
    return manifest


def detach_bundles() -> None:
    with _lock:
        del _bundles[:]


# ---------------------------------------------------------------------------
# admin: ls / verify / prune  (shared with tools/compile_cache_admin.py)
# ---------------------------------------------------------------------------

def ls_entries(d: str) -> List[dict]:
    """[{digest, path, bytes, mtime, kind, compile_ms, env_ok}] for every
    entry in ``d`` (unreadable headers report kind='corrupt')."""
    return _STORE.ls_entries(
        d, meta_fields=lambda meta: {"kind": meta.get("kind"),
                                     "compile_ms": meta.get("compile_ms"),
                                     "env_ok": _env_compatible(meta)})


def verify_entry(path: str) -> Tuple[bool, str]:
    """(ok, detail): CRC sidecar + header + payload unpickle check —
    everything short of loading onto devices."""
    ok, detail = _STORE.verify_entry(
        path, payload_check=lambda meta, payload: pickle.loads(payload),
        env_ok=_env_compatible)
    if detail == "ok (stale env: invalidates on load)":
        detail = "ok (stale env: recompiles on load)"
    return ok, detail


def prune(d: str, budget_mb: int) -> List[str]:
    """Delete oldest-mtime entries (and their sidecars) until the
    directory is under ``budget_mb``.  Returns the removed paths."""
    removed = _STORE.prune(d, budget_mb)
    if removed:
        _log_event("compile_cache_pruned", dir=d, removed=len(removed))
    return removed
