"""Readers of the program's start-up record: the ``start:*`` spans that
``mxnet_tpu.profiler`` stamps at construction and warm-up sites whether or
not anything listens (``profiler.startup()``), and the compile ledger
``mxnet_tpu.compile_cache`` keeps of JAX's own trace, lower, compile and
cache-load events (``compile_cache.ledger()``).  Everything of it happens
before the window opens, so no trace holds it: the readers read it where
it lies, in this process's memory, after the run.  They need no device
line and read numbers on the host platform too.

Each reader moves ``setup_s``.  A program that keeps no such record (a
commit before PR 39) reads None everywhere.
"""
import functools

PROGRAM = "start:program"


def record(info):
    """(spans, rows), or None where there is nothing to read.  ``info``
    may bring a record of its own under ``"startup"`` (``spans``,
    ``rows``): the tests' hand-made ones."""
    given = info.get("startup")
    if given is None:
        try:
            from mxnet_tpu import compile_cache, profiler

            given = {"spans": profiler.startup()["spans"],
                     "rows": compile_cache.ledger()}
        except (ImportError, AttributeError):
            return None
    return (given["spans"], given["rows"]) if given["spans"] else None


def _reads(fn):
    """``fn(spans, rows)`` as a reader: None where there is no record."""
    @functools.wraps(fn)
    def read(info):
        rec = record(info)
        return None if rec is None else float(fn(*rec))
    return read


def _seconds(span):
    return span["end"] - span["start"]


def _named_s(spans, *names):
    return sum(_seconds(s) for s in spans if s["name"] in names)


def _rows_s(rows, *phases):
    return sum(r["seconds"] for r in rows
               if r["span"] == PROGRAM and r["phase"] in phases)


@_reads
def setup_program_s(spans, rows):
    """Wall time the union of all ``start:*`` spans covers."""
    wall, edge = 0.0, float("-inf")
    for start, end in sorted((s["start"], s["end"]) for s in spans):
        wall += max(0.0, end - max(start, edge))
        edge = max(edge, end)
    return wall


@_reads
def setup_import_s(spans, rows):
    return _named_s(spans, "start:import", "start:backend")


@_reads
def setup_bind_s(spans, rows):
    """Self time: a span less the start-up spans directly inside it (a
    predictor's ``start:params``, the backend's first contact)."""
    inside = {}
    for s in spans:
        if s["parent"] is not None:
            inside[s["parent"]] = inside.get(s["parent"], 0.0) + _seconds(s)
    return sum(_seconds(s) - inside.get(s["id"], 0.0) for s in spans
               if s["name"] in ("start:bind", "start:pool",
                                "start:optimizer"))


@_reads
def setup_params_s(spans, rows):
    return _named_s(spans, "start:params")


@_reads
def setup_programs(spans, rows):
    return sum(s["name"] == PROGRAM for s in spans)


@_reads
def setup_trace_lower_s(spans, rows):
    return _rows_s(rows, "trace", "lower")


@_reads
def setup_cache_load_s(spans, rows):
    return _rows_s(rows, "load")


@_reads
def setup_compile_s(spans, rows):
    return _rows_s(rows, "compile")


@_reads
def setup_cache_misses(spans, rows):
    return sum(r["events"] for r in rows
               if r["span"] == PROGRAM and r["phase"] == "compile")


@_reads
def setup_first_run_s(spans, rows):
    """The programs' first calls less what the ledger has of them:
    dispatch, the first execution, and what JAX does around its events."""
    return _named_s(spans, PROGRAM) - _rows_s(rows, "trace", "lower", "load",
                                              "compile")
