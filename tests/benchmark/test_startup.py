"""The ten ``setup_*`` readers (``perfbench/harness/startup.py``): each reads
a hand-made start-up record exactly, reads None where there is none, and a
traced rehearsal of a training and a decode cell prints all ten."""
import os

import pytest

from perfbench.harness import manifest as mf
from perfbench.harness import startup

from bench_util import ROOT, last_line, run_cell

SETUP = ("setup_program_s", "setup_import_s", "setup_bind_s",
         "setup_params_s", "setup_programs", "setup_trace_lower_s",
         "setup_cache_load_s", "setup_compile_s", "setup_cache_misses",
         "setup_first_run_s")


def _span(i, name, start, end, parent=None, thread="MainThread", **args):
    return {"id": i, "name": "start:" + name, "start": start, "end": end,
            "thread": thread, "parent": parent, "args": args}


def _row(program, phase, seconds, span="start:program", span_id=None,
         events=1):
    return {"program": program, "phase": phase, "span": span,
            "span_id": span_id, "events": events, "seconds": seconds}


RECORD = {"spans": [
    _span(0, "import", 0.0, 2.0),
    _span(1, "backend", 2.5, 2.625, platform="tpu", devices=1),
    _span(2, "server", 10.0, 30.0),
    _span(3, "bind", 11.0, 13.0, parent=2, kind="predict", bucket=1),
    _span(4, "params", 11.5, 12.0, parent=3, leaves=3, bytes=96),
    _span(5, "pool", 13.0, 13.5, parent=2, bytes=4096, pages=8, slots=0),
    _span(6, "program", 14.0, 20.0, parent=2, program="p", kind="fwd"),
    _span(7, "program", 20.0, 21.0, parent=2, program="q", kind="pool"),
    # another thread's first call, half of it beside the server's span
    _span(8, "program", 29.0, 32.0, thread="mxtpu-gen-engine", program="r",
          kind="gen-step"),
    _span(9, "optimizer", 40.0, 41.0, states=0, bytes=0)],
    "rows": [
    _row("p", "trace", 1.0, span_id=6), _row("p", "lower", 0.5, span_id=6),
    _row("p", "load", 0.25, span_id=6),
    _row("q", "compile", 0.75, span_id=7, events=2),
    # a recompile in service and a jit that is not the program's: not
    # start-up's
    _row("p", "compile", 9.0, span="gen:step"),
    _row("user_fn", "trace", 5.0, span=None)]}

WANT = {"setup_program_s": 2.0 + 0.125 + 22.0 + 1.0,
        "setup_import_s": 2.125,
        # the bind less the params inside it, the pool, the optimizer
        "setup_bind_s": 1.5 + 0.5 + 1.0,
        "setup_params_s": 0.5, "setup_programs": 3.0,
        "setup_trace_lower_s": 1.5, "setup_cache_load_s": 0.25,
        "setup_compile_s": 0.75, "setup_cache_misses": 2.0,
        "setup_first_run_s": 10.0 - 2.5}


@pytest.fixture(scope="module")
def manifest():
    return mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", SETUP)
def test_reader_reads_a_hand_made_record(manifest, name):
    read = manifest.load_module("layer_metrics", name + ".py").read
    assert read is getattr(startup, name)
    assert read({"startup": RECORD}) == pytest.approx(WANT[name], abs=1e-12)
    assert isinstance(read({"startup": RECORD}), float)
    # an empty record, and a program that keeps none, read nothing
    assert read({"startup": {"spans": [], "rows": []}}) is None


def test_a_program_without_a_record_reads_none(monkeypatch):
    """The parent commit's ``profiler`` has no ``startup``: every reader
    leaves its metric out and none raises."""
    from mxnet_tpu import profiler

    monkeypatch.delattr(profiler, "startup")
    for name in SETUP:
        assert getattr(startup, name)({}) is None


def test_the_entries_move_setup_s_in_every_cell(manifest_data):
    entries = [m for m in manifest_data["per_layer"]
               if m["moves"] == "setup_s"]
    assert [m["name"] for m in entries] == list(SETUP)
    # appended at the end, one layer, every cell by name
    assert manifest_data["per_layer"][-len(SETUP):] == entries
    cells = [w["name"] for w in manifest_data["workloads"]][:6]
    for m in entries:
        assert m["layer"] == "start-up (context.py, compile_cache.py)"
        assert m["better"] == "lower" and m["workloads"] == cells
        assert m["unit"] == ("count" if m["name"] in (
            "setup_programs", "setup_cache_misses") else "s")
        assert m["source"] == ("program_counter" if m["name"] in (
            "setup_trace_lower_s", "setup_cache_load_s", "setup_compile_s",
            "setup_cache_misses") else "program_span")


@pytest.mark.parametrize("workload,programs", [("toy-train-lm", 1),
                                               ("toy-decode", 8)])
def test_a_traced_rehearsal_prints_all_ten(workload, programs):
    rc, out, err = run_cell(workload, seed=2**31 + 39, seconds=1.5, trace=1)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    got = {k: v["value"] for k, v in line["metrics"].items()
           if k.startswith("setup_")}
    assert set(got) == set(SETUP)
    assert all(line["metrics"][k]["unit"] in ("s", "count") for k in got)
    assert got["setup_programs"] == programs
    # nothing is cached on the host: every program compiles
    assert got["setup_cache_misses"] >= programs
    assert got["setup_cache_load_s"] == 0.0 and got["setup_compile_s"] > 0
    assert got["setup_import_s"] > 0 and got["setup_first_run_s"] >= 0
    # the program's share lies inside the run's set-up; its parts inside it
    setup_s = float([ln for ln in out.splitlines()
                     if ln.startswith("[run] setup_s")][0].split()[2][:-1])
    assert 0 < got["setup_program_s"] < setup_s
    assert got["setup_trace_lower_s"] + got["setup_compile_s"] \
        + got["setup_first_run_s"] + got["setup_bind_s"] \
        + got["setup_params_s"] < got["setup_program_s"] + 1e-6
