"""``perfbench/harness/moe_prefill.py``: the reader of the routed expert
layers' device time inside the runs of the prefill programs (PR 45), on
hand-made traces (the window, the programs' names, the scope, XLA's own
grouped-product calls without it) and on a recorded one-chip trace."""
import os

import pytest

from perfbench.harness import moe_prefill, spans
from perfbench.harness.manifest import Manifest

from bench_util import ROOT

RECORDED = os.path.join(ROOT, "perfbench", "testdata", "small-tpu.xplane.pb")
ENTRIES = {"moe_prefill_experts_ms_g4hs": "g4hsmall-decode-closed16",
           "moe_prefill_experts_ms_pangu": "pangu718b-decode-closed16"}
EXPERTS = "jit(prefill_L2048)/layer3_experts/moe_experts/jit(routed_experts)"
PLANE = "/device:TPU:0"


def _op(name, start, end, scope):
    return spans.Op(name, start, end, scope)


def _trace(ops, runs, window=(0.0, 100.0)):
    return spans.Trace([], {PLANE: ops}, window, {PLANE: runs})


OPS = [
    # a 2,048-token prefill: the kernel, the gather loop and the combine
    # under the scope, the router and a matmul outside it
    _op("moe_grouped.3", 1.0, 1.4, EXPERTS + "/jit(_kernel_held)/moe_grouped"),
    _op("fusion.19", 1.4, 1.5, EXPERTS + "/jit(_kernel_held)/while/body"),
    _op("moe_combine.3", 1.5, 1.7, EXPERTS + "/jit(_kernel_held)/moe_combine"),
    _op("fusion.7", 1.7, 1.8, "jit(prefill_L2048)/layer3_experts_router/"
        "moe_router/jit(route)/top_k"),
    _op("fusion.8", 1.8, 2.0, "jit(prefill_L2048)/layer3_in_proj/dot_general"),
    # a lane step's experts: the scope, another program
    _op("moe_grouped.1", 3.0, 3.5, "jit(decode_b16)/layer3_experts/"
        "moe_experts/jit(routed_experts)/jit(_kernel_grouped)/moe_grouped"),
    # a 512-token prefill under the "ragged" formulation: XLA's call
    # carries no scope and is known by its name
    _op("ragged-dot-none.4", 5.0, 5.2, None),
    _op("fusion.21", 5.2, 5.3, "jit(prefill_L512)/layer3_experts/"
        "moe_experts/jit(routed_experts)/take"),
    # a prefill that starts after the window
    _op("moe_grouped.3", 101.0, 101.4, EXPERTS),
]
RUNS = [("jit_prefill_L2048(123)", 1.0, 2.0), ("jit_decode_b16(7)", 3.0, 3.6),
        ("jit_prefill_L512(99)", 5.0, 5.5),
        ("jit_prefill_L2048(123)", 101.0, 102.0)]


def test_the_scope_inside_the_prefill_programs_runs_over_their_count():
    """0.4 + 0.1 + 0.2 s of the first prefill, 0.2 + 0.1 of the second, two
    runs in the window: 500 ms a run; the lane step's experts, the router,
    the matmul and the prefill past the window are not in it."""
    got = moe_prefill.ms_inside_runs(
        _trace(OPS, RUNS), moe_prefill.PREFILL_MODULE, moe_prefill.is_expert_op)
    assert got == pytest.approx(500.0)
    # and the lane program's reading stays its own
    got = moe_prefill.ms_inside_runs(
        _trace(OPS, RUNS), spans.DECODE_MODULE, moe_prefill.is_expert_op)
    assert got == pytest.approx(500.0)


@pytest.mark.parametrize("ops,runs", [
    (OPS, []),                                   # no prefill on the line
    (OPS, [("jit_decode_b16(7)", 3.0, 3.6)]),    # lane steps only
    ([o for o in OPS if not moe_prefill.is_expert_op(o)], RUNS),  # no scope
    ([], RUNS)])
def test_nothing_to_read_is_none_and_no_error(ops, runs):
    assert moe_prefill.ms_inside_runs(
        _trace(ops, runs), moe_prefill.PREFILL_MODULE,
        moe_prefill.is_expert_op) is None
    assert moe_prefill.ms_inside_runs(
        None, moe_prefill.PREFILL_MODULE, moe_prefill.is_expert_op) is None


def test_a_recorded_one_chip_trace_holds_no_prefill():
    tr = spans.load(RECORDED)
    assert tr.devices
    assert moe_prefill.ms_inside_runs(
        tr, moe_prefill.PREFILL_MODULE, moe_prefill.is_expert_op) is None


def test_the_reader_reads_the_runs_trace(monkeypatch):
    monkeypatch.setattr(spans, "of_run", lambda info: _trace(OPS, RUNS))
    assert moe_prefill.moe_prefill_experts_ms({}) == pytest.approx(500.0)
    monkeypatch.setattr(spans, "of_run", lambda info: None)
    assert moe_prefill.moe_prefill_experts_ms({}) is None


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_the_entries_and_their_files(metric, manifest_data):
    """Appended to the list, each for its own long-prompt cell, in the layer
    and with the fields of the lane step's entry; a run without a trace
    reads nothing and raises nothing (the parent's program has the scope and
    the prefill programs: it reads there too)."""
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    entry = m.per_layer[metric]
    assert entry == dict(m.per_layer["moe_experts_ms_per_step_g4hs"],
                         name=metric, workloads=[ENTRIES[metric]])
    assert m.workloads[ENTRIES[metric]]["chips"] == 1
    names = [x["name"] for x in manifest_data["per_layer"]]
    assert names.index(metric) > names.index("decode_bytes_roofline_pct_g4hs")
    read = m.load_module("layer_metrics", metric + ".py").read
    assert read({"workload": "no-such-cell", "trace": None}) is None
    assert read({"workload": "no-such-cell",
                 "trace": {"busy_s": 1.0}}) is None
