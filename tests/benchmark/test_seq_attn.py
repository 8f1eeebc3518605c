"""``perfbench/harness/seq_attn.py``: the reader of the sequence attention's
device time inside the runs of the prefill programs (PR 52), on hand-made
traces (the window, the programs' names, the node's name as a whole scope
component), and its entry in the manifest."""
import pytest

from perfbench.harness import moe_prefill, seq_attn, spans
from perfbench.harness.manifest import Manifest

from bench_util import ROOT

ENTRY, CELL = "seq_attn_prefill_ms_laguna", "lagunaxs2-decode-closed16"
PLANE = "/device:TPU:0"
FULL = "jit(prefill_L4096)/layer0_attn/jit(call)/flash_fwd/pallas_call"
BAND = "jit(prefill_L4096)/layer13_attn/window_attention/jit(call)/" \
    "flash_fwd/pallas_call"


def _trace(ops, runs, window=(0.0, 100.0)):
    return spans.Trace([], {PLANE: ops}, window, {PLANE: runs})


OPS = [
    # a 4,096-token prefill: a full layer's kernel and the transpose before
    # it, a sliding layer's kernel; the gate, the projection and the rings'
    # gather are other nodes or other scopes of the same node
    spans.Op("fusion.3", 0.9, 1.0, "jit(prefill_L4096)/layer0_attn/transpose"),
    spans.Op("flash_fwd.1", 1.0, 1.6, FULL),
    spans.Op("flash_fwd.14", 2.0, 3.4, BAND),
    spans.Op("fusion.9", 3.4, 3.5, "jit(prefill_L4096)/layer13_attn/"
             "window_attention/gather"),
    spans.Op("fusion.4", 3.5, 3.7, "jit(prefill_L4096)/layer13_attn_gate/"
             "dot_general"),
    spans.Op("fusion.5", 3.7, 3.8, "jit(prefill_L4096)/layer13_attn_gate_mul/"
             "mul"),
    spans.Op("fusion.6", 3.8, 4.0, "jit(prefill_L4096)/layer13_q/dot_general"),
    # a lane step's attention: the node's name, another program
    spans.Op("paged_decode.1", 5.0, 5.4, "jit(decode_b16)/layer0_attn/"
             "paged_attention/paged_decode"),
    # the XLA form in a 1,024 prefill of a family that keeps it
    spans.Op("fusion.30", 7.0, 7.5, "jit(prefill_L1024)/layer3_attn/"
             "window_attention/dot_general"),
    # a prefill that starts after the window
    spans.Op("flash_fwd.1", 101.0, 101.6, FULL),
]
RUNS = [("jit_prefill_L4096(1)", 0.8, 4.2), ("jit_decode_b16(7)", 5.0, 5.5),
        ("jit_prefill_L1024(2)", 7.0, 7.8),
        ("jit_prefill_L4096(1)", 101.0, 104.0)]


def test_the_nodes_inside_the_prefill_programs_runs_over_their_count():
    """0.1 + 0.6 + 1.4 + 0.1 s of the first prefill, 0.5 of the second, two
    runs in the window: 1,350 ms a run; the gate's two nodes, the
    projection, the lane step's attention and the prefill past the window
    are not in it."""
    got = moe_prefill.ms_inside_runs(_trace(OPS, RUNS), seq_attn.PREFILL_MODULE,
                                     seq_attn.is_attention_op)
    assert got == pytest.approx(1350.0)


@pytest.mark.parametrize("scope,said", [
    (FULL, True), (BAND, True),
    ("jit(prefill_L4096)/layer0_attn", True),
    ("jit(prefill_L4096)/transpose(layer7_attn)/mul", True),
    ("jit(prefill_L4096)/layer0_attn_gate/dot_general", False),
    ("jit(prefill_L4096)/layer0_attn_q_norm/mul", False),
    ("jit(prefill_L4096)/layerN_attn/mul", False),
    ("jit(prefill_L4096)/my_layer0_attn/mul", False),
    (None, False)])
def test_the_node_is_matched_as_a_whole_component(scope, said):
    assert seq_attn.is_attention_op(spans.Op("fusion", 0.0, 1.0, scope)) \
        is said


@pytest.mark.parametrize("ops,runs", [
    (OPS, []),                                   # no prefill on the line
    (OPS, [("jit_decode_b16(7)", 5.0, 5.5)]),    # lane steps only
    ([o for o in OPS if not seq_attn.is_attention_op(o)], RUNS),  # no node
    ([], RUNS)])
def test_nothing_to_read_is_none_and_no_error(ops, runs):
    assert moe_prefill.ms_inside_runs(
        _trace(ops, runs), seq_attn.PREFILL_MODULE,
        seq_attn.is_attention_op) is None
    assert seq_attn.seq_attn_prefill_ms({}) is None


def test_the_entry_reads_this_reader_in_the_sliding_window_cell():
    m = Manifest(ROOT + "/BENCHMARK.json", root=ROOT)
    x = m.per_layer[ENTRY]
    assert x["workloads"] == [CELL] and x["unit"] == "ms"
    assert (x["better"], x["source"], x["moves"]) == (
        "lower", "device_trace", "decode_tokens_per_s")
    assert x["layer"] == m.per_layer["mla_attn_ms_per_step_pangu"]["layer"]
    assert m.load_module("layer_metrics", ENTRY + ".py").read \
        is seq_attn.seq_attn_prefill_ms
