"""``perfbench/harness/collectives.py``: the two readers that keep a
data-parallel step's all-reduce in sight when the TPU compiler runs it
asynchronously (PR 42), on names copied from the cell's compiled step (my
chip run, PR 42: ``two_crs1m_kloop``, operands cut), on hand-made events and
on a recorded one-chip trace."""
import os

import pytest

from perfbench.harness import collectives, spans
from perfbench.harness.manifest import Manifest

from bench_util import ROOT

RECORDED = os.path.join(ROOT, "perfbench", "testdata", "small-tpu.xplane.pb")
METRICS = ["allreduce_wait_ms_per_step_lm", "allreduce_carrier_ms_per_step_lm"]
CELL = "cgpt13b-train-dp4"

CARRIER = ("%fusion.1289 = (bf16[2048]{0:T(1024)(128)(2,1)}, "
           "bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
           "fusion(%get-tuple-element.1515, %copy-done.55), kind=kLoop, "
           "output_to_operand_aliasing={{2}: (0, {})}, "
           "calls=%async_collective_fusion.1289")
DONE = ("%async-collective-done.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} "
        "fusion(%get-tuple-element.1653), kind=kCustom, "
        "calls=%fused_computation.1960")
START = ("%async-collective-start = (bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, "
         "u32[]{:S(2)}) fusion(%fusion.764), kind=kCustom, "
         "calls=%fused_computation.1952")
SYNC = ("%all-reduce.97 = bf16[8192,2048]{1,0:T(8,128)(2,1)} "
        "all-reduce(%fusion.12), channel_id=7, replica_groups=[1,4]<=[4], "
        "to_apply=%add")
PLAIN = ("%fusion.11 = f32[512,1024]{1,0:T(8,128)S(1)} fusion(%x.1), "
         "kind=kOutput, calls=%fused_computation.12")


@pytest.mark.parametrize("name,want", [
    (CARRIER, "carrier"), (DONE, "wait"), (START, None), (SYNC, "wait"),
    (PLAIN, None),
    ("%all-reduce-start.5 = f32[8]{0} all-reduce-start(%p), to_apply=%add",
     None),
    ("%all-reduce-done.5 = f32[8]{0} all-reduce-done(%all-reduce-start.5)",
     "wait"),
    ("%all-gather.2 = f32[8,4]{1,0} all-gather(%p), dimensions={0}", "wait"),
    ("%slice-done.3 = f32[256,512]{1,0} async-done(%slice-start.3)", None),
    # an envelope covers other events of its line
    ("%while.3 = (s32[], f32[8]{0}) while(%tuple), condition=%c, body=%b",
     None),
    ("fusion.5", None), ("all-reduce.1", "wait")])
def test_an_events_name_says_what_it_is(name, want):
    assert collectives.kind(name) == want


def test_seconds_are_clipped_to_the_window_and_averaged_over_devices():
    devices = {
        "/device:TPU:0": [(PLAIN, 0.0, 1.0), (CARRIER, 1.0, 3.0),
                          (DONE, 3.0, 3.5), (SYNC, 9.5, 11.0)],
        "/device:TPU:1": [(CARRIER, 1.0, 2.0), (DONE, 2.0, 3.5),
                          (START, 3.5, 3.6)]}
    got = collectives.seconds_by_kind(devices, (0.5, 10.0))
    assert got == {"wait": pytest.approx((0.5 + 0.5 + 1.5) / 2),
                   "carrier": pytest.approx((2.0 + 1.0) / 2)}
    assert collectives.seconds_by_kind({}, None) == {"wait": 0.0,
                                                     "carrier": 0.0}


def _with(monkeypatch, devices, window=(0.0, 10.0)):
    collectives._of_path.cache_clear()
    monkeypatch.setattr(collectives._trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(collectives, "raw_ops", lambda path: devices)
    monkeypatch.setattr(spans, "load",
                        lambda path: spans.Trace([], {}, window))


@pytest.mark.parametrize("form", ["asynchronous", "synchronous", "neither"])
def test_the_readers_follow_the_ring_in_either_form(form, monkeypatch):
    """The change's step: carriers and the waits behind them.  The parent's:
    synchronous operations, which the wait reader reads as the accepted
    exposed metric does, and no carrier.  A step with no collective at all
    (or a trace of the host platform: no device plane): nothing."""
    events = {"asynchronous": [(CARRIER, 1.0, 3.0), (DONE, 3.0, 3.4),
                               (SYNC, 5.0, 5.1), (PLAIN, 6.0, 7.0)],
              "synchronous": [(SYNC, 1.0, 3.0), (PLAIN, 3.0, 4.0)],
              "neither": [(PLAIN, 3.0, 4.0)]}[form]
    _with(monkeypatch, {"/device:TPU:0": events, "/device:TPU:1": events})
    info = {"workload": CELL, "trace": {"busy_s": 1.0}, "chips": 4,
            "step_ms": [1.0, 1.0]}
    wait = collectives.allreduce_wait_ms_per_step(info)
    carrier = collectives.allreduce_carrier_ms_per_step(info)
    if form == "asynchronous":
        assert (wait, carrier) == (pytest.approx(250.0), pytest.approx(1e3))
    elif form == "synchronous":
        assert (wait, carrier) == (pytest.approx(1e3), None)
    else:
        assert (wait, carrier) == (None, None)
    # one chip has no ring; an untraced run no trace
    assert collectives.allreduce_wait_ms_per_step(dict(info, chips=1)) is None
    assert collectives.allreduce_wait_ms_per_step(
        dict(info, trace=None)) is None
    collectives._of_path.cache_clear()


def test_a_recorded_one_chip_trace_holds_neither():
    """The events' names as a TPU's profile gives them: whole operations,
    ``calls=`` included, which is what a carrier is known by."""
    devices = collectives.raw_ops(RECORDED)
    assert list(devices) == ["/device:TPU:0"]
    names = {name for name, _, _ in devices["/device:TPU:0"]}
    assert any("calls=%fused_computation" in n for n in names)
    assert collectives.seconds_by_kind(devices, None) == {"wait": 0.0,
                                                          "carrier": 0.0}


@pytest.mark.parametrize("metric", METRICS)
def test_the_entries_and_their_files(metric, manifest_data):
    """Appended to the list, for the four-chip cell alone, in the layer of
    the accepted pair; a run without a trace reads nothing and raises
    nothing."""
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    entry = m.per_layer[metric]
    assert entry == dict(m.per_layer["allreduce_exposed_ms_per_step_lm"],
                         name=metric)
    assert entry["workloads"] == [CELL] and m.workloads[CELL]["chips"] == 4
    names = [x["name"] for x in manifest_data["per_layer"]]
    assert names.index(metric) > names.index("decode_bytes_roofline_pct_pangu")
    read = m.load_module("layer_metrics", metric + ".py").read
    assert read({"workload": "no-such-cell", "trace": None, "chips": 4,
                 "step_ms": []}) is None
    assert read({"workload": "no-such-cell", "trace": {"busy_s": 1.0},
                 "chips": 4, "step_ms": [1.0]}) is None
