"""The trace reduction: on hand-made intervals and on a recorded trace."""
import os

import pytest

from perfbench.harness import readers, trace

from bench_util import ROOT

RECORDED = os.path.join(ROOT, "perfbench", "testdata",
                        "small-tpu.xplane.pb")


def _trace(devices, host=()):
    return {"devices": {k: sorted(v, key=lambda e: e[1])
                        for k, v in devices.items()},
            "host": sorted(host, key=lambda e: e[1])}


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_busy_idle_and_time_by_name():
    t = _trace({"/device:TPU:0": [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.0),
                                  ("fusion.1", 3.0, 1.0)]})
    r = trace.reduce(t, 0.0, 5.0)
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["by_name"]["fusion.1"] == pytest.approx(2.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(1.5),
                                              pytest.approx(1.0)]


def test_events_are_clipped_to_the_window_and_envelopes_left_out():
    t = _trace({"/device:TPU:0": [("while.3", 0.0, 10.0), ("fusion", 1.0, 2.0),
                                  ("fusion", 9.0, 2.0)]})
    r = trace.reduce(t, 0.0, 10.0)
    assert r["busy_s"] == pytest.approx(3.0)
    assert "while.3" not in r["by_name"]


def test_collective_time_and_the_part_nothing_hides():
    t = _trace({"/device:TPU:0": [("all-reduce.1", 0.0, 2.0),
                                  ("fusion", 1.0, 2.0)],
                "/device:TPU:1": [("all-reduce.1", 0.0, 2.0)]})
    r = trace.reduce(t, 0.0, 4.0)
    assert r["n_devices"] == 2
    assert r["collective_s"] == pytest.approx(2.0)
    assert r["collective_exposed_s"] == pytest.approx(1.5)  # (1 + 2) / 2
    assert r["busy_s"] == pytest.approx((3.0 + 2.0) / 2)


def test_gaps_are_named_by_the_most_specific_host_span():
    t = _trace({"/device:TPU:0": [("fusion", 0.0, 1.0), ("fusion", 3.0, 1.0)]},
               host=[("bench:window", 0.0, 4.0), ("$engine.py:1 _loop", 0.0, 4.0),
                     ("$engine.py:2 asnumpy", 1.1, 1.8)])
    r = trace.reduce(t)
    assert (r["window_s"], r["idle_gaps"][0][0]) == \
        (pytest.approx(4.0), "$engine.py:2 asnumpy")
    bare = trace.reduce(_trace({"/device:TPU:0": [("f", 0.0, 1.0),
                                                  ("f", 3.0, 1.0)]}))
    assert bare["idle_gaps"][0][0] == "unannotated"


# a gap from 1.0 to 3.0 between two operations; what the host recorded
@pytest.mark.parametrize("host,want", [
    # training: the program's innermost span, not the benchmark's around it
    ([("bench:step", 0.5, 3.0), ("Module.update", 0.9, 2.4),
      ("Executor.fused_step", 1.1, 1.6), ("Executor.fused_step:pack", 1.1, 0.3)],
     "Executor.fused_step"),
    ([("bench:step", 0.5, 3.0), ("Module.update", 0.9, 2.4)],
     "Module.update"),
    # decode: the span, then the runtime's innermost event inside it
    ([("serve:generate", 0.0, 9.0), ("gen:admit", 0.8, 2.6),
      ("gen:prefill", 0.9, 2.4), ("PjitFunction(prefill_L512)", 1.0, 2.2),
      ("CommonPjRtLoadedExecutable::ExecutePrepare", 1.2, 1.5)],
     "gen:prefill > CommonPjRtLoadedExecutable::ExecutePrepare"),
    # an event of another thread that the span does not hold is not its own
    ([("gen:step", 1.0, 1.8), ("ReadSyncFlag", 0.5, 2.0)], "gen:step"),
    # the benchmark's own span names a gap where no span of the program does
    ([("bench:step", 0.5, 3.0), ("PjitFunction(f)", 1.0, 1.5)], "bench:step"),
    # a program span that covers under half of it does not
    ([("bench:step", 0.5, 3.0), ("Module.update", 2.5, 1.0)], "bench:step"),
    # and the runtime's shortest event where neither is there
    ([("PjitFunction(f)", 0.9, 2.4), ("ExecutePrepare", 1.2, 1.5)],
     "ExecutePrepare")])
def test_gaps_are_named_by_what_the_program_was_doing(host, want):
    t = _trace({"/device:TPU:0": [("fusion", 0.0, 1.0), ("fusion", 3.0, 1.0)]},
               host=[("bench:window", 0.0, 4.0)] + host)
    assert trace.reduce(t)["idle_gaps"][0] == [want, pytest.approx(2.0)]


def test_a_gap_of_many_short_spans_is_named_by_what_fills_most_of_it():
    host = [("H2D Dispatch", 1.0 + 0.1 * i, 0.06) for i in range(20)] \
        + [("Linearize", 1.0 + 0.1 * i + 0.06, 0.02) for i in range(20)]
    t = _trace({"/device:TPU:0": [("f", 0.0, 1.0), ("f", 3.0, 1.0)]},
               host=host)
    assert trace.reduce(t)["idle_gaps"][0][0] == "mostly H2D Dispatch"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(_trace({}))


@pytest.mark.parametrize("by_name,want", [
    ({"jvp__.2:custom-call": 1.0, "jvp__.7:custom-call": 2.0,
      "fusion.3:fusion": 5.0}, 30.0),
    ({"fusion.3:fusion": 5.0}, None)])
def test_flash_share_is_every_custom_call_over_busy_time(by_name, want):
    got = readers.flash_share_pct({"trace": {"by_name": by_name,
                                             "busy_s": 10.0}})
    assert got == (pytest.approx(want) if want else None)


def test_flash_share_without_a_trace_reads_nothing():
    assert readers.flash_share_pct({"trace": None}) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_reduction_of_the_recorded_tpu_trace():
    """Four steps of a small jitted function between ``bench:`` spans,
    recorded on one TPU v5e chip (PR 24, my chip run)."""
    t = trace.load(RECORDED)
    assert list(t["devices"]) == ["/device:TPU:0"]
    r = trace.reduce(t)
    # the window is the host span bench:window, 18.1 ms there
    assert r["window_s"] == pytest.approx(0.018116878, rel=1e-6)
    assert r["busy_s"] == pytest.approx(3.4543e-05, rel=1e-3)
    assert r["idle_share"] == pytest.approx(0.99809, abs=1e-4)
    assert r["device_ops"][0][0] == "fusion.23:fusion"
    assert all(":" in name for name, _ in r["device_ops"])
    assert sum(r["by_name"].values()) >= r["busy_s"] * 0.999
    assert r["collective_s"] == 0.0 and r["n_devices"] == 1
    # the four long gaps are the four steps' sleeps, named by their span
    assert [name for name, _ in r["idle_gaps"][:4]] == ["bench:step"] * 4
    assert all(0.004 < g < 0.006 for _, g in r["idle_gaps"][:4])


def test_device_event_names_are_shortened_to_name_and_opcode():
    long = ('%jvp__.2 = (bf16[4,128,32]{2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[4,1,128]{2,1,0:T(1,128)S(1)}) custom-call(bf16[4,128,32]'
            '{2,1,0:T(8,128)(2,1)S(1)} %bitcast.389), '
            'custom_call_target="tpu_custom_call"')
    assert trace.short_name(long) == "jvp__.2:custom-call"
    assert trace.short_name("%while.3 = (s32[]) while(s32[] %x)") == \
        "while.3:while"
    assert trace.ENVELOPE_RE.match("while.3:while")
    assert not trace.ENVELOPE_RE.match("fusion.3:fusion")
    assert trace.short_name("dot_general.1") == "dot_general.1"
