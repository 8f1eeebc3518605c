"""``perfbench/harness/spans.py``: on hand-made traces and on a recorded one.

The recorded trace (``perfbench/testdata/small-tpu-spans.xplane.pb``, one TPU
v5e chip, PR 25, my chip run) holds four steps of a small jitted program
(``toy_step``: a matmul under ``block_a``, the flash kernels' forward and
backward under ``block_b``, an update under ``optimizer``), each inside
``toy:step`` with the children ``toy:h2d`` / ``toy:forward`` / ``toy:d2h``
recorded through ``mxnet_tpu.profiler.Frame``.
"""
import json
import os

import pytest

from perfbench.harness import spans
from perfbench.harness.spans import Op, Span, Trace

from bench_util import ROOT

RECORDED = os.path.join(ROOT, "perfbench", "testdata",
                        "small-tpu-spans.xplane.pb")
NEW_METRICS = [
    "gen_step_ms_p50", "gen_pool_h2d_ms_per_step",
    "gen_pool_d2h_ms_per_step",
    "gen_sched_ms_per_step", "gen_prefill_ms_p50", "gen_queue_wait_p50_ms",
    "gen_device_ms_per_step", "gen_paged_attn_share_pct",
    "step_host_ms_p50_lm", "step_host_ms_p50_img",
    "flash_fwd_ms_per_step_lm", "flash_bwd_dq_ms_per_step_lm",
    "flash_bwd_dkv_ms_per_step_lm", "optimizer_share_pct_lm",
    "optimizer_share_pct_img"]


def _decode_trace():
    """Two decode steps of 10 s on thread 1 with their pool spans, one
    admission with a prefill before them, a span of another thread, and a
    device that runs the lane program 1 s inside each step's d2h and the
    prefill's program 1 s inside the prefill."""
    sp = [Span("bench:window", 0.0, 30.0, 0, {}),
          Span("gen:admit", 1.0, 4.0, 1, {"n": 1}),
          Span("gen:prefill", 1.5, 3.5, 1, {"bucket": 64}),
          Span("gen:queued", 1.5, 1.5, 1, {"sid": 7, "wait_ms": 12.5}),
          Span("serve:generate", 0.5, 29.0, 2, {"sid": 7})]
    ops = []
    for t in (5.0, 15.0):
        sp += [Span("gen:step", t, t + 10.0, 1, {"lanes": 1}),
               Span("gen:pool_h2d", t + 0.5, t + 2.5, 1, {}),
               Span("gen:forward", t + 2.5, t + 3.0, 1, {}),
               Span("Executor.forward", t + 2.6, t + 2.9, 1, {}),
               Span("gen:pool_d2h", t + 3.0, t + 7.0, 1, {})]
        ops += [Op("fusion.1:fusion", t + 3.0, t + 3.6,
                   "jit(decode_b8)/layer0_attn/paged_attention/gather"),
                Op("fusion.2:fusion", t + 3.6, t + 4.0,
                   "jit(decode_b8)/layer0_fc1/dot_general")]
    ops.append(Op("fusion.9:fusion", 2.0, 3.0,
                  "jit(prefill_L64)/layer0_fc1/dot_general"))
    sp.sort(key=lambda s: (s.start, -s.end))
    runs = [("jit_prefill_L64(22)", 2.0, 3.0), ("jit_decode_b8(11)", 8.0, 9.0),
            ("jit_decode_b8(11)", 18.0, 19.0)]
    return Trace(sp, {"/device:TPU:0": sorted(ops, key=lambda o: o.start)},
                 (0.0, 30.0), {"/device:TPU:0": runs})


def test_self_time_is_the_span_less_what_its_threads_children_cover():
    tr = _decode_trace()
    step = spans.named(tr, "gen:step")[0]
    # 10 s less h2d 2, forward 0.5 (its own child counts once), d2h 4; the
    # handler thread's serve:generate is no child
    assert spans.self_s(tr, step) == pytest.approx(3.5)
    admit = spans.named(tr, "gen:admit")[0]
    assert spans.self_s(tr, admit) == pytest.approx(1.0)


def test_children_are_found_on_the_parents_thread_only():
    tr = _decode_trace()
    steps = spans.named(tr, "gen:step")
    assert len(spans.inside(tr, steps, "gen:pool_d2h")) == 2
    assert spans.inside(tr, steps, "serve:generate") == []
    assert {s.name for s in spans.inside(tr, steps[:1])} == {
        "gen:pool_h2d", "gen:forward", "Executor.forward", "gen:pool_d2h"}


def test_device_time_by_scope_and_by_program():
    tr = _decode_trace()
    assert spans.busy_s(tr) == pytest.approx(3.0)
    # a program's runs: whole, by the name on the module line
    assert spans.module_runs(tr, "jit_decode_b") == {
        "/device:TPU:0": [(8.0, 9.0), (18.0, 19.0)]}
    assert spans.module_runs(tr, "jit_prefill_L") == {
        "/device:TPU:0": [(2.0, 3.0)]}
    assert spans.module_runs(tr, "jit_fused_step") == {}
    assert spans.module_runs(tr._replace(modules=None), "jit_decode_b") == {}
    paged = spans.in_scope(r"paged_attention(?:_window)?")
    assert spans.op_s(tr, paged) == pytest.approx(1.2)
    assert spans.op_s(tr, spans.in_scope("layer0_fc1")) == pytest.approx(1.8)
    assert spans.op_s(tr, spans.in_scope("fc1")) == 0.0  # whole components


def test_scope_components_survive_the_transformations_names():
    op = Op("fusion.3:fusion", 0, 1,
            "jit(fused_step)/transpose(jvp(layer3_fc2))/dot_general")
    assert spans.in_scope("layer3_fc2")(op)
    assert not spans.in_scope("layer3_fc")(op)
    assert spans.in_scope("optimizer")(
        Op("f", 0, 1, "jit(fused_step)/optimizer/mul"))
    assert not spans.in_scope("optimizer")(Op("f", 0, 1, None))


@pytest.mark.parametrize("name,kernel,hit", [
    ("flash_fwd.12:custom-call", "flash_fwd", True),
    ("jvp_flash_fwd_.1:custom-call", "flash_fwd", True),
    ("flash_bwd_dkv.8:custom-call", "flash_bwd_dq", False),
    ("flash_bwd_dq.8:custom-call", "flash_bwd_dq", True),
    ("jvp_flash_bwd_dkv_.1:custom-call", "flash_bwd_dkv", True),
    ("flash_fwd_fusion.3:fusion", "flash_fwd", False),
    ("custom-call.9:custom-call", "flash_fwd", False)])
def test_a_kernels_events_are_found_by_its_name(name, kernel, hit):
    assert spans.kernel(kernel)(Op(name, 0, 1, None)) is hit


def test_only_program_spans_are_kept_from_the_host_plane():
    for name in ("gen:step", "bench:window", "Module.update",
                 "Executor.fused_step:pack", "Module.fit:epoch3",
                 "kv.rpc.push"):
        assert spans.NAME_RE.match(name), name
    for name in ("Transpose::ExecuteChunk", "tpu::System::Execute=>Done",
                 "PjitFunction(fused_step)", "H2D Dispatch",
                 "$engine.py:12 _loop", "PJRT_LoadedExecutable_Execute",
                 "dot_general.66", "fusion.3"):
        assert not spans.NAME_RE.match(name), name


def _with(monkeypatch, tr):
    monkeypatch.setattr(spans, "of_run", lambda info: tr)


def test_the_decode_steps_parts_add_up(monkeypatch):
    tr = _decode_trace()
    _with(monkeypatch, tr)
    info = {}
    assert spans.gen_step_ms_p50(info) == pytest.approx(10e3)
    h2d = spans.gen_pool_h2d_ms_per_step(info)
    d2h = spans.gen_pool_d2h_ms_per_step(info)
    sched = spans.gen_sched_ms_per_step(info)
    assert (h2d, d2h) == (pytest.approx(2e3), pytest.approx(4e3))
    # per step: forward 0.5 + self 3.5, and half of the admission's 1.0
    assert sched == pytest.approx(4.5e3)
    prefill = 1e3 * 2.0 / 2
    engine = 1e3 * (10.0 + 10.0 + 3.0) / 2
    assert h2d + d2h + sched + prefill == pytest.approx(engine)
    assert spans.gen_prefill_ms_p50(info) == pytest.approx(2e3)
    assert spans.gen_queue_wait_p50_ms(info) == 12.5
    assert spans.gen_device_ms_per_step(info) == pytest.approx(1e3)
    assert spans.gen_paged_attn_share_pct(info) == pytest.approx(40.0)


def test_training_readers(monkeypatch):
    sp = [Span("bench:window", 0.0, 10.0, 0, {})]
    ops = []
    for i, t in enumerate((1.0, 4.0, 7.0)):
        sp += [Span("Module.forward_backward", t, t + 0.1, 0, {}),
               Span("Module.update", t + 0.1, t + 0.5 + 0.1 * i, 0, {})]
        ops += [Op("flash_fwd.%d:custom-call" % i, t + 1.0, t + 1.2, None),
                Op("flash_bwd_dq.%d:custom-call" % i, t + 1.2, t + 1.3, None),
                Op("fusion.%d:fusion" % i, t + 1.3, t + 2.0,
                   "jit(fused_step)/optimizer/sub")]
    _with(monkeypatch, Trace(sp, {"/device:TPU:0": ops}, (0.0, 10.0)))
    info = {"step_ms": [1, 2, 3]}
    assert spans.step_host_ms_p50(info) == pytest.approx(600.0)
    assert spans.flash_fwd_ms_per_step(info) == pytest.approx(200.0)
    assert spans.flash_bwd_dq_ms_per_step(info) == pytest.approx(100.0)
    assert spans.flash_bwd_dkv_ms_per_step(info) is None
    assert spans.optimizer_share_pct(info) == pytest.approx(70.0)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_reader_reads_nothing_without_a_trace(metric, manifest_data):
    from perfbench.harness.manifest import Manifest

    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"), root=ROOT)
    read = m.load_module("layer_metrics", metric + ".py").read
    # an untraced run, and a traced one whose trace directory is not there
    assert read({"workload": "no-such-cell", "trace": None,
                 "step_ms": []}) is None
    assert read({"workload": "no-such-cell", "trace": {"busy_s": 1.0},
                 "step_ms": [1.0]}) is None
    entry = m.per_layer[metric]
    assert entry["source"] in ("program_span", "device_trace")
    assert entry["workloads"]


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    """The parent of the PR that added them: device operations, the
    benchmark's own spans, nothing of the program's."""
    bare = Trace([Span("bench:window", 0.0, 4.0, 0, {}),
                  Span("bench:step", 0.0, 2.0, 0, {})],
                 {"/device:TPU:0": [Op("jvp__.2:custom-call", 0.5, 1.0,
                                       None),
                                    Op("fusion.1:fusion", 1.0, 2.0, None)]},
                 (0.0, 4.0))
    _with(monkeypatch, bare)
    info = {"step_ms": [1.0]}
    for name in ("gen_step_ms_p50", "gen_pool_h2d_ms_per_step",
                 "gen_pool_d2h_ms_per_step",
                 "gen_sched_ms_per_step", "gen_prefill_ms_p50",
                 "gen_queue_wait_p50_ms", "gen_device_ms_per_step",
                 "gen_paged_attn_share_pct", "step_host_ms_p50",
                 "flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
                 "flash_bwd_dkv_ms_per_step", "optimizer_share_pct"):
        assert getattr(spans, name)(info) is None, name


def test_a_step_in_flight_is_timed_by_its_program_not_by_its_span(
        monkeypatch):
    """Since PR 31 a step runs on while the engine thread has left its
    ``gen:step`` and is in ``gen:admit``: the lane program's 3 s run from 3.5
    to 6.5, half a second of it inside the step that dispatched it."""
    sp = [Span("bench:window", 0.0, 20.0, 0, {}),
          Span("gen:step", 0.0, 4.0, 1, {"inflight": 1}),
          Span("gen:admit", 4.0, 9.0, 1, {"n": 1}),
          Span("gen:prefill", 4.5, 8.5, 1, {"bucket": 64}),
          Span("gen:step", 9.0, 13.0, 1, {"inflight": 1})]
    ops = [Op("fusion.1:fusion", 3.5, 6.5, "jit(decode_b8)/layer0_fc1/dot"),
           Op("fusion.1:fusion", 6.5, 8.0, "jit(prefill_L64)/layer0_fc1/dot"),
           Op("fusion.1:fusion", 12.5, 15.5, "jit(decode_b8)/layer0_fc1/dot"),
           Op("fusion.1:fusion", 19.5, 22.5, "jit(decode_b8)/layer0_fc1/dot")]
    runs = [("jit_decode_b8(11)", 3.5, 6.5), ("jit_prefill_L64(22)", 6.5, 8.0),
            ("jit_decode_b8(11)", 12.5, 15.5),
            ("jit_decode_b8(11)", 19.5, 22.5)]
    tr = Trace(sp, {"/device:TPU:0": ops}, (0.0, 20.0),
               {"/device:TPU:0": runs})
    _with(monkeypatch, tr)
    # every run that starts in the window, whole: the last one ends after it
    assert spans.gen_device_ms_per_step({}) == pytest.approx(3e3)
    # what the steps' spans hold of it: 0.5 s each
    inside = sum(min(o.end, s.end) - max(o.start, s.start)
                 for s in spans.named(tr, "gen:step") for o in ops
                 if min(o.end, s.end) > max(o.start, s.start))
    assert inside / 2 == pytest.approx(0.5)
    # a trace without the module line (the host platform) reads nothing
    _with(monkeypatch, tr._replace(modules=None))
    assert spans.gen_device_ms_per_step({}) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (number, int or bytes) fields."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


STAT_IDS = {"program_id": 1, "tf_op": 2}


def _xplane(path, metas, modules, ops):
    """Write an ``.xplane.pb`` of one TPU plane: ``metas`` {id: (name,
    program id or None, scope or None)}, ``modules`` and ``ops`` [(metadata
    id, start ps, duration ps)] for the two lines."""
    plane = [(1, 1), (2, b"/device:TPU:0")]
    for name, sid in STAT_IDS.items():
        plane.append((5, _msg((1, sid), (2, _msg((1, sid),
                                                 (2, name.encode()))))))
    for mid, (name, program, scope) in metas.items():
        meta = [(1, mid), (2, name.encode())]
        if program is not None:
            meta.append((5, _msg((1, STAT_IDS["program_id"]), (3, program))))
        if scope is not None:
            meta.append((5, _msg((1, STAT_IDS["tf_op"]),
                                 (5, scope.encode()))))
        plane.append((4, _msg((1, mid), (2, _msg(*meta)))))
    for lid, (name, events) in enumerate(
            (("XLA Modules", modules), ("XLA Ops", ops)), 1):
        plane.append((3, _msg((1, lid), (2, name.encode()), (3, 0), *[
            (4, _msg((1, mid), (2, start), (3, dur)))
            for mid, start, dur in events])))
    with open(path, "wb") as f:
        f.write(_msg((1, _msg(*plane))))


def test_two_programs_that_share_an_operations_name_each_keep_their_scope(
        tmp_path):
    """One model's decode and prefill programs both hold a ``fusion.73``
    (letter for letter the same text) under different scopes, and a
    ``copy-done`` that only the prefill's program gives a scope."""
    fusion = "%fusion.73 = bf16[16,2048]{1,0} fusion(bf16[16,2048]{1,0} %p)"
    done = "%copy-done = bf16[16,2048]{1,0} copy-done(%copy-start)"
    metas = {1: ("jit_decode_b16(11)", None, None),
             2: ("jit_prefill_L64(22)", None, None),
             3: (fusion, 11,
                 "jit(decode_b16)/layer5_attn/paged_attention/gather:"),
             4: (fusion, 22, "jit(prefill_L64)/layer0_ssm/ssm_scan/mul:"),
             5: (done, 11, None),
             6: (done, 22, "jit(prefill_L64)/layer0_ssm/ssm_scan/copy:"),
             7: ("%fusion.9 = bf16[1]{0} fusion()", 11,
                 "jit(decode_b16)/lm_head/dot_general:")}
    us = 1000000  # picoseconds
    path = str(tmp_path / "two.xplane.pb")
    _xplane(path, metas,
            modules=[(1, 0, 10 * us), (2, 20 * us, 10 * us),
                     (1, 40 * us, 10 * us)],
            ops=[(3, 0, 4 * us), (5, 4 * us, 2 * us), (7, 6 * us, 4 * us),
                 (4, 20 * us, 7 * us), (6, 27 * us, 3 * us),
                 (3, 40 * us, 4 * us),
                 # an operation outside every run: its program is not known
                 (3, 60 * us, us), (7, 61 * us, us)])
    by_name = spans.op_scopes(path)["/device:TPU:0"]
    assert by_name[fusion] == {
        11: "jit(decode_b16)/layer5_attn/paged_attention/gather",
        22: "jit(prefill_L64)/layer0_ssm/ssm_scan/mul"}
    tr = spans.load(path)
    (ops,) = tr.devices.values()
    assert [(o.name, o.scope) for o in ops] == [
        ("fusion.73:fusion",
         "jit(decode_b16)/layer5_attn/paged_attention/gather"),
        ("copy-done:copy-done", None),
        ("fusion.9:fusion", "jit(decode_b16)/lm_head/dot_general"),
        ("fusion.73:fusion", "jit(prefill_L64)/layer0_ssm/ssm_scan/mul"),
        ("copy-done:copy-done", "jit(prefill_L64)/layer0_ssm/ssm_scan/copy"),
        ("fusion.73:fusion",
         "jit(decode_b16)/layer5_attn/paged_attention/gather"),
        # outside every run a shared name has no scope, a name of one
        # program's keeps it
        ("fusion.73:fusion", None),
        ("fusion.9:fusion", "jit(decode_b16)/lm_head/dot_general")]
    assert tr.modules == {"/device:TPU:0": [
        ("jit_decode_b16(11)", 0.0, pytest.approx(10e-6)),
        ("jit_prefill_L64(22)", pytest.approx(20e-6), pytest.approx(30e-6)),
        ("jit_decode_b16(11)", pytest.approx(40e-6), pytest.approx(50e-6))]}
    # so both scopes' readers find their operations: 8 us and 10 us
    assert spans.op_s(tr, spans.in_scope(
        r"paged_attention(?:_window)?")) == pytest.approx(8e-6)
    assert spans.op_s(tr, spans.in_scope("ssm_scan")) == pytest.approx(10e-6)


def test_the_manifest_only_gained_entries(manifest_data):
    """PR 25's metrics stand together in their order; later PRs' entries
    follow them at the end of the list."""
    names = [m["name"] for m in manifest_data["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    layers = {m["layer"] for m in manifest_data["per_layer"]
              if m["name"] not in NEW_METRICS}
    assert {m["layer"] for m in manifest_data["per_layer"]} == layers
    assert len(json.dumps(manifest_data)) < 64 * 1024


# ---------------------------------------------------------------------------
# the recorded trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace in this checkout")
    assert os.path.getsize(RECORDED) < 300 * 1024
    return spans.load(RECORDED)


def test_recorded_program_spans_with_their_stats(recorded):
    steps = spans.named(recorded, "toy:step")
    assert [s.stats for s in steps] == [
        {"i": i, "sids": "%d|%d" % (i, i + 1), "done": 1} for i in range(4)]
    assert recorded.window == (pytest.approx(0.045577179),
                               pytest.approx(0.068807339))
    assert spans.median_ms(steps) == pytest.approx(2.00339, rel=1e-4)
    h2d = spans.inside(recorded, steps, "toy:h2d")
    assert [s.stats for s in h2d] == [{"bytes": 2097152}] * 4
    assert spans.inside(recorded, steps, "toy:d2h")[0].stats == \
        {"bytes": 131072}


def test_recorded_self_time_and_per_step_totals(recorded):
    steps = spans.named(recorded, "toy:step")
    per_step = {n: 1e3 * spans.total_s(spans.inside(recorded, steps, n))
                / len(steps)
                for n in ("toy:h2d", "toy:forward", "toy:d2h")}
    assert per_step == {"toy:h2d": pytest.approx(0.346278, rel=1e-4),
                        "toy:forward": pytest.approx(0.411660, rel=1e-4),
                        "toy:d2h": pytest.approx(1.432270, rel=1e-4)}
    self_ms = [1e3 * spans.self_s(recorded, s) for s in steps]
    assert self_ms == [pytest.approx(x, abs=1e-4) for x in
                       (0.039509, 0.016169, 0.021250, 0.017540)]
    # a step is its self time and its children
    for s, own in zip(steps, self_ms):
        kids = spans.total_s(spans.inside(recorded, [s]))
        assert 1e3 * (s.end - s.start) == pytest.approx(own + 1e3 * kids)


def test_recorded_device_time_and_the_programs_runs(recorded):
    busy = spans.busy_s(recorded)
    assert busy == pytest.approx(6.0782e-05, rel=1e-4)
    # the module line: one run of ``jit_toy_step`` a step, each the whole of
    # its operations (the device plane's clock runs 1 to 1.5 ms ahead of the
    # host's here: a run appears before the dispatch that started it, so no
    # host span bounds it)
    (runs,) = spans.module_runs(recorded, "jit_toy_step").values()
    assert [round(1e6 * (b - a), 2) for a, b in runs] == \
        [15.49, 15.48, 15.51, 15.47]
    assert sum(b - a for a, b in runs) == pytest.approx(busy, rel=0.03)
    (names,) = [{n for n, _, _ in v} for v in recorded.modules.values()]
    assert names == {"jit_toy_step(2712319679022519215)"}
    assert spans.module_runs(recorded, "jit_decode_b") == {}


def test_a_run_that_starts_before_the_window_is_not_the_windows():
    """PR 24's recorded trace: four runs of ``jit_f``, the first of them
    before ``bench:window`` opens."""
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace in this checkout")
    old = spans.load(os.path.join(ROOT, "perfbench", "testdata",
                                  "small-tpu.xplane.pb"))
    (every,) = old.modules.values()
    assert len(every) == 4 and every[0][1] < old.window[0]
    (runs,) = spans.module_runs(old, "jit_f").values()
    assert runs == [(a, b) for _, a, b in every[1:]]


def test_recorded_scopes_and_kernel_names(recorded):
    (ops,) = recorded.devices.values()
    scope = {o.name: o.scope for o in ops}
    assert scope["fusion:fusion"] == "jit(toy_step)/optimizer/dot_general"
    assert scope["jvp_flash_fwd_.1:custom-call"] == \
        "jit(toy_step)/block_b/jvp(flash_fwd)/pallas_call"
    assert scope["jvp_flash_bwd_dq_.1:custom-call"].endswith(
        "jvp(flash_bwd_dq)/pallas_call")
    assert scope["copy-start:copy-start"] is None
    assert spans.op_s(recorded, spans.in_scope("optimizer")) == \
        pytest.approx(2.7067e-05, rel=1e-3)
    assert spans.op_s(recorded, spans.in_scope("block_b")) == \
        pytest.approx(3.0892e-05, rel=1e-3)
    per_kernel = [spans.op_s(recorded, spans.kernel(k)) for k in
                  ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")]
    assert per_kernel == [pytest.approx(x, rel=1e-3) for x in
                          (1.4722e-05, 3.947e-06, 8.646e-06)]
    # the three kernels are the trace's custom calls, every one
    from perfbench.harness import readers

    every = sum(o.end - o.start for o in ops
                if readers.CUSTOM_CALL_RE.search(o.name))
    assert sum(per_kernel) == pytest.approx(every)


def test_scopes_are_decoded_from_the_files_metadata():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace in this checkout")
    by_plane = spans.op_scopes(RECORDED)
    assert list(by_plane) == ["/device:TPU:0"]
    full = [k for k in by_plane["/device:TPU:0"] if k.startswith("%fusion =")]
    # under the id of the program, which the module line's name ends in
    assert full and by_plane["/device:TPU:0"][full[0]] == {
        2712319679022519215: "jit(toy_step)/optimizer/dot_general"}
    # an operation the program gave no scope is there, with none
    done = [k for k in by_plane["/device:TPU:0"]
            if k.startswith("%copy-done =")]
    assert done and by_plane["/device:TPU:0"][done[0]] == {
        2712319679022519215: None}
    # the trace PR 24 recorded has scopes too, of an unscoped program
    old = spans.op_scopes(os.path.join(ROOT, "perfbench", "testdata",
                                       "small-tpu.xplane.pb"))
    assert {3647712631135220326: "jit(f)/dot_general"} in \
        old["/device:TPU:0"].values()
