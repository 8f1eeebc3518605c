"""Every cell's code path end to end at toy size, the platform forced to the
host by ``--rehearse`` (toy configuration, mix and limit files live under
``tests/benchmark/toy`` and are found by name like any other)."""
import pytest

from bench_util import last_line, run_cell

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _check_line(line, chips):
    assert CONTRACT_KEYS <= set(line)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None


@pytest.mark.parametrize("workload,chips,rate", [
    ("toy-train-lm", 1, "train_tokens_per_s"),
    ("toy-decode", 1, "decode_tokens_per_s"),
    ("toy-train-dp4", 4, "train_tokens_per_s")])
def test_cell_end_to_end(workload, chips, rate):
    rc, out, err = run_cell(workload, seed=2**31 + 5, seconds=1.5)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    _check_line(line, chips)
    assert line["metrics"][rate]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("workload,chips,has", [
    ("toy-train-lm", 1, {"step_ms_p50_lm", "compiles_in_window_lm",
                         "device_idle_share_lm"}),
    ("toy-decode", 1, {"gen_itl_p50_ms", "gen_ttft_p50_ms",
                       "gen_lanes_per_step", "gen_prefill_share_pct",
                       "compiles_in_window_gen", "device_idle_share_gen"}),
    ("toy-train-dp4", 4, {"allreduce_ms_per_step_lm",
                          "allreduce_exposed_ms_per_step_lm"})])
def test_cell_traced(workload, chips, has):
    rc, out, err = run_cell(workload, seed=3, seconds=1.5, trace=1)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    _check_line(line, chips)
    assert has <= set(line["metrics"])
    # a rehearsal on the host never reports a share of a device's peak
    assert not any("mfu" in k or "roofline" in k for k in line["metrics"])
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    compiles = [v["value"] for k, v in line["metrics"].items()
                if k.startswith("compiles_in_window")]
    assert compiles == [0.0]


def test_decode_prints_its_in_window_counts():
    rc, out, err = run_cell("toy-decode", seed=4, seconds=1.5)
    assert rc == 0, err[-2000:]
    window = [l for l in out.splitlines() if l.startswith("[window]")]
    assert window and all(w in window[0] for w in
                          ("decode steps", "prefills", "tokens",
                           "requests finished", "inter-token gaps"))
    assert any(l.startswith("[check] served_token_logit_gap")
               for l in out.splitlines())


def test_no_tpu_and_no_rehearsal_fails_without_a_result():
    rc, out, err = run_cell("toy-train-lm", rehearse=False)
    assert rc != 0
    assert "no TPU" in err
    assert not any(l.startswith("{") for l in out.splitlines())


def test_unknown_workload_fails():
    rc, out, _ = run_cell("no-such-cell")
    assert rc != 0 and not out.strip()
