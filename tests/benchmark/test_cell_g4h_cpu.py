"""``g4hmicro-decode-closed16`` rehearsed at toy size on the host: the
hybrid state-space / attention family through ``perfbench/run.py`` as the
driver runs it.  The toy configuration, mix and limits live under
``tests/benchmark/toy`` and are found by name; the manifest is made here from
BENCHMARK.json itself (the cell, its configuration and its metrics renamed),
as ``test_add_by_files.py`` makes its own.

The cell reports the accepted decode metrics, whose ``workloads`` it was
appended to, and the four of the state-space layers
(``perfbench/harness/ssm.py``, ``layer_metrics/ssm_*_g4h.py``), which are its
own: the manifest made here takes all of them from BENCHMARK.json."""
import functools
import json
import os

from bench_util import ROOT, last_line, run_cell

from perfbench.harness import manifest as mf

CELL, CONFIG = "g4hmicro-decode-closed16", "granite-4.0-h-micro"
TOY_CELL, TOY_CONFIG = "toy-g4h-decode", "toy-g4h"


@functools.lru_cache(maxsize=None)
def _manifest(tmp):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["paths"] = ["tests/benchmark/toy", "perfbench"]
    m["run_seconds"] = 2
    m["configs"] = [dict(c, name=TOY_CONFIG, file="tests/benchmark/toy/"
                         "configs/%s.json" % TOY_CONFIG)
                    for c in m["configs"] if c["name"] == CONFIG]
    m["workloads"] = [dict(w, name=TOY_CELL, config=TOY_CONFIG,
                           traffic="toy-" + w["traffic"])
                      for w in m["workloads"] if w["name"] == CELL]
    for section in ("end_to_end", "per_layer"):
        kept = []
        for metric in m[section]:
            if "workloads" in metric:
                if CELL not in metric["workloads"]:
                    continue
                metric["workloads"] = [TOY_CELL]
            kept.append(metric)
        m[section] = kept
    assert mf.validate(m) == []
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


def test_the_cell_and_its_files_are_in_the_manifest():
    m = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert mf.validate(m.data) == []
    cell = m.workloads[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert m.configs[CONFIG]["reduced"] == []
    assert CELL in m.end_to_end["decode_tokens_per_s"]["workloads"]
    cfg = m.config(CONFIG)
    assert len(cfg["layer_types"]) == cfg["n_layer"] == 40
    assert m.load_json("traffic", cell["traffic"] + ".json")["clients"] == 16
    assert set(m.load_json("limits", CELL + ".json")["limits"]) == {
        "served_token_logit_gap", "cold_runs_in_window"}
    # the cell reports per-layer metrics of its end-to-end metric
    assert m.cell_metrics("per_layer", CELL, moves={"decode_tokens_per_s"})
    # and four of its own, whose readers are there by name
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [CELL]]
    assert len(own) == 4
    for x in own:
        assert x["name"].startswith("ssm_") and x["name"].endswith("_g4h")
        assert (x["better"], x["moves"]) == ("lower", "decode_tokens_per_s")
        assert callable(m.load_module("layer_metrics",
                                      x["name"] + ".py").read)


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog row's ``config``, under the same key."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        import pytest

        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    cfg = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert {k: cfg[k] for k in row["config"]} == row["config"]


def test_cell_end_to_end(tmp_path_factory):
    path = _manifest(str(tmp_path_factory.mktemp("g4h")))
    rc, out, err = run_cell(TOY_CELL, seed=2**31 + 7, seconds=1.5,
                            manifest=path)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["decode_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert any(l.startswith("[check] served_token_logit_gap")
               for l in out.splitlines())


def test_cell_traced(tmp_path_factory):
    path = _manifest(str(tmp_path_factory.mktemp("g4h")))
    rc, out, err = run_cell(TOY_CELL, seed=5, seconds=1.5, trace=1,
                            manifest=path)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # what needs no device peak and no device scope: spans, counters, stamps
    assert {"gen_step_ms_p50", "gen_sched_ms_per_step", "gen_prefill_ms_p50",
            "gen_pool_h2d_ms_per_step", "gen_pool_d2h_ms_per_step",
            "gen_queue_wait_p50_ms", "gen_itl_p50_ms", "gen_ttft_p50_ms",
            "gen_lanes_per_step", "gen_prefill_share_pct",
            "device_idle_share_gen", "peak_hbm_gb_gen",
            "compiles_in_window_gen", "ssm_state_gb_per_step_g4h"} <= \
        set(got)
    assert got["compiles_in_window_gen"]["value"] == 0.0
    # 4 lanes of 8 x 16 x 16 float32 + 3 x 160 bfloat16, 4 state-space layers
    per_lane = 4 * (8 * 16 * 16 * 4 + 3 * 160 * 2)
    assert 0 < got["ssm_state_gb_per_step_g4h"]["value"] <= 4 * per_lane / 1e9
    # the host has no scopes: a share of device time is left out, not faked
    assert not any("share_pct" in k and "ssm" in k for k in got)
    assert not any("mfu" in k or "roofline" in k for k in got)


def test_readers_find_nothing_in_a_program_without_the_state():
    """On the parent's program the new readers return None and do not raise:
    a run's info without a trace, and one whose trace has no such span."""
    from perfbench.harness import ssm

    for read in (ssm.ssm_step_share_pct, ssm.ssm_scan_share_pct,
                 ssm.ssm_step_ms_per_step, ssm.ssm_state_gb_per_step):
        assert read({"trace": None, "workload": "x"}) is None
        assert read({"trace": {"busy_s": 1.0}, "workload": "no-such"}) is None
