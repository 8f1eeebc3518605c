"""``lfm2moe-decode-closed16`` rehearsed at toy size on the host: the
short-convolution / attention family with routed experts through
``perfbench/run.py`` as the driver runs it.  The toy configuration and limits
live under ``tests/benchmark/toy`` and are found by name (the toy mix is the
hybrid cell's: the two cells run one traffic); the manifest is made here from
BENCHMARK.json itself (the cell, its configuration and its metrics renamed),
as ``test_cell_g4h_cpu.py`` makes its own.

The cell reports the accepted decode metrics, whose ``workloads`` it was
appended to, and the four of the expert layers (``perfbench/harness/moe.py``,
``layer_metrics/*_lfm2.py``), which are its own: the manifest made here takes
all of them from BENCHMARK.json."""
import functools
import json
import os

from bench_util import ROOT, last_line, run_cell

from perfbench.harness import manifest as mf

CELL, CONFIG = "lfm2moe-decode-closed16", "lfm2-8b-a1b"
TOY_CELL, TOY_CONFIG = "toy-lfm2-decode", "toy-lfm2"


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
    # the hybrid cell's own traffic file, not a copy
    assert cell["traffic"] == \
        m.workloads["g4hmicro-decode-closed16"]["traffic"]
    assert m.configs[CONFIG]["reduced"] == ["num_hidden_layers"]
    assert CELL in m.end_to_end["decode_tokens_per_s"]["workloads"]
    cfg = m.config(CONFIG)
    assert cfg["n_layer"] == cfg["num_hidden_layers"] == 16
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers_published"] == 24
    # four whole periods of the pattern, both dense layers, every expert
    assert cfg["layer_types"][:16] == ["conv", "conv", "full_attention",
                                       "conv"] * 4
    assert (cfg["num_dense_layers"], cfg["num_experts"],
            cfg["num_experts_per_tok"]) == (2, 32, 4)
    assert set(m.load_json("limits", CELL + ".json")["limits"]) == {
        "served_token_logit_gap", "cold_runs_in_window"}
    # the cell reports the 16 decode metrics the hybrid cell reports

    def shared(cell):
        return [n for n in m.cell_metrics("per_layer", cell,
                                          moves={"decode_tokens_per_s"})
                if m.per_layer[n]["workloads"] != [cell]]

    assert len(shared(CELL)) == 16
    assert shared(CELL) == shared("g4hmicro-decode-closed16")
    # and four of its own, whose readers are there by name
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [CELL]]
    assert len(own) == 4
    for x in own:
        assert x["name"].endswith("_lfm2")
        # a share of a peak is the one that is better higher
        assert x["better"] == ("higher" if "roofline" in x["name"]
                               else "lower")
        assert callable(m.load_module("layer_metrics",
                                      x["name"] + ".py").read)


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog row's ``config``, under the same key, but
    the one key BENCHMARK.json lists under ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        import pytest

        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    cfg = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(CONFIG)
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differs == {"num_hidden_layers"}
    assert cfg["num_hidden_layers_published"] == \
        row["config"]["num_hidden_layers"]


def test_the_parameter_count_is_the_files():
    """5.40 B parameters in 16 layers, 8.34 B in the published 24."""
    from perfbench.models import lfm2_moe_lm as ref

    cfg = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(CONFIG)
    assert round(ref.n_params(cfg, 16) / 1e9, 2) == 5.40
    assert round(ref.n_params(cfg, 24) / 1e9, 2) == 8.34


def test_cell_end_to_end(tmp_path_factory):
    path = _manifest(str(tmp_path_factory.mktemp("lfm2")))
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
    path = _manifest(str(tmp_path_factory.mktemp("lfm2")))
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
            "compiles_in_window_gen", "moe_experts_hit_per_step_lfm2"} <= \
        set(got)
    assert got["compiles_in_window_gen"]["value"] == 0.0
    # at most 4 lanes x 2 picks over 8 experts a layer, and at least 2
    assert 2 <= got["moe_experts_hit_per_step_lfm2"]["value"] <= 8
    # the host has no scopes and no module line: a share of device time or
    # of a roofline is left out, not faked
    assert not any("share_pct" in k and "moe" in k for k in got)
    assert not any("mfu" in k or "roofline" in k for k in got)


def test_readers_find_nothing_in_a_program_without_experts():
    """On the parent's program the new readers return None and do not raise:
    a run's info without a trace, and one whose trace has no such span."""
    from perfbench.harness import moe

    for read in (moe.moe_experts_share_pct, moe.moe_experts_ms_per_step,
                 moe.moe_experts_hit_per_step, moe.decode_bytes_roofline_pct):
        assert read({"trace": None, "workload": "x"}) is None
        assert read({"trace": {"busy_s": 1.0}, "workload": "no-such"}) is None


def test_the_step_bytes_are_the_shapes():
    """``decode_step_bytes`` at the cell's sizes: 0.93 GB of weights outside
    the experts, and with every expert hit the whole 10.8 GB."""
    from perfbench.harness import moe
    from perfbench.models import lfm2_moe_lm as ref

    cfg = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(CONFIG)
    shapes = ref.param_shapes(cfg, 16)
    dense = moe.decode_step_bytes(cfg, shapes, 0, 0, 16, 0)
    assert round(dense / 1e9, 2) == 0.93
    one = 3 * 2048 * 1792 * 2
    full = moe.decode_step_bytes(cfg, shapes, 14 * 32 * one, 0, 16, 0)
    assert full == 2 * ref.n_params(cfg, 16)
    # 16 lanes of 300 tokens: 19 pages each, 8 KB a token; 98 KB of tails
    more = moe.decode_step_bytes(cfg, shapes, 0, 16 * 19, 16,
                                 16 * 12 * 2 * 2048 * 2) - dense
    assert more == 16 * 19 * 16 * 8192 + 2 * 16 * 98304
