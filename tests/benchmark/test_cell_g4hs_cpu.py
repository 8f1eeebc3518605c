"""``g4hsmall-decode-closed16`` rehearsed at toy size on the host: the hybrid
state-space / attention family WITH routed experts (a softmax over the picked
logits) beside a shared MLP through ``perfbench/run.py`` as the driver runs
it.  The toy configuration, reference stub and limits live under
``tests/benchmark/toy`` and are found by name (the toy mix is the latent
cell's: the two cells run one traffic); the manifest is made here from
BENCHMARK.json itself (the cell, its configuration and its metrics renamed),
as ``test_cell_pangu_cpu.py`` makes its own.

The cell reports the accepted decode and start-up metrics, whose
``workloads`` it was appended to, and seven of its own: five over the readers
``perfbench/harness/moe.py`` and ``ssm.py`` have (entries of their own because
``test_cell_lfm2_cpu.py`` and ``test_cell_g4h_cpu.py`` pin those cells'
entries by count) and two over ``perfbench/harness/ssm_moe.py``.  Nothing
here pins the END of a list or a count of cells: a later PR that appends
must still pass."""
import functools
import json
import os

import pytest

from bench_util import ROOT, last_line, run_cell

from perfbench.harness import manifest as mf

CELL, CONFIG = "g4hsmall-decode-closed16", "granite-4.0-h-small"
TOY_CELL, TOY_CONFIG = "toy-g4hs-decode", "toy-g4hs"
REDUCED = {"num_hidden_layers": 40, "num_local_experts": 72,
           "vocab_size": 100352}
OWN = ["moe_experts_hit_per_step_g4hs", "moe_experts_ms_per_step_g4hs",
       "ssm_step_ms_per_step_g4hs", "ssm_scan_share_pct_g4hs",
       "ssm_state_gb_per_step_g4hs", "moe_router_ms_per_step_g4hs",
       "decode_bytes_roofline_pct_g4hs"]


def _real():
    return mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))


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
    m = _real()
    assert mf.validate(m.data) == []
    cell = m.workloads[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    # the latent cell's own traffic file, not a copy
    assert cell["traffic"] == \
        m.workloads["pangu718b-decode-closed16"]["traffic"] == \
        "closed16-p512-2048-o192-384"
    assert "half the deployment's" in cell["why"]
    assert m.configs[CONFIG]["reduced"] == list(REDUCED)
    assert CELL in m.end_to_end["decode_tokens_per_s"]["workloads"]
    assert [w["name"] for w in m.data["workloads"] if w["chips"] == 4] == \
        ["cgpt13b-train-dp4"]
    limits = m.load_json("limits", CELL + ".json")
    assert set(limits["limits"]) == {"served_token_logit_gap",
                                     "cold_runs_in_window"}
    assert limits["control"] == "fp8"
    ref = m.load_module("references", CONFIG + ".py")
    assert ref.FAMILY == ref.BUILDER == "granite_moe_hybrid_lm"

    def shared(cell, moves):
        return [n for n in m.cell_metrics("per_layer", cell, moves={moves})
                if m.per_layer[n]["workloads"] != [cell]]

    # the 16 decode metrics and the 10 start-up metrics of the latent cell
    for moves, count in (("decode_tokens_per_s", 16), ("setup_s", 10)):
        assert len(shared(CELL, moves)) == count
        assert shared(CELL, moves) == shared("pangu718b-decode-closed16",
                                             moves)
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in own] == OWN
    for x in own:
        assert x["moves"] == "decode_tokens_per_s"
        assert x["better"] == ("higher" if "roofline" in x["name"]
                               else "lower")
        assert callable(m.load_module("layer_metrics",
                                      x["name"] + ".py").read)
    # no accepted cell's own entries took this cell
    for other in ("g4hmicro-decode-closed16", "lfm2moe-decode-closed16",
                  "pangu718b-decode-closed16"):
        theirs = [x for x in m.data["per_layer"]
                  if x.get("workloads") == [other]]
        assert len(theirs) == {"pangu718b-decode-closed16": 6}.get(other, 4)


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog row's ``config``, under the same key, but
    the three keys BENCHMARK.json lists under ``reduced``, whose published
    values stand beside them; no width among them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    cfg = _real().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differs == set(REDUCED)
    for key, published in REDUCED.items():
        assert cfg[key + "_published"] == published == row["config"][key]
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"], cfg["n_layer"], cfg["first_expert"]) == \
        (10, 36, 50176, 10, 0)
    # the published pattern whole; the ten layers run are one period of it
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + \
        ["mamba"] * 4
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["shared_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"]) == \
        (4096, 768, 1536, 10, 128, 64, 128, 32, 8)
    assert "2 chips share each layer" in cfg["cut"]["deployment"] and \
        "four pipeline stages" in cfg["cut"]["deployment"]
    assert "4.757 B = 9.51 GB" in cfg["cut"]["parameters"]
    assert {"router", "state", "initialisation"} <= set(cfg["assumed"])


def test_the_parameter_count_and_the_step_bytes_are_the_shapes():
    """4.76 B parameters (32.2 B uncut); and ``decode_step_bytes`` against
    ``param_shapes``: with every held expert hit, no token cached and no
    state, a step reads every leaf once."""
    from perfbench.harness import ssm_moe
    from perfbench.models import granite_moe_hybrid_lm as ref

    cfg = _real().config(CONFIG)
    n = ref.n_params(cfg, 10)
    assert round(n / 1e9, 3) == 4.757 and round(n / 1e9, 2) == 4.76
    whole = dict(cfg, num_local_experts=72, vocab_size=100352)
    assert round(ref.n_params(whole, 40) / 1e9, 1) == 32.2
    shapes = ref.param_shapes(cfg, 10)
    one = 3 * 4096 * 768 * 2
    dense = ssm_moe.decode_step_bytes(cfg, shapes, 0, 0, 0)
    assert dense == 2 * n - 10 * 36 * one
    # mixers 1.93 GB, shared MLPs 0.38 GB, the tied table 0.41 GB
    assert round(dense / 1e9, 2) == 2.72
    full = ssm_moe.decode_step_bytes(cfg, shapes, 10 * 36 * one, 0, 0)
    assert full == 2 * n
    # a lane's slot: 9 layers x (128 x 64 x 128 x 4 B + 3 x 8448 x 2 B),
    # read and written; a token: one attention layer's K and V, 4,096 B
    slot = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(slot / 1e6, 1) == 38.2
    assert ssm_moe.decode_step_bytes(cfg, shapes, 0, 16 * slot, 0) - dense \
        == 2 * 16 * slot
    assert ssm_moe.decode_step_bytes(cfg, shapes, 0, 0, 16 * 1500) - dense \
        == 16 * 1500 * 4096


def test_cell_end_to_end(tmp_path_factory):
    path = _manifest(str(tmp_path_factory.mktemp("g4hs")))
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
    path = _manifest(str(tmp_path_factory.mktemp("g4hs")))
    rc, out, err = run_cell(TOY_CELL, seed=5, seconds=1.5, trace=1,
                            manifest=path)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert {"gen_step_ms_p50", "gen_sched_ms_per_step", "gen_prefill_ms_p50",
            "gen_pool_h2d_ms_per_step", "gen_pool_d2h_ms_per_step",
            "gen_queue_wait_p50_ms", "gen_itl_p50_ms", "gen_ttft_p50_ms",
            "gen_lanes_per_step", "gen_prefill_share_pct",
            "device_idle_share_gen", "peak_hbm_gb_gen",
            "compiles_in_window_gen", "setup_program_s", "setup_programs",
            "moe_experts_hit_per_step_g4hs",
            "ssm_state_gb_per_step_g4hs"} <= set(got)
    assert got["compiles_in_window_gen"]["value"] == 0.0
    # 4 lanes x 4 picks over 16 experts a layer, 4 of them held here
    assert 0 <= got["moe_experts_hit_per_step_g4hs"]["value"] <= 4
    # 4 lanes of 8 x 16 x 16 float32 + 3 x 160 bfloat16, 4 state-space layers
    per_lane = 4 * (8 * 16 * 16 * 4 + 3 * 160 * 2)
    assert 0 < got["ssm_state_gb_per_step_g4hs"]["value"] <= \
        4 * per_lane / 1e9
    # the host has no scopes and no module line: device times and shares of
    # a peak are left out, not faked
    assert not any(k.endswith("_ms_per_step_g4hs") for k in got)
    assert not any("mfu" in k or "roofline" in k or "share_pct_g4hs" in k
                   for k in got)


def test_readers_find_nothing_in_a_program_without_these_spans():
    """On a program without the spans the new readers return None and do
    not raise: a run's info without a trace, and one whose trace has no such
    span."""
    from perfbench.harness import ssm_moe

    for read in (ssm_moe.moe_router_ms_per_step,
                 ssm_moe.decode_bytes_roofline_pct):
        assert read({"trace": None, "workload": "x"}) is None
        assert read({"trace": {"busy_s": 1.0}, "workload": "no-such"}) is None
