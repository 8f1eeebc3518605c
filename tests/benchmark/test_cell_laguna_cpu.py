"""``lagunaxs2-decode-closed16`` rehearsed at toy size on the host: the
family with sliding-window layers over a ring a lane beside global layers
over pages through ``perfbench/run.py`` as the driver runs it.  The toy
configuration, mix and limits live under ``tests/benchmark/toy`` and are
found by name; the manifest is made here from BENCHMARK.json itself (the
cell, its configuration and its metrics renamed), as
``test_cell_pangu_cpu.py`` makes its own.

Every assertion about BENCHMARK.json is of membership, never of a position
in a list or of a count of cells: a later PR appends, and this file must
still pass."""
import functools
import json
import math
import os

import pytest

from bench_util import ROOT, last_line, run_cell

from perfbench.harness import manifest as mf

CELL, CONFIG = "lagunaxs2-decode-closed16", "laguna-xs.2"
TRAFFIC = "closed16-p1024-4096-o512-1024"
TOY_CELL, TOY_CONFIG = "toy-laguna-decode", "toy-laguna"
REDUCED = {"num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352}
OWN = ["window_attn_ms_per_step_laguna", "full_attn_ms_per_step_laguna",
       "window_gb_per_step_laguna", "window_read_share_pct_laguna",
       "window_attn_roofline_pct_laguna", "moe_experts_ms_per_step_laguna",
       "moe_experts_hit_per_step_laguna", "decode_bytes_roofline_pct_laguna"]


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


def test_the_cell_its_files_and_its_metrics_are_in_the_manifest():
    m = _real()
    assert mf.validate(m.data) == []
    assert CONFIG in m.configs and CELL in m.workloads
    cell = m.workloads[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == TRAFFIC
    assert "1/8 of the deployment's load" in cell["why"]
    assert m.configs[CONFIG]["reduced"] == list(REDUCED)
    assert m.configs[CONFIG]["file"] == "perfbench/configs/laguna-xs.2.json"
    assert CELL in m.end_to_end["decode_tokens_per_s"]["workloads"]
    assert set(m.load_json("limits", CELL + ".json")["limits"]) == {
        "served_token_logit_gap", "cold_runs_in_window"}
    ref = m.load_module("references", CONFIG + ".py")
    assert (ref.FAMILY, ref.BUILDER) == ("laguna_lm", "laguna_lm")
    assert m.load_json("traffic", TRAFFIC + ".json")["driver"] == \
        "generate_closed_loop"

    # every decode and start-up metric another decode cell shares with the
    # rest lists this cell too
    def shared(cell, moves):
        return {n for n in m.cell_metrics("per_layer", cell, moves={moves})
                if len(m.per_layer[n].get("workloads", [None, None])) > 1}

    for moves in ("decode_tokens_per_s", "setup_s"):
        assert shared("pangu718b-decode-closed16", moves) <= \
            shared(CELL, moves)
        assert shared(CELL, moves)
    for name in OWN:
        x = m.per_layer[name]
        assert x["workloads"] == [CELL]
        assert x["moves"] == "decode_tokens_per_s"
        assert x["better"] == ("higher" if "roofline" in name else "lower")
        assert x["unit"] == ("%" if "pct" in name else
                             "GB" if "_gb_" in name else
                             "count" if "_hit_" in name else "ms")
        assert callable(m.load_module("layer_metrics", name + ".py").read)
    # layers another metric already names, letter for letter
    others = {x["layer"] for x in m.data["per_layer"]
              if x["name"] not in OWN}
    assert {m.per_layer[n]["layer"] for n in OWN} <= others


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = _real().load_json("traffic", TRAFFIC + ".json")
    want = dict(driver="generate_closed_loop", clients=16,
                prompt_len={"lo": 1024, "hi": 4096},
                answer_len={"lo": 512, "hi": 1024}, dephase=True,
                max_seq_len=5120, pool_lanes=16, pool_tokens_per_lane=5120,
                page_size=16, lane_buckets=[16],
                prefill_len_buckets=[1024, 2048, 4096],
                prefill_batch_buckets=[1], check_requests=6, check_len=5120,
                trace_share=0.3)
    assert {k: mix[k] for k in want} == want
    from perfbench.harness import traffic

    plan = traffic.closed_loop_plan(dict(mix, rounds=2), 12544, 2**31 + 5)
    assert [r[0]["max_new_tokens"] for r in plan] == list(range(64, 1025, 64))
    assert sorted(len(r[1]["prompt"]) for r in plan) == \
        traffic.grid(1024, 4096, 16)
    assert sorted(r[1]["max_new_tokens"] for r in plan) == \
        traffic.grid(512, 1024, 16)
    assert max(len(r[1]["prompt"]) + r[1]["max_new_tokens"]
               for r in plan) <= mix["pool_tokens_per_lane"]
    assert max(t for r in plan for q in r for t in q["prompt"]) < 12544


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog row's ``config``, under the same key, but
    the three keys BENCHMARK.json lists under ``reduced``, whose published
    values stand beside them; no width among them; nested groups whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    cfg = _real().config(CONFIG)
    assert cfg["source"] == row["source_url"] and len(cfg["source"]) < 200
    differs = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differs == set(REDUCED)
    for key, published in REDUCED.items():
        assert cfg[key + "_published"] == published == row["config"][key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["n_layer"], cfg["first_expert"]) == (20, 32, 12544, 20, 0)
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    assert (cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_published"], cfg["num_experts_per_tok"],
            cfg["moe_routed_scaling_factor"]) == \
        (2048, 8, 128, 512, 8192, 512, 512, 256, 8, 2.5)
    assert sorted(set(cfg["num_attention_heads_per_layer"])) == [48, 64]
    assert (full["rope_theta"], full["factor"], full["beta_fast"],
            full["beta_slow"], full["original_max_position_embeddings"],
            full["attention_factor"], full["partial_rotary_factor"]) == \
        (500000, 64, 64, 1, 4096, 1.4158883083359672, 0.5)
    assert (sliding["rope_theta"], sliding["partial_rotary_factor"]) == \
        (10000, 1)
    assert cfg["left_out"] == {}
    for word in ("8 chips", "FIRST of two", "experts 0-31", "12,544"):
        assert word in cfg["cut"]["deployment"]
    assert "layers 0-19" in cfg["cut"]["layers"]
    assert {"gate", "router", "bias_and_qk_norm", "rotary",
            "initialisation"} <= set(cfg["assumed"])


def test_the_builder_takes_the_configuration_and_refuses_what_it_lacks():
    from perfbench.builders import laguna_lm as builder

    cfg = _real().config(CONFIG)
    spec = builder.family_spec(cfg)
    assert spec["layer_types"] == ["attention", "window", "window",
                                   "window"] * 5
    assert (spec["num_heads"], spec["window_heads"], spec["kv_heads"],
            spec["head_dim"], spec["window"]) == (48, 64, 8, 128, 512)
    assert (spec["num_experts"], spec["experts_held"],
            spec["experts_per_token"], spec["num_dense_layers"]) == \
        (256, 32, 8, 1)
    assert spec["rotary_dim"] == 64 and spec["attn_gate"] is True
    with pytest.raises(ValueError, match="weight their OUTPUT"):
        builder.family_spec(dict(cfg, moe_apply_router_weight_on_input=True))
    with pytest.raises(ValueError, match="no bias"):
        builder.family_spec(dict(cfg, attention_bias=True))


def test_the_parameter_counts_and_the_step_bytes_are_the_shapes():
    """2.80 B parameters held and the whole model's 33.44 B by the same
    function; ``decode_step_bytes`` against ``param_shapes``; a ring is 31.5
    MB a lane, 0.535 GB over 17 slots, where pages would be 5.03 GB."""
    from perfbench.harness import window
    from perfbench.models import laguna_lm as ref

    cfg = _real().config(CONFIG)
    assert round(ref.n_params(cfg, 20) / 1e9, 2) == 2.80
    whole = dict(cfg, num_experts=256, vocab_size=100352)
    assert round(ref.n_params(whole, 40) / 1e9, 2) == 33.44
    assert window.ring_bytes_per_lane(cfg) == 15 * 2 * 512 * 1024 * 2
    assert round(17 * window.ring_bytes_per_lane(cfg) / 1e9, 3) == 0.535
    assert window.paged_token_bytes(cfg, "sliding_attention") == 61440
    assert window.paged_token_bytes(cfg, "full_attention") == 20480
    assert round(16 * 5120 * 61440 / 1e9, 2) == 5.03
    shapes = ref.param_shapes(cfg, 20)
    one = 3 * 2048 * 512 * 2
    table = 12544 * 2048 * 2
    dense = window.decode_step_bytes(cfg, shapes, 16, 0, 0, 0)
    rows = 16 * 15 * 2 * 1024 * 2        # this step's K and V rows
    assert dense == 2 * ref.n_params(cfg, 20) - 19 * 32 * one - table \
        + 16 * 2048 * 2 + rows
    # attention's weights are 1.43 GB of it
    attn = sum(2 * int(math.prod(s)) for n, s in shapes.items()
               if any(k in n for k in ("_q_weight", "_k_weight", "_v_weight",
                                       "_gate_weight", "_o_weight")))
    assert round(attn / 1e9, 2) == 1.43
    full = window.decode_step_bytes(
        cfg, shapes, 16, 19 * 32 * one, 16 * 2944,
        16 * window.ring_bytes_per_lane(cfg))
    assert full == dense + 19 * 32 * one + 16 * 2944 * 20480 \
        + 16 * window.ring_bytes_per_lane(cfg)
    # 16 lanes 2,944 tokens deep: the rings are 17 % of what pages would be
    share = 16 * window.ring_bytes_per_lane(cfg) / (16 * 2944 * 61440)
    assert 0.16 < share < 0.18


def test_cell_end_to_end(tmp_path_factory):
    path = _manifest(str(tmp_path_factory.mktemp("laguna")))
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
    path = _manifest(str(tmp_path_factory.mktemp("laguna")))
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
            "moe_experts_hit_per_step_laguna", "window_gb_per_step_laguna",
            "window_read_share_pct_laguna"} <= set(got)
    assert got["compiles_in_window_gen"]["value"] == 0.0
    # 4 lanes x 4 picks over 16 experts a layer, 4 of them held here
    assert 0 <= got["moe_experts_hit_per_step_laguna"]["value"] <= 4
    # at most 4 lanes x 6 sliding layers x K and V x 16 rows x 32 values x 2 B
    lane = 6 * 2 * 16 * 32 * 2
    assert 0 < got["window_gb_per_step_laguna"]["value"] <= 4 * lane / 1e9
    # a lane under 16 tokens deep holds a whole ring: the share passes 100
    assert 0 < got["window_read_share_pct_laguna"]["value"] <= 100.0
    # the host has no scopes and no module line: device times and shares of
    # a peak are left out, not faked
    assert not any(k.endswith("_ms_per_step_laguna") for k in got)
    assert not any("mfu" in k or "roofline" in k for k in got)


def test_readers_find_nothing_in_a_program_without_a_ring():
    """On the parent's program the new readers return None and do not raise:
    a run's info without a trace, and one whose trace has no such span."""
    from perfbench.harness import window

    for read in (window.window_attn_ms_per_step, window.full_attn_ms_per_step,
                 window.window_gb_per_step, window.window_read_share_pct,
                 window.window_attn_roofline_pct,
                 window.moe_experts_hit_per_step,
                 window.decode_bytes_roofline_pct):
        assert read({"trace": None, "workload": "x"}) is None
        assert read({"trace": {"busy_s": 1.0}, "workload": "no-such"}) is None
