"""``pangu718b-decode-closed16`` rehearsed at toy size on the host: the
latent-attention family with a shared expert beside a share of the routed
experts through ``perfbench/run.py`` as the driver runs it.  The toy
configuration, mix and limits live under ``tests/benchmark/toy`` and are
found by name; the manifest is made here from BENCHMARK.json itself (the
cell, its configuration and its metrics renamed), as
``test_cell_lfm2_cpu.py`` makes its own.

The cell reports the accepted decode and start-up metrics, whose
``workloads`` it was appended to, and six of its own
(``perfbench/harness/mla.py`` and, for the scope ``moe_experts``, the
LFM2 cell's reader: ``test_cell_lfm2_cpu.py`` pins that cell's entries by
count, so their ``workloads`` cannot take this cell;
``layer_metrics/*_pangu.py``)."""
import functools
import json
import math
import os

import pytest

from bench_util import ROOT, last_line, run_cell

from perfbench.harness import manifest as mf

CELL, CONFIG = "pangu718b-decode-closed16", "openpangu-ultra-moe-718b"
TOY_CELL, TOY_CONFIG = "toy-pangu-decode", "toy-pangu"
REDUCED = {"num_hidden_layers": 61, "first_k_dense_replace": 3,
           "n_routed_experts": 256, "vocab_size": 153600}


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
    # the newest entries stand last in their lists
    assert m.data["configs"][-1]["name"] == CONFIG
    assert m.data["workloads"][-1]["name"] == CELL
    assert len(m.workloads) == 7
    assert [w["name"] for w in m.data["workloads"] if w["chips"] == 4] == \
        ["cgpt13b-train-dp4"]
    cell = m.workloads[CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "closed16-p512-2048-o192-384"
    assert "one sixteenth" in cell["why"]
    assert m.configs[CONFIG]["reduced"] == list(REDUCED)
    assert m.end_to_end["decode_tokens_per_s"]["workloads"][-1] == CELL
    assert set(m.load_json("limits", CELL + ".json")["limits"]) == {
        "served_token_logit_gap", "cold_runs_in_window"}

    def shared(cell, moves):
        return [n for n in m.cell_metrics("per_layer", cell, moves={moves})
                if m.per_layer[n]["workloads"] != [cell]]

    # the 16 decode metrics and the 10 start-up metrics of the LFM2 cell
    for moves, count in (("decode_tokens_per_s", 16), ("setup_s", 10)):
        assert len(shared(CELL, moves)) == count
        assert shared(CELL, moves) == shared("lfm2moe-decode-closed16",
                                             moves)
    own = [x for x in m.data["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in own] == [
        "mla_attn_ms_per_step_pangu", "mla_latent_gb_per_step_pangu",
        "moe_experts_hit_per_step_pangu", "moe_experts_ms_per_step_pangu",
        "moe_shared_ms_per_step_pangu", "decode_bytes_roofline_pct_pangu"]
    assert m.data["per_layer"][-6:] == own
    for x in own:
        assert x["moves"] == "decode_tokens_per_s"
        assert x["better"] == ("higher" if "roofline" in x["name"]
                               else "lower")
        assert callable(m.load_module("layer_metrics",
                                      x["name"] + ".py").read)


def test_the_start_up_entries_stand_together_and_list_every_cell():
    """What ``test_startup.py`` pins for six cells and for the list's end,
    said so that a PR which appends a cell or a metric still passes: the ten
    ``setup_*`` entries stand together, in their order, and each lists every
    cell of the manifest by name."""
    m = _real()
    per = m.data["per_layer"]
    at = [i for i, x in enumerate(per) if x["moves"] == "setup_s"]
    assert len(at) == 10 and at == list(range(at[0], at[0] + 10))
    assert [per[i]["name"] for i in at][:2] == ["setup_program_s",
                                                "setup_import_s"]
    cells = [w["name"] for w in m.data["workloads"]]
    for i in at:
        assert per[i]["workloads"] == cells


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = _real().load_json("traffic", "closed16-p512-2048-o192-384.json")
    want = dict(driver="generate_closed_loop", clients=16,
                prompt_len={"lo": 512, "hi": 2048},
                answer_len={"lo": 192, "hi": 384}, dephase=True,
                max_seq_len=2432, pool_lanes=16, pool_tokens_per_lane=2432,
                page_size=16, lane_buckets=[16],
                prefill_len_buckets=[512, 1024, 2048],
                prefill_batch_buckets=[1], check_requests=6, check_len=2432,
                trace_share=0.3)
    assert {k: mix[k] for k in want} == want
    from perfbench.harness import traffic

    plan = traffic.closed_loop_plan(dict(mix, rounds=2), 19200, 2**31 + 5)
    assert [r[0]["max_new_tokens"] for r in plan] == list(range(24, 385, 24))
    assert sorted(len(r[1]["prompt"]) for r in plan) == \
        traffic.grid(512, 2048, 16)
    assert max(len(r[1]["prompt"]) + r[1]["max_new_tokens"]
               for r in plan) <= mix["pool_tokens_per_lane"]
    assert max(t for r in plan for q in r for t in q["prompt"]) < 19200


def test_the_configuration_holds_every_published_key():
    """Every number of the catalog row's ``config``, under the same key, but
    the four keys BENCHMARK.json lists under ``reduced``, whose published
    values stand beside them; no width among them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    cfg = _real().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differs == set(REDUCED)
    for key, published in REDUCED.items():
        assert cfg[key + "_published"] == published == row["config"][key]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"], cfg["n_layer"]) == \
        (5, 1, 16, 19200, 5)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == \
        (7680, 128, 128, 64, 128, 1536, 512, 18432, 2048, 8, 2.5)
    assert "num_nextn_predict_layers" in cfg["left_out"]
    assert "16 chips" in cfg["cut"]["deployment"] and cfg["assumed"]


def test_the_parameter_count_and_the_step_bytes_are_the_shapes():
    """4.92 B parameters; and ``decode_step_bytes`` against
    ``param_shapes``: everything outside the routed experts but the
    embedding table is 3.50 GB, and with every held expert hit and no token
    live a step reads every leaf but the table."""
    from perfbench.harness import mla
    from perfbench.models import latent_moe_lm as ref

    cfg = _real().config(CONFIG)
    assert round(ref.n_params(cfg, 5) / 1e9, 2) == 4.92
    shapes = ref.param_shapes(cfg, 5)
    table = 19200 * 7680 * 2
    dense = mla.decode_step_bytes(shapes, 16, 0, 0)
    one = 3 * 7680 * 2048 * 2
    assert dense == 2 * ref.n_params(cfg, 5) - 4 * 16 * one - table \
        + 16 * 7680 * 2
    assert round(dense / 1e9, 2) == 3.50
    # the five latent-attention blocks are 1.97 GB of it
    attn = sum(2 * int(math.prod(s))
               for n, s in shapes.items()
               if any(k in n for k in ("_q_a_", "_q_b_", "_kv_a_", "_kv_b_",
                                       "_o_weight")))
    assert round(attn / 1e9, 2) == 1.97
    full = mla.decode_step_bytes(shapes, 16, 4 * 16 * one, 0)
    assert full == 2 * ref.n_params(cfg, 5) - table + 16 * 7680 * 2
    # 16 lanes of 1,500 tokens: 5,760 B a token
    assert mla.decode_step_bytes(shapes, 16, 0, 16 * 1500 * 5760) - dense \
        == 16 * 1500 * 5760
    # never more than the chip holds: a reading over 100 % would have to
    # come from a step faster than the HBM
    assert full < 2 * ref.n_params(cfg, 5)


def test_cell_end_to_end(tmp_path_factory):
    path = _manifest(str(tmp_path_factory.mktemp("pangu")))
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
    path = _manifest(str(tmp_path_factory.mktemp("pangu")))
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
            "moe_experts_hit_per_step_pangu",
            "mla_latent_gb_per_step_pangu"} <= set(got)
    assert got["compiles_in_window_gen"]["value"] == 0.0
    # 4 lanes x 4 picks over 16 experts a layer, 4 of them held here
    assert 0 <= got["moe_experts_hit_per_step_pangu"]["value"] <= 4
    # at most 4 lanes x 64 tokens x 4 layers x 32 values x 2 B
    assert 0 < got["mla_latent_gb_per_step_pangu"]["value"] <= \
        4 * 64 * 4 * 32 * 2 / 1e9
    # the host has no scopes and no module line: device times and shares of
    # a peak are left out, not faked
    assert not any(k.endswith("_ms_per_step_pangu") for k in got)
    assert not any("mfu" in k or "roofline" in k for k in got)


def test_readers_find_nothing_in_a_program_without_a_latent_plane():
    """On the parent's program the new readers return None and do not raise:
    a run's info without a trace, and one whose trace has no such span."""
    from perfbench.harness import mla

    for read in (mla.mla_attn_ms_per_step, mla.mla_latent_gb_per_step,
                 mla.moe_experts_hit_per_step, mla.moe_shared_ms_per_step,
                 mla.decode_bytes_roofline_pct):
        assert read({"trace": None, "workload": "x"}) is None
        assert read({"trace": {"busy_s": 1.0}, "workload": "no-such"}) is None


@pytest.mark.parametrize("scope,found", [
    ("jit(decode_b16)/layer3_shared_in/dot_general", True),
    ("jit(decode_b16)/layer12_shared_gate/mul", True),
    ("jit(decode_b16)/layer1_shared_out/dot_general", True),
    ("jit(decode_b16)/layer0_mlp_in/dot_general", False),
    ("jit(decode_b16)/layer3_experts/moe_experts/moe_grouped", False),
    ("jit(decode_b16)/layer3_shared_in_weight", False),
    (None, False)])
def test_the_shared_expert_is_read_by_its_nodes_names(scope, found):
    """The shared expert is the dense MLP's three ops under their own node
    names, which the executor traces each op under: no scope of its own."""
    import types

    from perfbench.harness import mla, spans

    op = types.SimpleNamespace(scope=scope)
    assert spans.in_scope(mla.SHARED_EXPERT)(op) is found
