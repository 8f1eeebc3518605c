"""BENCHMARK.json against the contract's names, units and keys, and every
file it names."""
import copy
import os

import pytest

from perfbench.harness import manifest as mf

from bench_util import (ROOT, TOY_CELLS, TOYDIR, toy_manifest,
                        toy_manifest_data)


def test_the_manifest_is_valid(manifest_data):
    assert mf.validate(manifest_data) == []
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_manifest_holds_what_the_issue_names(manifest_data):
    m = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    # later PRs add cells and configurations: these stay
    assert set(m.workloads) >= {
        "cgpt13b-train-s2048", "cgpt13b-decode-closed",
        "resnet50-train-b256", "cgpt13b-train-dp4"}
    assert set(m.configs) >= {"cerebras-gpt-1.3b", "resnet-50"}
    assert {"train_tokens_per_s", "train_img_per_s", "decode_tokens_per_s",
            "setup_s"} <= set(m.end_to_end)
    assert m.workloads["cgpt13b-train-dp4"]["chips"] == 4
    assert manifest_data["command"] == ["python3", "perfbench/run.py"]
    assert manifest_data["paths"] == ["perfbench", "tests/benchmark"]


def test_the_toy_manifest_is_the_real_one_renamed(manifest_data):
    """Every cell, configuration and metric has its toy stand-in, made from
    BENCHMARK.json itself, so the two cannot drift apart."""
    toy = toy_manifest_data()
    assert mf.validate(toy) == []
    assert {w["name"] for w in toy["workloads"]} == set(TOY_CELLS.values())
    # every metric one of those cells reports is in the toy manifest too
    assert [m["name"] for m in toy["per_layer"]] == \
        [m["name"] for m in manifest_data["per_layer"]
         if set(m.get("workloads", TOY_CELLS)) & set(TOY_CELLS)]
    m = mf.Manifest(toy_manifest(), root=ROOT)
    for w in toy["workloads"]:
        assert w["name"].startswith("toy-")
        assert m.find("traffic", w["traffic"] + ".json").startswith(TOYDIR)
        assert m.find("limits", w["name"] + ".json").startswith(TOYDIR)
    for c in toy["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


@pytest.mark.parametrize("name,ok", [
    ("cgpt13b-train-s2048", True), ("_x.1-b", True), ("9lives", True),
    ("a" * 64, True), ("a" * 65, False), ("-lead", False), ("has space", False),
    ("comma,name", False), ("sl/ash", False), ("", False), ("µs", False)])
def test_names(name, ok):
    assert bool(mf.NAME_RE.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("tokens/s", True), ("%", True), ("ms", True), ("img/s", True),
    ("GB", True), ("tokens per second", False), ("µs", False), ("", False),
    ("x" * 17, False)])
def test_units(unit, ok):
    assert bool(mf.UNIT_RE.match(unit)) is ok


def _broken(data, how):
    d = copy.deepcopy(data)
    if how == "metric_why":
        d["per_layer"][0]["why"] = "not allowed"
    elif how == "bad_unit":
        d["end_to_end"][0]["unit"] = "tokens per second"
    elif how == "loose_bound":
        d["end_to_end"][0]["bound"] = 0.2
    elif how == "no_setup":
        d["end_to_end"] = [m for m in d["end_to_end"]
                           if m["name"] != "setup_s"]
    elif how == "two_four_chip_cells":
        d["workloads"][0]["chips"] = 4
    elif how == "moves_nothing":
        d["per_layer"][0]["moves"] = "nothing"
    elif how == "pair_twice":
        d["workloads"].append(dict(d["workloads"][0], name="again"))
    elif how == "extra_key":
        d["notes"] = "x"
    elif how == "file_outside":
        d["configs"][0]["file"] = "mxnet_tpu/x.json"
    elif how == "tabbed_why":
        d["workloads"][0]["why"] = "a\tb"
    return d


@pytest.mark.parametrize("how", [
    "metric_why", "bad_unit", "loose_bound", "no_setup",
    "two_four_chip_cells", "moves_nothing", "pair_twice", "extra_key",
    "file_outside", "tabbed_why"])
def test_validate_finds_each_fault(manifest_data, how):
    assert mf.validate(_broken(manifest_data, how)) != []


def test_every_named_file_is_there(manifest_data):
    m = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    for c in manifest_data["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        ref = m.load_module("references", c["name"] + ".py")
        assert m.find("builders", ref.BUILDER + ".py")
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "models",
                                           ref.FAMILY + ".py"))
    for w in manifest_data["workloads"]:
        mix = m.load_json("traffic", w["traffic"] + ".json")
        assert m.find("drivers", mix["driver"] + ".py")
        limits = m.load_json("limits", w["name"] + ".json")
        assert limits["limits"] and limits["control"] in ("bf16", "fp8")
        assert limits["readings"]
    for metric in manifest_data["per_layer"]:
        assert callable(m.load_module("layer_metrics",
                                      metric["name"] + ".py").read)


def test_no_reader_without_an_entry_and_no_entry_without_a_reader(
        manifest_data):
    """A file under ``layer_metrics/`` that no entry names is never run by
    the driver; an entry without its file fails every traced run."""
    m = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    files = {f[:-3] for f in os.listdir(os.path.join(ROOT, "perfbench",
                                                     "layer_metrics"))
             if f.endswith(".py")}
    entries = [metric["name"] for metric in manifest_data["per_layer"]]
    assert sorted(files - set(entries)) == [], "readers without an entry"
    assert sorted(set(entries) - files) == [], "entries without a reader"
    for name in entries:
        assert callable(m.load_module("layer_metrics", name + ".py").read)


def test_config_files_state_source_widths_and_cut(manifest_data):
    m = mf.Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = m.config("cerebras-gpt-1.3b")
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_inner"], cfg["n_layer"],
            cfg["n_positions"], cfg["vocab_size"]) == \
        (2048, 16, 8192, 24, 2048, 50257)
    assert 1 <= cfg["n_layer_train"] <= 24 and "assumed" in cfg
    assert m.config("resnet-50")["image_shape"] == [3, 224, 224]


def test_files_under_paths_are_named_from_the_allowed_characters():
    import re
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in ("perfbench", os.path.join("tests", "benchmark")):
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
