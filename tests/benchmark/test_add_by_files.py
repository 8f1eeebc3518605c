"""A later PR adds a configuration, a mix and a per-layer metric by adding
files and entries: nothing that is there is edited, the harness least of all."""
import json
import os
import shutil

from bench_util import ROOT, last_line, run_cell


def test_dummy_config_mix_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "mxnet_tpu"),
               os.path.join(root, "mxnet_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    before = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for name in files:
            p = os.path.join(d, name)
            with open(p, "rb") as f:
                before[p] = f.read()

    # --- what the later PR adds: files ...
    new = os.path.join(root, "perfbench")
    with open(os.path.join(new, "configs", "dummy-gpt.json"), "w") as f:
        json.dump({"source": "a dummy", "n_embd": 32, "n_head": 2,
                   "n_inner": 128, "n_layer": 2, "n_positions": 64,
                   "vocab_size": 128}, f)
    with open(os.path.join(new, "references", "dummy-gpt.py"), "w") as f:
        f.write('FAMILY = "gpt2_lm"\nBUILDER = "gpt2_lm"\n')
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "train-s2048.json")) as f:
        mix = dict(json.load(f), seq_len=64, batch_per_chip=3)
    with open(os.path.join(new, "traffic", "dummy-s64.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(new, "limits", "dummy-cell.json"), "w") as f:
        json.dump({"limits": {"loss_gap": 0.05, "grad_norm_gap": 0.3,
                              "grad_slice_gap": 0.9,
                              "grad_slice_median_gap": 0.5,
                              "delta_norm_gap": 0.3}, "control": "fp8",
                   "readings": "dummy"}, f)
    with open(os.path.join(new, "layer_metrics", "dummy_steps_lm.py"),
              "w") as f:
        f.write("def read(info):\n    return float(info['steps'])\n")
    with open(os.path.join(new, "layer_metrics", "dummy_nothing_lm.py"),
              "w") as f:
        f.write("def read(info):\n    return None\n")
    # --- ... and entries
    manifest["configs"].append({
        "name": "dummy-gpt", "source": "a dummy",
        "file": "perfbench/configs/dummy-gpt.json", "reduced": [],
        "why": "dummy"})
    manifest["workloads"].append({
        "name": "dummy-cell", "config": "dummy-gpt", "traffic": "dummy-s64",
        "chips": 1, "why": "dummy"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("dummy-cell")
    for name in ("dummy_steps_lm", "dummy_nothing_lm"):
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "device",
            "moves": "train_tokens_per_s", "workloads": ["dummy-cell"]})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)

    rc, out, err = run_cell("dummy-cell", seconds=1, trace=1, manifest=path,
                            root=root)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    assert line["metrics"]["dummy_steps_lm"]["value"] > 0
    # a reader that finds nothing returns nothing: left out of the line
    assert "dummy_nothing_lm" not in line["metrics"]
    # only this cell's metrics: none of the other cells' readers ran
    assert set(line["metrics"]) == {"dummy_steps_lm"}
    # no file that was there has changed
    for p, data in before.items():
        with open(p, "rb") as f:
            assert f.read() == data, p
