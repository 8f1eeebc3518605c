"""The driver-facing bench entry: orchestration, phase records, and
failure normalization (all CPU-safe; the TPU paths differ only in which
branches the phase children take).

Reference equivalent for the record shape:
example/image-classification/train_imagenet.py --benchmark 1 prints the
steady-state img/s the same way (common/fit.py:106-116)."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402

def _cli(extra=()):
    return bench._arg_parser().parse_args(list(extra))

def test_headline_prefers_lm_mfu():
    rec = {"metric": "resnet50_train_throughput", "value": 2400.0,
           "unit": "img/s", "vs_baseline": 13.2,
           "transformer_lm_mfu": 0.514}
    out = bench._headline(dict(rec))
    assert out["metric"] == "transformer_lm_train_mfu"
    assert out["value"] == 0.514
    assert out["vs_baseline"] == round(0.514 / bench.LM_NORTH_STAR, 3)
    # the parity track stays visible
    assert out["resnet50_img_per_sec"] == 2400.0
    assert out["resnet50_vs_p100"] == 13.2

def test_headline_falls_back_to_resnet():
    rec = {"metric": "resnet50_train_throughput", "value": 2400.0,
           "unit": "img/s", "vs_baseline": 13.2}
    assert bench._headline(dict(rec)) == rec

def test_run_phase_normalizes_child_error(monkeypatch):
    """A crashed child's fallback JSON (metric/value/error keys) must not
    contaminate the merged record — only <phase>_error survives."""
    fake = json.dumps({"metric": "transformer_lm_train_mfu", "value": 0.0,
                       "unit": "MFU", "vs_baseline": 0.0,
                       "error": "RuntimeError: boom"})

    def fake_run(*a, **k):
        return subprocess.CompletedProcess(a, 1, stdout=fake + "\n",
                                           stderr="")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench._run_phase("lm", _cli(), timeout=5)
    assert set(out) == {"lm_error"}
    assert "boom" in out["lm_error"]

def test_run_phase_normalizes_timeout(monkeypatch):
    def fake_run(*a, **k):
        raise subprocess.TimeoutExpired(cmd=a, timeout=k.get("timeout"))

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    out = bench._run_phase("resnet", _cli(), timeout=7)
    assert set(out) == {"resnet_error"}
    assert "7" in out["resnet_error"]

def test_run_phase_parses_last_json_line(monkeypatch):
    ok = {"backend": "tpu", "transformer_lm_mfu": 0.4}

    def fake_run(*a, **k):
        return subprocess.CompletedProcess(
            a, 0, stdout="noise\n" + json.dumps(ok) + "\n", stderr="")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    assert bench._run_phase("lm", _cli(), timeout=5) == ok

def test_run_phase_passthrough_flags(monkeypatch):
    seen = {}

    def fake_run(cmd, **k):
        seen["cmd"] = cmd
        return subprocess.CompletedProcess(cmd, 0, stdout="{}", stderr="")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    bench._run_phase("resnet", _cli(["--skip-transformer",
                                     "--skip-attention"]), timeout=5)
    cmd = seen["cmd"]
    assert "--skip-transformer" in cmd and "--skip-attention" in cmd
    assert cmd[cmd.index("--phase") + 1] == "resnet"

def test_lm_phase_skips_off_tpu():
    """Real subprocess: on the CPU test platform the lm phase reports
    lm_skipped rather than hanging or crashing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--phase", "lm"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec == {"backend": "cpu", "lm_skipped": "backend cpu"}
