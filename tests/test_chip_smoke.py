"""chip_smoke.py on the CPU: the contracted last line, the fast failure
without a chip, and the phase functions at toy width (steered from here —
the program has no option for it)."""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY_LM = dict(vocab=64, hidden=32, heads=2, layers=1)


class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def _assert_contract(line, ok):
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"}
    assert set(obj["device"]) == {"platform", "kind", "count"}
    assert obj["ok"] is ok
    return obj


@pytest.mark.parametrize("n", [1, 4])
def test_final_line_has_exactly_the_contracted_keys(n):
    obj = _assert_contract(
        chip_smoke.final_line(True, [_FakeDevice()] * n), True)
    assert obj["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": n}
    _assert_contract(chip_smoke.final_line(False, []), False)


def test_without_a_chip_fails_fast_and_ends_in_the_contracted_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert time.monotonic() - t0 < 60
    assert proc.returncode != 0
    obj = _assert_contract(proc.stdout.splitlines()[-1], False)
    assert obj["device"]["platform"] == "cpu"


def test_trainer_phase_toy_width_on_cpu():
    import mxnet_tpu as mx

    out = chip_smoke.trainer_phase(
        dict(TOY_LM, seq=16, batch=2, steps=3, lr=1e-2), [mx.tpu(0)])
    assert len(out["losses"]) == 3 and out["losses"][-1] < out["losses"][0]
    assert [d.id for d in out["param_devices"]] == [0]


def test_dp_phase_toy_width_on_four_virtual_devices():
    import mxnet_tpu as mx

    out = chip_smoke.dp_phase(
        dict(TOY_LM, seq=16, batch=4, steps=3, lr=1e-2),
        [mx.tpu(i) for i in range(4)])
    assert [d.id for d in out["dp"]["data_devices"]] == [0, 1, 2, 3]
    assert "all-reduce" in out["dp"]["compiled_text"]


def test_server_phase_toy_width_on_cpu():
    import mxnet_tpu as mx

    out = chip_smoke.server_phase(
        dict(TOY_LM, max_seq=64, lanes=4, page_size=8,
             prompt_lens=(8, 16, 32), new_tokens=6,
             paged=dict(lanes=2, num_pages=9, page_size=4, heads=2,
                        head_dim=8, max_pages=4, positions=(3, 14)),
             paged_grouped=(dict(lanes=3, num_pages=13, page_size=16, heads=8,
                                 kv_heads=2, head_dim=64, max_pages=4,
                                 positions=(3, 60), dtype="bfloat16"),),
             paged_latent=dict(lanes=3, num_pages=13, page_size=16, heads=8,
                               nope=16, rope=64, rank=128, v=16, row=256,
                               max_pages=4, positions=(3, 60)),
             ssm_scan=(dict(L=512, heads=8, lengths=(200, 512)),
                       dict(L=64, heads=8, lengths=(40,)))),
        mx.tpu(0))
    assert out["paged_kernel_gap"] <= 1e-5  # interpreted: float32 both
    assert out["latent_kernel_gap"] <= chip_smoke.PAGED_TOL
    assert len(out["scan_kernel_gaps"]) == 2 and all(
        y <= chip_smoke.SCAN_Y_TOL and s <= chip_smoke.SCAN_STATE_TOL
        for y, s in out["scan_kernel_gaps"])
    assert len(out["transcripts"]) == 4
    assert out["transcripts"][0] == out["transcripts"][-1]
    assert out["logits_rel_diff"] <= chip_smoke.LOGITS_REL_TOL


def test_import_initialises_no_backend():
    code = ("import mxnet_tpu, jax; "
            "from jax._src import xla_bridge as xb; "
            "assert not xb.backends_are_initialized(), 'backend touched'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
