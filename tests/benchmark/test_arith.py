"""FLOP arithmetic against hand-worked values."""
import json
import os

import pytest

from perfbench.harness import arith, peaks
from perfbench.models import gpt2_lm, resnet_v1

from bench_util import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name)) as f:
        return json.load(f)


def test_lm_flops_per_token_by_hand():
    # one layer, hidden 4, inner 16, vocab 10, seq 8
    n_matmul = 4 * 16 + 2 * 4 * 16 + 10 * 4          # 232
    attn_fwd = 1 * 2 * 2 * 4.0 * 4                    # 64
    assert arith.lm_train_flops_per_token(4, 16, 2, 1, 10, 8) == \
        6 * n_matmul + 3 * attn_fwd


def test_lm_flops_at_the_published_widths():
    cfg = _cfg("cerebras-gpt-1.3b.json")
    per_layer = 4 * 2048 ** 2 + 2 * 2048 * 8192
    assert per_layer == 50331648
    got = arith.lm_train_flops_per_token(2048, 8192, 16, cfg["n_layer_train"],
                                         50257, 2048)
    want = 6.0 * (cfg["n_layer_train"] * per_layer + 50257 * 2048) \
        + 3.0 * cfg["n_layer_train"] * 4 * 1024 * 2048
    assert got == want


def test_lm_parameter_count_is_the_published_size():
    cfg = _cfg("cerebras-gpt-1.3b.json")
    n = gpt2_lm.n_params(cfg)
    # 1.3B plus the untied head (103M) the program's model has
    assert 1.31e9 < n - 50257 * 2048 - 50257 < 1.32e9


def test_resnet50_forward_multiply_adds():
    cfg = _cfg("resnet-50.json")
    assert resnet_v1.forward_macs(cfg) == 4089184256
    assert arith.image_train_flops(4089184256) == 6 * 4089184256
    params, _ = resnet_v1.param_shapes(cfg)
    total = sum(int.__mul__(*((s + (1,))[:2])) if len(s) < 3
                else s[0] * s[1] * s[2] * s[3] for s in params.values())
    assert total == 25557032


def test_mfu():
    assert arith.mfu_pct(2e9, 1000.0, 1, 197e12) == \
        pytest.approx(100 * 2e12 / 197e12)
    assert arith.mfu_pct(2e9, 4000.0, 4, 197e12) == \
        pytest.approx(100 * 2e12 / 197e12)


def test_peaks_are_the_published_v5e_and_nothing_else():
    assert peaks.peak("TPU v5 lite", "flops") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "flops")
