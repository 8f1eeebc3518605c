"""``correct`` has been shown to fail: the control (the reference computed in
the precision below the configuration's, put in the program's place) and a
timed path broken underneath both come out as not correct."""
import argparse
import json
import os

import jax
import pytest

from perfbench import run as bench
from perfbench.harness import check
from perfbench.models import gpt2_lm
from perfbench.models.precision import einsum, seed_key

from bench_util import TOYDIR, toy_manifest


def _args(workload, seed=21, seconds=0.5):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0, manifest=toy_manifest(),
                              rehearse=True)


def _toy(name):
    with open(os.path.join(TOYDIR, name)) as f:
        return json.load(f)


def test_fp8_rounds_coarser_than_bf16_coarser_than_f32():
    a = jax.random.normal(seed_key(1), (64, 64))
    b = jax.random.normal(seed_key(2), (64, 64))
    exact = einsum("ik,kj->ij", a, b, "f32")
    err = {p: float(abs(einsum("ik,kj->ij", a, b, p) - exact).max())
           for p in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"]
    with pytest.raises(ValueError):
        einsum("ik,kj->ij", a, b, "int3")


def test_seeds_above_32_bits_give_their_own_weights():
    cfg = _toy("configs/toy-gpt.json")
    a = gpt2_lm.make_weights(cfg, 5, 1)["lm_head_weight"]
    b = gpt2_lm.make_weights(cfg, 2**31 + 5, 1)["lm_head_weight"]
    c = gpt2_lm.make_weights(cfg, 2**31 + 5, 1)["lm_head_weight"]
    assert float(abs(a - b).max()) > 0 and float(abs(b - c).max()) == 0


def test_training_control_reads_far_above_the_configurations_precision():
    """The control at a size a test run can hold: the reference computed in
    float8 put in the program's place reads several times what the same
    reference reads in bfloat16, the precision the configuration states;
    a limit between the two fails the one and passes the other."""
    cfg, mix = _toy("configs/toy-gpt.json"), \
        _toy("traffic/toy-train-s2048.json")
    assert _toy("limits/toy-train-lm.json")["control"] == "fp8"
    ids = jax.random.randint(seed_key(33), (2, 129), 0, cfg["vocab_size"])
    batches = [(ids[:, :-1], ids[:, 1:])]
    opt = mix["optimizer_params"]
    want = gpt2_lm.follow_training(cfg, 2, opt, 33, batches, 3, "f32")
    assert max(check.training_numbers(want, want).values()) == 0.0
    nums = {p: check.training_numbers(
        gpt2_lm.follow_training(cfg, 2, opt, 33, batches, 3, p), want)
        for p in ("bf16", "fp8")}
    for k in ("loss_gap", "grad_norm_gap"):
        assert nums["fp8"][k] > 3 * nums["bf16"][k] > 0, (k, nums)


def test_the_parameters_change_leaves_out_the_key_bias():
    """A key bias has a true gradient of zero (softmax ignores a shift of
    all of a query's scores), so whatever moves it is noise: a change there
    reads as none, a change in the query or value bias as itself."""
    cfg = _toy("configs/toy-gpt.json")
    h = cfg["n_embd"]
    first = gpt2_lm.make_weights(cfg, 7, 1)
    moved = dict(first)
    moved["layer0_qkv_bias"] = first["layer0_qkv_bias"].at[h:2 * h].add(1.0)
    # the seeded leaf is made again inside the program that subtracts it,
    # where the compiler may contract 0.02 * x - leaf into one rounding
    assert max(gpt2_lm.delta_norms(cfg, 1, 7, moved).values()) < 1e-6
    moved["layer0_qkv_bias"] = first["layer0_qkv_bias"].at[:h].add(1.0)
    assert gpt2_lm.delta_norms(cfg, 1, 7, moved)["layer0_qkv_bias"] == \
        pytest.approx(h ** 0.5)


def test_worst_leaf_gap_measures_against_the_median_leaf():
    want = {"a": 1.0, "b": 1.0, "tiny": 1e-9}
    gap, leaf = check.worst_leaf_gap({"a": 1.1, "b": 1.0, "tiny": 2e-9},
                                     want)
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, leaf = check.worst_leaf_gap({"a": 1.0, "b": 1.0,
                                      "tiny": float("nan")}, want)
    assert gap == float("inf") and leaf == "tiny"


def test_checks_print_value_beside_limit_and_need_every_number(capsys):
    c = check.Checks({"x": 1.0, "y": 0})
    assert c.correct is False  # nothing compared yet
    assert c.add("x", 0.5) and c.correct
    assert not c.add("y", 1.0) and not c.correct
    out = capsys.readouterr().out
    assert "limit 1" in out and "FAILED" in out
    with pytest.raises(KeyError):
        c.add("unknown", 0.0)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    """Skips the look for a chip and drives the rest of a run with the
    optimizer's update broken underneath."""
    import mxnet_tpu as mx

    monkeypatch.setattr(
        mx.optimizer.Adam, "pure_update",
        lambda self, weight, grad, state, lr, wd, t, rng=None:
        (weight, state))
    line = bench.execute(bench.prepare(_args("toy-train-lm")))
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"


def test_a_sound_run_in_process_is_correct():
    line = bench.execute(bench.prepare(_args("toy-train-lm", seed=22)))
    assert line["correct"] is True and line["failed"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from mxnet_tpu.generation import engine

    emit = engine.GenStream._emit
    monkeypatch.setattr(
        engine.GenStream, "_emit",
        lambda self, token: emit(self, (int(token) + 1) % 384))
    line = bench.execute(bench.prepare(_args("toy-decode", seconds=1.0)))
    assert line["correct"] is False and line["attempted"] > 0
