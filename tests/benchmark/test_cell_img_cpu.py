"""The image-classification cell's code path at toy size (in a file of its
own: ResNet-50's graph takes a minute to compile on the host)."""
from bench_util import last_line, run_cell


def test_image_cell_end_to_end():
    rc, out, err = run_cell("toy-train-img", seed=6, seconds=1, trace=1)
    assert rc == 0, err[-2000:]
    line = last_line(out)
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["correct"] is True
    assert {"step_ms_p50_img", "compiles_in_window_img",
            "device_idle_share_img"} <= set(line["metrics"])
    assert "train_mfu_pct_img" not in line["metrics"]


def test_float8_control_reads_far_above_bfloat16_on_the_image_family():
    """The image family's control at a size a test run can hold: the
    reference with every product and activation stored in float8 reads
    several times what it reads with bfloat16 products."""
    import json
    import os

    import jax

    from bench_util import TOYDIR
    from perfbench.harness import check
    from perfbench.models import resnet_v1
    from perfbench.models.precision import seed_key

    with open(os.path.join(TOYDIR, "configs", "toy-resnet.json")) as f:
        cfg = json.load(f)
    opt = {"learning_rate": 0.001, "momentum": 0.9, "wd": 1e-4}
    key = seed_key(3)
    batch = [(jax.random.normal(key, (8, 3, 72, 72)),
              jax.random.randint(key, (8,), 0, 10))]
    want = resnet_v1.follow_training(cfg, None, opt, 5, batch, 1, "f32")
    nums = {p: check.training_numbers(
        resnet_v1.follow_training(cfg, None, opt, 5, batch, 1, p), want)
        for p in ("bf16", "fp8")}
    for k in ("loss_gap", "grad_norm_gap", "grad_slice_median_gap"):
        assert nums["fp8"][k] > 3 * nums["bf16"][k] > 0, (k, nums)
