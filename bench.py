"""Headline benchmark, two-phase orchestration (to be replaced by the
benchmark PR — ROADMAP A1/D3; quickest on-chip check: chip_smoke.py).

Phase LM (the headline): model-level transformer-LM
train-step MFU (seq 4096, bf16, adam) through the REAL framework path —
Module.bind/init_optimizer + forward_backward/update — plus the flash
kernel secondary. Small program, compiles in minutes (and hits the
persistent .jax_cache after the first chip session).

Phase ResNet (the parity track): ResNet-50 training throughput through
the same Module path, i.e. exactly what
``examples/image_classification/train_imagenet.py --benchmark 1`` runs.
Reference equivalent: example/image-classification/train_imagenet.py with
``--benchmark 1`` (synthetic data, common/fit.py:106-116); reference
baseline 181.53 img/s on 1x P100 (docs/how_to/perf.md:130-139). Its
fused fwd+bwd+update program is ~60-90min of cold XLA compile on a
1-core host (minutes once .jax_cache is warm).

Run as ``python bench.py`` each phase executes in its own SUBPROCESS
with a hard timeout (the parent never touches JAX, so each child can
take the chip), and a provisional headline line is printed as soon as
the LM phase lands so even a mid-ResNet kill leaves a parsable result.
The LAST JSON line on stdout is the record of note.

``python bench.py --in-process`` (or ``bench.main()``) keeps everything
in one process, for a caller that already holds the chip: a subprocess
could not claim the TPU from a parent that owns it.
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# bench runs always collect step telemetry (MFU/recompile/step-time
# counters); explicit MXNET_TELEMETRY=0 in the environment still wins
os.environ.setdefault("MXNET_TELEMETRY", "1")

# BASELINE.md two-track targets of record (model-level transformer MFU)
LM_ROUND_TARGET = 0.30
LM_NORTH_STAR = 0.40


def _peak_flops():
    """Per-chip peak bf16 FLOP/s of the attached device kind, from the
    one table (hlo_analysis.DEVICE_PEAKS); None for an unlisted kind —
    no MFU rather than another chip's."""
    from mxnet_tpu.hlo_analysis import peak_flops

    return peak_flops()


def _arg_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--num-steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--skip-attention", action="store_true",
                    help="omit the secondary flash-attention metric")
    ap.add_argument("--skip-transformer", action="store_true",
                    help="omit the model-level transformer-LM metric")
    ap.add_argument("--lm-seq-len", type=int, default=4096)
    ap.add_argument("--lm-hidden", type=int, default=2048)
    ap.add_argument("--lm-layers", type=int, default=6)
    ap.add_argument("--lm-batch", type=int, default=4)
    ap.add_argument("--in-process", action="store_true",
                    help="single-process mode (for callers already "
                         "holding the TPU); default CLI orchestrates "
                         "subprocess phases with hard timeouts")
    ap.add_argument("--phase", choices=["resnet", "lm"], default=None,
                    help="internal: run one phase and print its record")
    ap.add_argument("--resnet-timeout", type=int, default=6600,
                    help="seconds before the ResNet subprocess is killed")
    ap.add_argument("--lm-timeout", type=int, default=2400,
                    help="seconds before the LM subprocess is killed")
    ap.add_argument("--skip-kvstore", action="store_true",
                    help="omit the CPU-only kvstore transport phase")
    ap.add_argument("--kvstore-timeout", type=int, default=240,
                    help="seconds before the kvstore subprocess is killed")
    ap.add_argument("--skip-sparse", action="store_true",
                    help="omit the CPU-only sparse parameter plane phase")
    ap.add_argument("--sparse-timeout", type=int, default=300,
                    help="seconds before the sparse subprocess is killed")
    ap.add_argument("--skip-shard-probe", action="store_true",
                    help="omit the CPU-only GSPMD sharding smoke phase")
    ap.add_argument("--shard-probe-timeout", type=int, default=600,
                    help="seconds before the shard-probe subprocess is "
                         "killed")
    ap.add_argument("--skip-coldstart", action="store_true",
                    help="omit the CPU-only serving cold-start phase")
    ap.add_argument("--coldstart-timeout", type=int, default=300,
                    help="seconds before each cold-start subprocess is "
                         "killed")
    ap.add_argument("--skip-platform", action="store_true",
                    help="skip the CPU-only multi-model platform phase "
                         "(tools/bench_platform.py)")
    ap.add_argument("--platform-timeout", type=int, default=300,
                    help="seconds before the platform phase is killed")
    ap.add_argument("--skip-generate", action="store_true",
                    help="omit the CPU-only continuous-batching "
                         "generation phase")
    ap.add_argument("--generate-timeout", type=int, default=600,
                    help="seconds before the generation subprocess is "
                         "killed")
    return ap


def resnet_bench(cli):
    """ResNet-50 Module-path record (the r1-r4 headline)."""
    import jax

    from examples.image_classification.common import fit
    from examples.image_classification.train_imagenet import get_network

    backend = jax.default_backend()
    batch = cli.batch_size or (256 if backend == "tpu" else 8)
    steps = cli.num_steps if backend == "tpu" else 3
    warmup = cli.warmup if backend == "tpu" else 1

    parser = argparse.ArgumentParser()
    fit.add_fit_args(parser)
    args = parser.parse_args([
        "--network", "resnet-50", "--num-classes", "1000",
        "--image-shape", "3,224,224", "--batch-size", str(batch),
        "--lr", str(cli.lr), "--dtype", cli.dtype, "--benchmark", "1"])
    net = get_network(args)

    stats = fit.benchmark(args, net, num_steps=steps, warmup=warmup)

    if not stats.get("finite", True):
        return {"metric": "resnet50_train_throughput", "value": 0.0,
                "unit": "img/s", "vs_baseline": 0.0,
                "error": "non-finite parameters after training"}

    img_per_sec = stats["img_per_sec"]
    # ResNet-50 fwd ~= 4.09 GFLOP/img at 224x224; train ~= 3x fwd
    model_flops = 3 * 4.089e9
    peak = _peak_flops()
    mfu = (img_per_sec * model_flops / peak) if peak else None
    return {
        "metric": "resnet50_train_throughput",
        "value": round(img_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(img_per_sec / 181.53, 3),
        "batch_size": batch,
        "dtype": cli.dtype,
        "backend": backend,
        "step_time_ms": round(stats["step_time_ms"], 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "path": "module",
    }


def _flash_kernel_fields(record):
    """Secondary metric: the flash-attention kernel train step."""
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from bench_attention import run_bench

    att = run_bench(seq=8192, steps=5, block_q=512, block_k=1024)
    record["flash_attention_tflops"] = att["value"]
    record["flash_attention_mfu"] = att["mfu"]


def _lm_fields(record, cli):
    """First-class MODEL-level metric: transformer-LM train step (seq 4k,
    bf16, Module fused path) — the framework-level MFU story, not just
    the attention kernel (examples/transformer/train_lm.py)."""
    lm = transformer_lm_bench(seq_len=cli.lm_seq_len,
                              hidden=cli.lm_hidden,
                              num_layers=cli.lm_layers,
                              batch_size=cli.lm_batch)
    record["transformer_lm_tokens_per_sec"] = round(
        lm["tokens_per_sec"], 1)
    record["transformer_lm_step_ms"] = round(lm["step_time_ms"], 1)
    record["transformer_lm_tflops"] = round(lm["model_tflops"], 2)
    peak = _peak_flops()
    record["transformer_lm_mfu"] = round(
        lm["model_tflops"] * 1e12 / peak, 4) if peak else None


def transformer_lm_bench(seq_len=4096, hidden=2048, num_layers=6,
                         batch_size=4, num_steps=10, warmup=2):
    """Model-level transformer-LM train-step benchmark through the Module
    fused path (in-process; the TPU is held by this process)."""
    import argparse as _ap

    from examples.transformer import train_lm

    args = train_lm.add_args(_ap.ArgumentParser()).parse_args([
        "--benchmark", "1", "--seq-len", str(seq_len),
        "--hidden", str(hidden), "--num-layers", str(num_layers),
        "--num-heads", str(max(1, hidden // 128)),
        "--batch-size", str(batch_size),
        "--dtype", "bfloat16", "--optimizer", "adam",
        "--num-steps", str(num_steps), "--warmup", str(warmup)])
    import mxnet_tpu as mx

    net = mx.models.get_transformer_lm(
        vocab_size=args.vocab_size, num_layers=args.num_layers,
        num_heads=args.num_heads, hidden=args.hidden, seq_len=args.seq_len)
    return train_lm.benchmark(args, net)


def _headline(record):
    """Shape the final one-line JSON. The model-level transformer-LM MFU
    is the headline when measured (BASELINE.md two-track table: model
    >=30% this round, >=40% standing); the ResNet record stays embedded
    (and is the fallback headline when the LM number is absent)."""
    if record.get("transformer_lm_mfu"):
        out = {"metric": "transformer_lm_train_mfu",
               "value": record["transformer_lm_mfu"],
               "unit": "MFU",
               "vs_baseline": round(
                   record["transformer_lm_mfu"] / LM_NORTH_STAR, 3),
               "round_target": LM_ROUND_TARGET,
               "north_star": LM_NORTH_STAR}
        for k, v in record.items():
            if k not in ("metric", "value", "unit", "vs_baseline"):
                out[k] = v
        # keep the parity track visible at the top level
        if record.get("metric") == "resnet50_train_throughput":
            out["resnet50_img_per_sec"] = record.get("value")
            out["resnet50_vs_p100"] = record.get("vs_baseline")
        return out
    return record


def _telemetry_fields(record):
    """Fold the telemetry summary into the record (never allowed to
    break the bench)."""
    try:
        from mxnet_tpu import telemetry
        if telemetry.enabled():
            summ = telemetry.summary()
            if summ:  # nothing ran — keep the record shape unchanged
                record["telemetry"] = summ
    except Exception as e:
        print("telemetry summary failed: %r" % (e,), file=sys.stderr)
    return record


def _autotune_fields(record):
    """Fold the autotuner counters into the record when tuning is on
    (never allowed to break the bench): DB hits prove a fleet-shipped
    tuning DB actually fed this run's configs."""
    try:
        from mxnet_tpu import autotune
        if autotune.enabled():
            record["autotune"] = autotune.stats()
    except Exception as e:
        print("autotune stats failed: %r" % (e,), file=sys.stderr)
    return record


def _guardian_fields(record):
    """Fold the training-guardian counters into the record when the
    guardian is on (never allowed to break the bench): a bench number
    produced alongside skips/rollbacks is not a clean number, and
    anomaly counts on real hardware are the SDC-rate signal."""
    try:
        from mxnet_tpu import guardian
        if guardian.enabled():
            record["guardian"] = guardian.stats()
    except Exception as e:
        print("guardian stats failed: %r" % (e,), file=sys.stderr)
    return record


def main(argv=None):
    """Single-process bench (the pre-r5 behavior): ResNet first, then the
    flash kernel + transformer-LM secondaries (``--in-process``)."""
    cli = _arg_parser().parse_args(argv)

    record = resnet_bench(cli)
    if "error" in record:
        print(json.dumps(record))
        return record
    backend = record.get("backend")
    if backend == "tpu" and not cli.skip_attention:
        # Never allowed to break the headline.
        try:
            _flash_kernel_fields(record)
        except Exception as e:
            print("flash-attention secondary bench failed: %r" % (e,),
                  file=sys.stderr)
    if backend == "tpu" and not cli.skip_transformer:
        try:
            _lm_fields(record, cli)
        except Exception as e:
            print("transformer-LM secondary bench failed: %r" % (e,),
                  file=sys.stderr)
    # keep the resnet-shaped record (metric/value = img/s) — the
    # checklist summarizer scores this shape; only the orchestrated CLI
    # reshapes the headline via _headline()
    _telemetry_fields(record)
    _autotune_fields(record)
    _guardian_fields(record)
    print(json.dumps(record))
    return record


def _phase(cli):
    """Run one phase in THIS process and print its partial record."""
    record = {}
    if cli.phase == "resnet":
        record = resnet_bench(cli)
        # when the lm phase is skipped entirely, the flash kernel
        # secondary still belongs somewhere — run it here
        if (record.get("backend") == "tpu" and cli.skip_transformer
                and not cli.skip_attention and "error" not in record):
            try:
                _flash_kernel_fields(record)
            except Exception as e:
                print("flash kernel secondary failed: %r" % (e,),
                      file=sys.stderr)
    else:
        import jax

        record["backend"] = jax.default_backend()
        if record["backend"] != "tpu":
            record["lm_skipped"] = "backend %s" % record["backend"]
        else:
            _lm_fields(record, cli)
            if not cli.skip_attention:
                try:
                    _flash_kernel_fields(record)
                except Exception as e:
                    print("flash kernel secondary failed: %r" % (e,),
                          file=sys.stderr)
    _telemetry_fields(record)
    _autotune_fields(record)
    _guardian_fields(record)
    print(json.dumps(record))
    return record


def _run_phase(phase, cli, timeout):
    """Run ``bench.py --phase <phase>`` as a subprocess with a HARD
    timeout (SIGKILL reaches a wedge inside a native XLA call, which an
    in-process SIGALRM cannot). Returns the
    phase's record dict, or an {"..._error": msg} dict."""
    passthrough = ["--phase", phase,
                   "--num-steps", str(cli.num_steps),
                   "--warmup", str(cli.warmup),
                   "--lr", str(cli.lr), "--dtype", cli.dtype,
                   "--lm-seq-len", str(cli.lm_seq_len),
                   "--lm-hidden", str(cli.lm_hidden),
                   "--lm-layers", str(cli.lm_layers),
                   "--lm-batch", str(cli.lm_batch)]
    if cli.batch_size:
        passthrough += ["--batch-size", str(cli.batch_size)]
    if cli.skip_attention:
        passthrough += ["--skip-attention"]
    if cli.skip_transformer:
        passthrough += ["--skip-transformer"]
    err_key = "%s_error" % phase
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + passthrough,
            capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {err_key: "phase killed after %ds (wedged compile or "
                         "unreachable TPU backend)" % timeout}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if "error" in rec:
            # normalize any child-side failure (including the __main__
            # fallback JSON, which carries metric/value keys that must
            # not contaminate the merged record) to one error field
            return {err_key: str(rec["error"])[:300]}
        return rec
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {err_key: "rc=%d %s" % (proc.returncode,
                                   "; ".join(tail[-2:])[:300])}


def _kvstore_fields(timeout=240):
    """CPU-only kvstore transport phase (tools/bench_kvstore.py) in a
    subprocess: sync vs async vs async+bucketed push/pull throughput
    over many small keys. Needs no accelerator, so the comm-engine perf
    trajectory gets numbers on a machine without a chip."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_kvstore.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run([sys.executable, script],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"kvstore_error":
                "kvstore phase killed after %ds" % timeout}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        return {"kvstore_pushpull_ops_s": rec.get("async_bucket_ops_s"),
                "kvstore_sync_ops_s": rec.get("sync_ops_s"),
                "kvstore_async_ops_s": rec.get("async_ops_s"),
                "kvstore_speedup_async": rec.get("speedup_async"),
                "kvstore_speedup_bucket": rec.get("speedup_bucket")}
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {"kvstore_error": "rc=%d %s" % (proc.returncode,
                                           "; ".join(tail[-2:])[:300])}


def _sparse_fields(timeout=300):
    """CPU-only sparse parameter plane phase (tools/bench_sparse.py) in a
    subprocess: touched-rows push+pull over sharded embedding tables vs
    the dense full-table push a sparse-less kvstore would pay each step,
    plus the flat-worker-memory check."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_sparse.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run([sys.executable, script],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"sparse_error": "sparse phase killed after %ds" % timeout}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        return {"sparse_pushpull_rows_s": rec.get("sparse_rows_s"),
                "sparse_step_ms": rec.get("sparse_step_ms"),
                "sparse_vs_dense_fulltable": rec.get("vs_baseline"),
                "sparse_worker_bytes_flat":
                    rec.get("worker_bytes_flat_vs_table")}
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {"sparse_error": "rc=%d %s" % (proc.returncode,
                                          "; ".join(tail[-2:])[:300])}


def _shard_probe_fields(timeout=600):
    """CPU-only GSPMD sharding smoke (tools/shard_probe.py) on a simulated
    8-device mesh: megatron-ruled transformer LM fused step, reporting the
    per-device vs replicated param bytes and the post-SPMD collective mix.
    Needs no accelerator."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "shard_probe.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=8"))
    try:
        proc = subprocess.run([sys.executable, script, "--smoke"],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"shard_probe_error":
                "shard probe killed after %ds" % timeout}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        return {"shard_mesh": rec.get("mesh"),
                "shard_params_bytes": rec.get("params_sharded_bytes"),
                "shard_replicated_bytes": rec.get("params_replicated_bytes"),
                "shard_collectives": rec.get("collectives")}
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {"shard_probe_error": "rc=%d %s" % (proc.returncode,
                                               "; ".join(tail[-2:])[:300])}


def _coldstart_fields(timeout=300):
    """CPU-only serving cold-start phase (tools/bench_coldstart.py):
    time-to-first-prediction for a fresh replica, measured cold (empty
    compile cache: every bucket compiles) and again warm (same cache
    dir: every bucket deserializes).  The warm run must report cache
    hits with zero compiles and a bit-identical first prediction — the
    PR-10 compile-once acceptance measurement, runnable with no
    accelerator."""
    import tempfile

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_coldstart.py")

    def run_once(cache_dir):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   MXNET_COMPILE_CACHE_DIR=cache_dir)
        proc = subprocess.run([sys.executable, script],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        raise RuntimeError("rc=%d %s" % (proc.returncode,
                                         "; ".join(tail[-2:])[:300]))

    try:
        with tempfile.TemporaryDirectory(prefix="mxtpu-cc-bench-") as d:
            cold = run_once(d)
            warm = run_once(d)
    except (subprocess.TimeoutExpired, RuntimeError, OSError) as e:
        return {"coldstart_error": str(e)[:300]}
    fields = {
        "coldstart_cold_ttfp_ms": cold.get("ttfp_ms"),
        "coldstart_warm_ttfp_ms": warm.get("ttfp_ms"),
        "coldstart_warm_hits": warm.get("cache", {}).get("hits"),
        "coldstart_warm_compiles": warm.get("cache", {}).get("misses"),
        "coldstart_outputs_identical":
            cold.get("out_digest") == warm.get("out_digest"),
    }
    if cold.get("ttfp_ms") and warm.get("ttfp_ms"):
        fields["coldstart_speedup"] = round(
            cold["ttfp_ms"] / warm["ttfp_ms"], 2)
    return fields


def _generate_fields(timeout=600):
    """CPU-only generative-serving phase (tools/bench_generate.py):
    continuous-batching tokens/s under a mixed-length workload vs the
    naive sequential full-prefix re-decode baseline (batch=1, no KV),
    plus TTFT/ITL percentiles, KV-pool peak pages against the
    live-token bound, and the post-warmup decode compile count (zero or
    the shape-static decode loop regressed)."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_generate.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def _mode(extra_args):
        try:
            proc = subprocess.run([sys.executable, script] + extra_args,
                                  capture_output=True, text=True,
                                  timeout=timeout, env=env)
        except (subprocess.TimeoutExpired, OSError) as e:
            return None, str(e)[:300]
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                return json.loads(line), None
            except ValueError:
                continue
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        return None, "rc=%d %s" % (proc.returncode,
                                   "; ".join(tail[-2:])[:300])

    fields = {}
    rec, err = _mode([])
    if rec is None:
        fields["generate_error"] = err
    else:
        fields.update({
            "generate_tokens_per_sec": rec.get("value"),
            "generate_naive_tokens_per_sec":
                rec.get("naive_tokens_per_sec"),
            "generate_speedup_vs_naive": rec.get("speedup_vs_naive"),
            "generate_outputs_identical": rec.get("outputs_identical"),
            "generate_ttft_ms_p50": rec.get("ttft_ms_p50"),
            "generate_ttft_ms_p99": rec.get("ttft_ms_p99"),
            "generate_itl_ms_p50": rec.get("itl_ms_p50"),
            "generate_itl_ms_p99": rec.get("itl_ms_p99"),
            "generate_peak_pages": rec.get("peak_pages"),
            "generate_live_token_page_bound":
                rec.get("live_token_page_bound"),
            "generate_cold_decode_runs": rec.get("cold_decode_runs"),
        })
    # prefix-cache phase: TTFT cached vs uncached on a shared-prefix storm
    rec, err = _mode(["--prefix-reuse"])
    if rec is None:
        fields["generate_prefix_error"] = err
    else:
        fields.update({
            "generate_prefix_ttft_reduction": rec.get("value"),
            "generate_prefix_ttft_ms_p50_cached":
                rec.get("ttft_ms_p50_cached"),
            "generate_prefix_ttft_ms_p50_uncached":
                rec.get("ttft_ms_p50_uncached"),
            "generate_prefix_outputs_identical":
                rec.get("outputs_identical"),
            "generate_prefix_hits": rec.get("prefix_hits"),
            "generate_prefix_prefill_tokens_cached":
                rec.get("prefill_tokens_cached"),
        })
    # speculative phase: draft+verify tokens/s vs the plain engine
    rec, err = _mode(["--draft"])
    if rec is None:
        fields["generate_draft_error"] = err
    else:
        fields.update({
            "generate_draft_speedup": rec.get("value"),
            "generate_draft_tokens_per_sec":
                rec.get("tokens_per_sec_draft"),
            "generate_draft_acceptance": rec.get("acceptance"),
            "generate_draft_k": rec.get("draft_k"),
            "generate_draft_outputs_identical":
                rec.get("outputs_identical"),
        })
    return fields


def _platform_fields(timeout=300):
    """CPU-only multi-model platform phase (tools/bench_platform.py) in
    a subprocess: N models on a pool with room for N/2, diurnal demand
    swings driving page-out/fault-in cycles over AOT bundles, plus a
    tenant flood measuring per-tenant shed isolation."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_platform.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run([sys.executable, script],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
    except (subprocess.TimeoutExpired, OSError) as e:
        return {"platform_error": str(e)[:300]}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        return {
            "platform_models": rec.get("models"),
            "platform_capacity_models": rec.get("capacity_models"),
            "platform_cold_fault_in_ms": rec.get("cold_fault_in_ms"),
            "platform_warm_fault_in_ms": rec.get("warm_fault_in_ms"),
            "platform_warm_speedup": rec.get("warm_speedup"),
            "platform_fault_ins": rec.get("fault_ins"),
            "platform_page_outs": rec.get("page_outs"),
            "platform_warm_cold_bucket_runs":
                rec.get("warm_cold_bucket_runs"),
            "platform_tenant_p99_ms": rec.get("tenant_p99_ms"),
            "platform_noisy_shed": rec.get("noisy_shed"),
            "platform_good_shed": rec.get("good_shed"),
        }
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {"platform_error": "rc=%d %s" % (proc.returncode,
                                            "; ".join(tail[-2:])[:300])}


def orchestrate(argv=None):
    """Default CLI path: LM phase first (fast; provisional headline line
    printed immediately), then the ResNet phase, then the merged record.
    The driver parses the LAST JSON line, so a kill at any point after
    the LM phase still leaves a scored result."""
    cli = _arg_parser().parse_args(argv)
    record = {}

    # CPU-only phases first: they need no accelerator
    kv_fields = {} if cli.skip_kvstore else \
        _kvstore_fields(cli.kvstore_timeout)
    sparse_fields = {} if cli.skip_sparse else \
        _sparse_fields(cli.sparse_timeout)
    shard_fields = {} if cli.skip_shard_probe else \
        _shard_probe_fields(cli.shard_probe_timeout)
    coldstart_fields = {} if cli.skip_coldstart else \
        _coldstart_fields(cli.coldstart_timeout)
    generate_fields = {} if cli.skip_generate else \
        _generate_fields(cli.generate_timeout)
    platform_fields = {} if cli.skip_platform else \
        _platform_fields(cli.platform_timeout)

    def finish(rec):
        rec.update(kv_fields)
        rec.update(sparse_fields)
        rec.update(shard_fields)
        rec.update(coldstart_fields)
        rec.update(generate_fields)
        rec.update(platform_fields)
        print(json.dumps(rec))
        return rec

    if not cli.skip_transformer:
        record.update(_run_phase("lm", cli, cli.lm_timeout))
        if record.get("transformer_lm_mfu"):
            print(json.dumps(_headline(dict(record))), flush=True)

    resnet = _run_phase("resnet", cli, cli.resnet_timeout)
    metric_fields = {k: resnet.pop(k, None) for k in
                     ("metric", "value", "unit", "vs_baseline")}
    record.update({k: v for k, v in resnet.items() if v is not None})
    if metric_fields.get("metric"):
        record.update({k: v for k, v in metric_fields.items()
                       if v is not None})

    record = _headline(record)
    if "value" not in record:  # both phases failed
        record = {"metric": "transformer_lm_train_mfu", "value": 0.0,
                  "unit": "MFU", "vs_baseline": 0.0,
                  "error": "; ".join(str(record[k]) for k in record
                                     if k.endswith("_error"))[:300]}
    return finish(record)


if __name__ == "__main__":
    try:
        if "--phase" in sys.argv:
            _phase(_arg_parser().parse_args())
        elif "--in-process" in sys.argv:
            main()
        else:
            rec = orchestrate()
            if "error" in rec:
                sys.exit(1)
    except Exception as e:  # emit the one JSON line even on failure
        print(json.dumps({"metric": "transformer_lm_train_mfu",
                          "value": 0.0, "unit": "MFU",
                          "vs_baseline": 0.0,
                          "error": "%s: %s" % (type(e).__name__,
                                               str(e)[:300])}))
        sys.exit(1)
