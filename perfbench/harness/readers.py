"""What the per-layer metrics' readers share.  A reader takes the run's
``info`` (host stamps, counters, the reduced trace) and returns a number, or
None where it finds nothing to read; the harness then leaves the metric out.
"""
import re
import statistics

from perfbench.harness import arith, peaks, rates

# the flash kernels' events in a device trace are Pallas custom calls; their
# names (``jvp__.N:custom-call``) do not tell forward, dQ and dK/dV apart
CUSTOM_CALL_RE = re.compile(r"custom-call|custom_call|pallas|mosaic|"
                            r"tpu_custom", re.I)


def step_ms_p50(info):
    return statistics.median(info["step_ms"]) if info.get("step_ms") else None


def compiles_in_window(info):
    return float(info["compiles_in_window"])


def train_mfu_pct(info):
    if info.get("kind") != "train" or info["platform"] == "cpu":
        return None  # a rehearsal on the host has no device metric
    return arith.mfu_pct(info["flops_per_item"], info["rate"], info["chips"],
                         peaks.peak(info["device_kind"], "flops"))


def device_idle_share(info):
    red = info.get("trace")
    return 100.0 * red["idle_share"] if red else None


def peak_hbm_gb(info):
    return info["memory_peak_bytes"] / 1e9


def _per_step(info, seconds):
    n = len(info.get("step_ms") or [])
    return 1e3 * seconds / n if n else None


def allreduce_ms_per_step(info):
    red = info.get("trace")
    if not red or info["chips"] < 2:
        return None
    return _per_step(info, red["collective_s"])


def allreduce_exposed_ms_per_step(info):
    red = info.get("trace")
    if not red or info["chips"] < 2:
        return None
    return _per_step(info, red["collective_exposed_s"])


def flash_share_pct(info):
    """Device time of every custom call over busy time: the LM's step has
    no custom calls but the flash kernels."""
    red = info.get("trace")
    if not red or red["busy_s"] <= 0:
        return None
    secs = sum(v for k, v in red["by_name"].items()
               if CUSTOM_CALL_RE.search(k))
    return 100.0 * secs / red["busy_s"] if secs > 0 else None


def gen_ttft_p50_ms(info):
    return 1e3 * statistics.median(info["ttft"]) if info.get("ttft") else None


def gen_itl_p50_ms(info):
    return 1e3 * statistics.median(info["gaps"]) if info.get("gaps") else None


def gen_itl_p95_ms(info):
    gaps = info.get("gaps") or []
    return 1e3 * rates.percentile(gaps, 95) if len(gaps) >= 20 else None


def gen_lanes_per_step(info):
    c = info.get("counters")
    if not c or not c["steps"]:
        return None
    # a prefill emits its request's first token; the rest come from steps
    return (c["tokens"] - c["admitted"]) / float(c["steps"])


def gen_prefill_share_pct(info):
    return rates.prefill_share_pct(info.get("gaps") or [])
