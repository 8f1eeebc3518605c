"""Shared XLA cost-analysis and HLO-audit helpers.

One home for the flops/bytes-accessed introspection that used to be
copy-pasted across ``telemetry.step_monitor``, ``compile_cache``,
``tools/perf_probe.py`` and ``tools/layout_probe.py``: lower a program
and read XLA's own cost analysis of it.  Everything here runs on CPU with
no chip — lowering is shape-only.
"""
from __future__ import annotations

import collections
import re
from typing import Optional

from .base import env

__all__ = ["peak_flops", "cost_analysis", "lower_and_analyze",
           "hlo_op_counts", "collective_counts", "op_scopes",
           "bn_fusion_analysis"]

# Published per-chip peaks keyed by jax ``device_kind`` — the one table
# every MFU denominator reads.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16.
# A kind that is not listed has no peak: MFU is None, not the v5e's.
DEVICE_PEAKS = {
    # what jax reports for a v5e chip (chip_smoke.py run, PR 22)
    "TPU v5 lite": {"flops": 197e12},
}


def peak_flops(device_kind=None) -> Optional[float]:
    """MFU denominator: MXNET_TELEMETRY_PEAK_FLOPS override, else the
    published bf16 peak of ``device_kind`` (default: the attached
    device's); None for a kind DEVICE_PEAKS does not list."""
    v = env("MXNET_TELEMETRY_PEAK_FLOPS", 0.0, float)
    if v:
        return float(v)
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind, {}).get("flops")


def cost_analysis(compiled) -> Optional[dict]:
    """XLA's cost analysis of a compiled executable as
    ``{"flops", "bytes_accessed"}``, or None when the backend doesn't
    report one."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return {"flops": ca.get("flops"),
                "bytes_accessed": ca.get("bytes accessed")}
    except Exception:
        return None


def lower_and_analyze(fn, abstract):
    """Lower+compile a jitted program at abstract args and read XLA cost
    analysis.  Returns (compiled, {"flops", "bytes_accessed"}); compiled
    is None when the program can't be lowered (naive engine)."""
    if fn is None or not hasattr(fn, "lower"):
        return None, None
    lowered = fn.lower(*abstract)
    compiled = lowered.compile()
    return compiled, cost_analysis(compiled)


def hlo_op_counts(hlo_text, interesting=None) -> dict:
    """Histogram of HLO opcodes in a compiled ``as_text()`` dump,
    optionally filtered to an opcode whitelist."""
    ops = collections.Counter(
        re.findall(r"^\s*[%\w.-]+ = [\w\[\]<>{}, ]*?(\w+)\(", hlo_text,
                   re.M))
    if interesting is None:
        return dict(ops)
    return {k: v for k, v in ops.most_common() if k in interesting}


_COLLECTIVE_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?[\]})] "
    r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\((?:[^\n]*?channel_id=(\d+))?")


def collective_counts(hlo_text) -> dict:
    """``{"collectives", "asynchronous"}`` of a compiled module's text: its
    collectives (one per channel, not per opcode: the TPU compiler runs an
    asynchronous one as a chain of ``async_collective_fusion`` computations,
    each a step of the same all-reduce fused with the compute it runs
    under), and how many of them run asynchronously (such a chain, or a
    ``-start`` / ``-done`` pair).  One the scheduler found nothing to run
    under is a plain, synchronous op again and counts as that."""
    seen, asynchronous = set(), set()
    computation = ""
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "}"):  # a computation's header
            computation = line.replace("ENTRY ", "", 1).lstrip("%")
            continue
        m = _COLLECTIVE_OP_RE.match(line)
        if not m:
            continue
        key = m.group(3) or m.group(1)
        seen.add(key)
        if m.group(2) or computation.startswith("async_collective_fusion"):
            asynchronous.add(key)
    return {"collectives": len(seen), "asynchronous": len(asynchronous)}


_SCOPED_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?"
    r'op_name="([^"]*)"', re.M)


def op_scopes(hlo_text) -> dict:
    """{instruction name: scope path} of a compiled module's text, from
    each instruction's ``metadata={op_name="jit(fused_step)/fc1/dot"}``:
    the ``jax.named_scope`` stack the operation was traced under (a Symbol
    node's name, ``optimizer``, ``guard``, a kernel's name).  A fusion
    carries the scope of its root.  Instructions of fused computations
    are listed too; one without metadata is left out."""
    return {name: scope for name, scope in _SCOPED_RE.findall(hlo_text)}


def bn_fusion_analysis(hlo_text) -> dict:
    """Does BN's scale/shift ride the conv epilogue?

    Classifies every convolution by actual dataflow, not substring
    presence: a conv counts as epilogue-fused only when its RESULT name
    is an operand of a multiply/add/subtract inside the same non-entry
    fusion computation (the BN affine transform then costs no extra HBM
    round trip). Convs in the ENTRY computation are bare by definition —
    entry-level instructions are separate kernels even when an
    elementwise op consumes them there (worth ~2 MFU points per PERF.md's
    control-minus-BN-stats data if that is where BN's scale/shift run)."""
    # computations: optional ENTRY prefix, then 'name (...) -> ... {'.
    # The '%' name sigil is optional THROUGHOUT: modern compiled.as_text()
    # dumps omit it ('convolution.3 = f32[...] convolution(arg.1, ...)'),
    # classic dumps keep it — names are normalized sigil-less.
    blocks = re.findall(r"^(ENTRY\s+)?%?[\w.-]+ [^\n]*\{\n(.*?)^\s*\}",
                        hlo_text, re.M | re.S)
    fused = fused_plain = bare = 0
    for entry_prefix, body in blocks:
        conv_names = [m.group(1).lstrip("%") for m in re.finditer(
            r"(%?[\w.-]+)\s*=\s*\S+\s+convolution\(", body)]
        if not conv_names:
            continue
        if entry_prefix:
            bare += len(conv_names)
            continue
        ew_operands = set()
        for m in re.finditer(
                r"=\s*\S+\s+(?:multiply|add|subtract)\(([^)]*)\)", body):
            ew_operands.update(
                t.lstrip("%")
                for t in re.findall(r"%?[\w][\w.-]*", m.group(1)))
        for c in conv_names:
            if c in ew_operands:
                fused += 1
            else:
                fused_plain += 1
    return {"convs_total": fused + fused_plain + bare,
            "convs_fused_with_elementwise_epilogue": fused,
            "convs_fused_plain": fused_plain,
            "convs_bare_in_entry": bare}
