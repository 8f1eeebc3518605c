"""Readers of the state-space layers' per-layer metrics (``ops/ssm.py``:
scopes ``ssm_step`` and ``ssm_scan``; ``generation/engine.py``: the
``state_bytes`` of a ``gen:step``).  As every reader: the run's ``info`` in,
a number out, or None where the trace holds nothing for it (an untraced run,
a rehearsal on the host, a program without these scopes and span arguments).

A scope's time is a lower bound where XLA fuses across it, so no share of a
roofline is made of it: ``ssm_state_gb_per_step`` over
``ssm_step_ms_per_step`` is a rate to read beside the HBM's, not a metric.
"""
from perfbench.harness import spans as _spans


def _scope_s(info, scope):
    """(trace, device seconds under ``scope`` in the window) or (None, 0)."""
    tr = _spans.of_run(info)
    return (tr, _spans.op_s(tr, _spans.in_scope(scope))) if tr else (None, 0.0)


def _share_pct(info, scope):
    tr, secs = _scope_s(info, scope)
    busy = _spans.busy_s(tr) if tr else 0.0
    return 100.0 * secs / busy if busy > 0 and secs > 0 else None


def ssm_step_share_pct(info):
    return _share_pct(info, "ssm_step")


def ssm_scan_share_pct(info):
    return _share_pct(info, "ssm_scan")


def ssm_step_ms_per_step(info):
    tr, secs = _scope_s(info, "ssm_step")
    steps = _spans.named(tr, "gen:step") if tr else []
    return 1e3 * secs / len(steps) if steps and secs > 0 else None


def ssm_state_gb_per_step(info):
    """Mean over the window's ``gen:step`` spans of ``state_bytes``: the
    recurrent state (and convolution tails) of the step's lanes, each byte
    read once and written once by the step."""
    tr = _spans.of_run(info)
    sizes = [float(s.stats["state_bytes"])
             for s in (_spans.named(tr, "gen:step") if tr else [])
             if "state_bytes" in s.stats]
    return sum(sizes) / len(sizes) / 1e9 if sizes else None
