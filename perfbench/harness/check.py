"""``correct``: every number compared is printed beside its limit."""
import math
import statistics


class Checks:
    def __init__(self, limits):
        self.limits = dict(limits)
        self.rows = []

    def add(self, name, value):
        """Hold ``value`` (lower is better) to the cell's limit ``name``."""
        if name not in self.limits:
            raise KeyError("no limit named %r for this cell" % name)
        limit = float(self.limits[name])
        ok = value is not None and math.isfinite(value) and value <= limit
        self.rows.append((name, value, limit, ok))
        print("[check] %-28s %s  limit %.6g  %s"
              % (name, "%.6g" % value if value is not None else "none",
                 limit, "ok" if ok else "FAILED"), flush=True)
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r[3] for r in self.rows)


def worst_leaf_gap(got, want):
    """The widest gap between two per-leaf norms, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  Returns (gap, leaf)."""
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for k, ref in want.items():
        gap = abs(got[k] - ref) / max(ref, floor, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def worst_slice_gap(got, want):
    """The widest relative distance between two per-leaf gradient slices:
    the norm of their difference over the reference slice's norm or the
    median slice's, whichever is larger.  Unlike a gap between norms it
    sees rounding that has no bias.  Returns (worst gap, its leaf, the
    median leaf's gap)."""
    import numpy as np

    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    floor = statistics.median(norms.values())
    gaps = {k: float(np.linalg.norm(np.asarray(got[k], np.float64) - ref))
            / max(norms[k], floor, 1e-30) for k, ref in want.items()}
    if not all(math.isfinite(g) for g in gaps.values()):
        return float("inf"), min(gaps, key=lambda k: math.isfinite(gaps[k])), \
            float("inf")
    where = max(gaps, key=gaps.get)
    return gaps[where], where, statistics.median(gaps.values())


def training_numbers(got, want):
    """The numbers a training cell compares, program (or control) against
    the reference: {"loss_gap", "grad_norm_gap", "grad_slice_gap" (worst
    leaf), "grad_slice_median_gap" (median leaf), "delta_norm_gap"}."""
    loss_gap = max(abs(a - b) for a, b in zip(got["loss"], want["loss"]))
    if len(got["loss"]) != len(want["loss"]):
        loss_gap = float("inf")
    g, gleaf = worst_leaf_gap(got["grad_norm"], want["grad_norm"])
    d, dleaf = worst_leaf_gap(got["delta_norm"], want["delta_norm"])
    sl, sleaf, typ = worst_slice_gap(got["grad_slice"], want["grad_slice"])
    print("[check] worst leaves: gradient norm %s, gradient slice %s, "
          "parameter change %s" % (gleaf, sleaf, dleaf), flush=True)
    return {"loss_gap": loss_gap, "grad_norm_gap": g, "grad_slice_gap": sl,
            "grad_slice_median_gap": typ, "delta_norm_gap": d}
