"""Rates over whole steps, gaps and percentiles: the arithmetic that turns
stamps into metrics."""
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def whole_step_rate(arrivals, t_open, t_close):
    """Items per second over whole steps: with t_a the first arrival at or
    after ``t_open`` and t_b the last at or before ``t_close``, the
    arrivals in (t_a, t_b] over t_b - t_a.  Arrivals come in steps (all
    lanes of a decode step together), so no partial step is counted
    against a wall-clock edge.  Returns (rate, count, t_a, t_b)."""
    inside = sorted(t for t in arrivals if t_open <= t <= t_close)
    if len(inside) < 2 or inside[-1] <= inside[0]:
        raise ValueError("fewer than two distinct arrivals in the window")
    t_a, t_b = inside[0], inside[-1]
    n = sum(1 for t in inside if t > t_a)
    return n / (t_b - t_a), n, t_a, t_b


def gaps_in_window(streams, t_open, t_close):
    """Every gap between two consecutive tokens of one stream whose later
    token arrived inside the window, in seconds."""
    out = []
    for stamps in streams:
        for a, b in zip(stamps, stamps[1:]):
            if t_open <= b <= t_close:
                out.append(b - a)
    return out


def prefill_share_pct(gaps):
    """Share of the streams' waiting that was another request's prefill:
    the excess over one median gap of every gap above 1.5 medians, over
    the sum of all gaps."""
    if not gaps:
        return None
    med = statistics.median(gaps)
    excess = sum(g - med for g in gaps if g > 1.5 * med)
    return 100.0 * excess / sum(gaps)


def iqr_share(values):
    """Spread as the contract takes it: the distance between the first and
    third quartile (statistics.quantiles, n=4) over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
