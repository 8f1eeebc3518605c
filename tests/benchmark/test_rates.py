"""The whole-step rate estimator, gaps and percentiles on synthetic stamps."""
import pytest

from perfbench.harness import rates


def _steps(step=0.5, lanes=8, n=100, t0=3.0):
    """Arrivals of ``n`` decode steps of ``lanes`` tokens each."""
    return [t0 + i * step for i in range(n) for _ in range(lanes)]


@pytest.mark.parametrize("shift", [0.0, 0.05, 0.2, 0.49])
def test_edge_moved_by_less_than_a_step_does_not_move_the_rate(shift):
    arr = _steps()
    base = rates.whole_step_rate(arr, 10.0 + 0.01, 40.0 + 0.01)[0]
    moved = rates.whole_step_rate(arr, 10.0 + 0.01 + shift,
                                  40.0 + 0.01 + shift)[0]
    assert moved == pytest.approx(base, rel=1e-12)
    assert base == pytest.approx(16.0, rel=1e-12)  # 8 lanes / 0.5 s


def test_wall_clock_rate_would_have_moved():
    arr = _steps()
    naive = [sum(1 for t in arr if a <= t <= a + 30.2) / 30.2
             for a in (10.01, 10.4)]
    assert naive[0] != naive[1]  # what the estimator is there to avoid


def test_rate_counts_arrivals_after_the_first_over_their_span():
    rate, n, t_a, t_b = rates.whole_step_rate([1.0, 2.0, 2.0, 4.0, 9.0],
                                              0.5, 5.0)
    assert (n, t_a, t_b) == (3, 1.0, 4.0)
    assert rate == pytest.approx(1.0)


def test_rate_refuses_an_empty_window():
    with pytest.raises(ValueError):
        rates.whole_step_rate([1.0, 1.0], 0.0, 2.0)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8),
                                    (100, 5.0)])
def test_percentile(q, want):
    assert rates.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_gaps_keep_only_those_ending_in_the_window():
    streams = [[0.0, 1.0, 2.0, 3.0], [2.5, 2.75]]
    assert rates.gaps_in_window(streams, 1.5, 2.8) == [1.0, 0.25]


def test_prefill_share_from_two_modes():
    # nine plain gaps of 0.5 s, one that held a 0.6 s prefill
    gaps = [0.5] * 9 + [1.1]
    assert rates.prefill_share_pct(gaps) == pytest.approx(
        100.0 * 0.6 / sum(gaps))
    assert rates.prefill_share_pct([]) is None


def test_spread_is_the_quartile_distance_over_the_median():
    import statistics
    vals = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    q = statistics.quantiles(vals, n=4)
    assert rates.iqr_share(vals) == pytest.approx((q[2] - q[0]) / 10.25)
