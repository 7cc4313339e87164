"""The whole-step arithmetic: one slow step, and a last step the clock
cuts, must not move the rate by more than the work they hold."""

import pytest

from chipbench import stats


def series(n, step=0.5, work=100, slow_at=None, slow=2.0):
    out, t = [], 0.0
    for i in range(n):
        d = slow if i == slow_at else step
        out.append((t, t + d, work))
        t += d
    return out


def test_a_cut_last_step_is_counted_whole():
    # 20.2 steps of 0.5 s fit a 10.1 s window: the 21st starts inside it
    recs = series(30)
    whole = stats.whole_steps(recs, 10.1)
    assert len(whole) == 21
    assert stats.rate_over_steps(whole) == pytest.approx(200.0)
    # a clock window would have read 21 * 100 / 10.1 = 207.9 or 198.0
    for seconds in (9.9, 10.0, 10.1, 10.3, 10.49):
        assert stats.rate_over_steps(
            stats.whole_steps(recs, seconds)) == pytest.approx(200.0)


def test_a_slow_step_costs_its_own_time_only():
    recs = series(40, slow_at=7)
    whole = stats.whole_steps(recs, 15.0)
    n = len(whole)
    span = whole[-1][1] - whole[0][0]
    assert stats.rate_over_steps(whole) == pytest.approx(100.0 * n / span)
    # where the slow step falls in the window changes nothing
    rates = {round(stats.rate_over_steps(stats.whole_steps(
        series(40, slow_at=k), 15.0)), 9) for k in (0, 7, 20)}
    assert len(rates) == 1
    # the median step does not see it at all: the rate does
    assert stats.median(stats.durations(whole)) == pytest.approx(0.5)
    assert stats.rate_over_steps(whole) < 0.95 * 100.0 / 0.5


def test_a_stall_inside_the_window_moves_the_rate():
    """The end-to-end rate is all the work over all the time: a window
    that holds a 12 s stall reads that much lower (the median step would
    have read 'unchanged')."""
    calm = stats.whole_steps(series(80, step=0.45), 30.0)
    stalled = stats.whole_steps(series(80, step=0.45, slow_at=20,
                                       slow=12.45), 30.0)
    assert len(stalled) < len(calm)
    assert stats.median(stats.durations(stalled)) == pytest.approx(0.45)
    assert stats.rate_over_steps(stalled) == pytest.approx(
        100.0 * len(stalled) / (0.45 * (len(stalled) - 1) + 12.45))
    assert stats.rate_over_steps(stalled) < 0.75 * stats.rate_over_steps(calm)


def test_quantile_matches_numpy():
    import numpy as np
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 1.0):
        assert stats.quantile(xs, q) == pytest.approx(np.quantile(xs, q))


def test_no_step_is_an_error():
    with pytest.raises(ValueError):
        stats.rate_over_steps([])
