import math

import numpy as np

from benchmark.lib import stats


def test_order_stat_is_exact_nearest_rank():
    v = [5, 1, 4, 2, 3, 10, 9, 8, 7, 6]
    assert stats.order_stat(v, 0.5) == 5
    assert stats.order_stat(v, 0.9) == 9
    assert stats.order_stat(v, 0.99) == 10
    assert stats.order_stat(v + [math.inf], 0.99) == math.inf
    assert stats.finite_ms(math.inf) == stats.INF_MS
    assert math.isnan(stats.order_stat([], 0.5))


def _steady(step=0.1, tokens=24.0, seconds=10.0):
    ends = np.arange(0.0, seconds + 5 * step, step)
    return ends - step, ends, np.full(ends.size, tokens)


def test_slice_median_is_the_rate_of_whole_iterations():
    starts, ends, tok = _steady()
    # window edges fall inside iterations: no edge effect, no 32-token steps
    got = stats.slice_median_rate(ends, tok, 0.033, 10.033)
    assert abs(got - 240.0) < 1e-6


def test_a_stall_counts_in_full_in_the_judged_rate_and_not_in_the_median():
    """``serve_tokens_per_s`` is the whole-window rate: all the work over all
    the time.  The slice median beside it says what the rate is without the
    stall, and ``stall_share`` how much of the window the stall took."""
    starts, ends, tok = _steady()
    clean = stats.slice_median_rate(ends, tok, 0.0, 10.0)
    # the iteration that should end at 4.3 s takes 1.4 s longer
    k = int(np.searchsorted(ends, 4.25))
    ends2 = ends.copy()
    ends2[k:] += 1.4
    starts2 = starts.copy()
    starts2[k + 1:] += 1.4
    stalled = stats.slice_median_rate(ends2, tok, 0.0, 10.0)
    assert abs(stalled - clean) < 1e-6
    whole_clean = stats.whole_step_rate(ends, 0.0, 10.0, tok)
    whole_stalled = stats.whole_step_rate(ends2, 0.0, 10.0, tok)
    assert abs(whole_clean - 240.0) < 1e-6
    # 1.4 s of 10 lost, and the judged rate loses all of them: 86 whole
    # iterations where there were 100
    assert abs(whole_stalled - 24.0 * 86 / 10.0) < 1e-6
    assert stats.stall_share(starts, ends, 0.0, 10.0) == 0.0
    share = stats.stall_share(starts2, ends2, 0.0, 10.0)
    assert abs(share - 15.0) < 0.01                 # 1.5 s of 10


def test_slice_with_no_iteration_end_has_rate_zero():
    ends = np.array([0.5, 9.5])
    rates = stats.slice_rates(ends, np.array([10.0, 10.0]), 0.0, 10.0)
    assert rates[0] == 0.0 and rates[5] == 0.0
    assert abs(rates[9] - 10.0 / 9.0) < 1e-9


def test_whole_step_rate_has_no_window_edge():
    ends = 0.25 + 0.5 * np.arange(30)
    got = stats.whole_step_rate(ends, 1.0, 11.0, 16384)
    assert abs(got - 16384 / 0.5) < 1e-6
    assert math.isnan(stats.whole_step_rate(ends[:1], 0.0, 1.0, 1))


def test_whole_step_rate_counts_each_steps_own_work():
    # iterations of unequal work and length; the window's edges fall inside
    # iterations, and the one that ended before it opened is not counted
    ends = np.array([0.9, 1.0, 1.3, 1.4, 2.0, 2.1, 3.5])
    work = np.array([99.0, 7.0, 24.0, 20.0, 24.0, 10.0, 99.0])
    got = stats.whole_step_rate(ends, 0.95, 3.0, work)
    assert abs(got - (24.0 + 20.0 + 24.0 + 10.0) / (2.1 - 1.0)) < 1e-9
