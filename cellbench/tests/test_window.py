"""Window arithmetic on a synthetic event log."""
import math

import pytest

from cellbench import window


def test_percentile_says_what_it_rests_on():
    values = list(range(1, 201))            # 1..200
    assert window.percentile(values, 99) == (198, 200, 2)
    assert window.percentile(values, 50) == (100, 200, 100)
    assert window.percentile([], 99) == (None, 0, 0)
    # a failed request counts as the largest value
    assert window.percentile([1.0, math.inf, 2.0], 99)[0] == math.inf
    assert window.median([3.0, 1.0, math.inf]) == 3.0
    assert window.median([1.0, 2.0, 3.0, 4.0]) == 2.5


def test_edges_fall_on_events_not_on_the_clock():
    marks = [0.5, 1.25, 2.0, 9.75, 11.5, 12.25, 30.0]
    assert window.aligned_edges(marks, 1.0, 10.5) == (1.25, 11.5)
    assert window.aligned_edges(marks, 1.25, 10.0) == (1.25, 9.75)
    # too short a log gives no window rather than a short one
    assert window.aligned_edges(marks, 12.0, 10.0) is None
    assert window.aligned_edges(marks, 31.0, 1.0) is None


def test_rate_counts_exactly_the_work_between_the_edges():
    arrivals = [i * 0.1 for i in range(1, 301)]      # 10 a second
    t_open, t_close = window.aligned_edges(arrivals, 5.04, 20.0)
    assert (t_open, t_close) == pytest.approx((5.1, 25.1))
    n = window.count_in(arrivals, t_open, t_close)
    assert n == 200
    assert n / (t_close - t_open) == pytest.approx(10.0)
    assert window.per_second(arrivals, t_open, t_close) == [10] * 20


def test_block_marks_are_every_fourth_completion():
    done = [3.0, 1.0, 2.0, 4.5, 5.0, 7.0, 6.0, 8.0, 9.0]
    assert window.block_marks(done, 4) == [4.5, 8.0]


def test_gaps_belong_to_the_window_of_their_later_token():
    reqs = [[1.0, 1.5, 2.5, 4.0], [2.0, 2.25], [10.0]]
    gaps = window.gaps_in(reqs, 1.5, 4.0)
    assert sorted(gaps) == [0.25, 1.0, 1.5]
    assert window.outliers([1, 1, 1, 1, 10, 4]) == [10, 4]
    assert window.histogram([5, 120, 180, 310], 100) == \
        {0: 1, 100: 2, 300: 1}


def test_spread_is_the_contract_s():
    values = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    import statistics
    q = statistics.quantiles(values, n=4)
    assert window.spread(values) == pytest.approx((q[2] - q[0]) / 100.0)
