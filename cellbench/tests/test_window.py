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


def _log_of(requests, tokens, seed):
    """A synthetic client log: `requests` streams of `tokens` tokens,
    exponential gaps about a 12 ms step, a few long stops among them."""
    import numpy as np
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(0.012, (requests, tokens))
    gaps[rng.random((requests, tokens)) < 0.002] += 0.25
    return np.cumsum(gaps, axis=1).tolist()


def _window_fields_as_first_written(token_times, t_open, t_close):
    """What kind `serve` logged and reported of a window's gaps until
    PR 40, kept as the plain statement of what is meant: the median is
    taken again for every gap, so it costs the square of their number."""
    gaps_ms = [1e3 * g for g in
               window.gaps_in(token_times, t_open, t_close)]
    return {"gaps": len(gaps_ms),
            "serve_itl_p50_ms": window.median(gaps_ms),
            "serve_itl_p99_ms": window.percentile(gaps_ms, 99)[0],
            "gaps_over_3x_median_ms": window.outliers(gaps_ms),
            "long_gaps_by_100_ms": window.histogram(
                [g for g in gaps_ms if g > 3 *
                 (window.median(gaps_ms) or 0)], 100)}


def _window_fields(token_times, t_open, t_close):
    gaps_ms, p50, p99, longest, by_100 = window.reduce_gaps(
        token_times, t_open, t_close)
    return {"gaps": len(gaps_ms), "serve_itl_p50_ms": p50,
            "serve_itl_p99_ms": p99, "gaps_over_3x_median_ms": longest,
            "long_gaps_by_100_ms": by_100}, gaps_ms


def test_a_window_s_gaps_are_reduced_in_one_pass():
    """The drives' one reduction: on 2 000 gaps the fields of the
    `window` line are those of the first expression, key for key; on
    100 000 it takes under two seconds (the first expression: hours)."""
    import time
    small = _log_of(20, 101, seed=1)
    edges = (0.05, 1.1)
    got, gaps = _window_fields(small, *edges)
    assert got == _window_fields_as_first_written(small, *edges)
    assert 1500 < got["gaps"] < 2000 and got["gaps_over_3x_median_ms"]
    assert len(got["long_gaps_by_100_ms"]) >= 2
    assert gaps == sorted(1e3 * g for g in window.gaps_in(small, *edges))
    large = _log_of(200, 501, seed=2)
    t0 = time.perf_counter()
    got, gaps = _window_fields(large, 0.0, 1e9)
    assert time.perf_counter() - t0 < 2.0
    assert got["gaps"] == len(gaps) == 100000
    assert got["serve_itl_p50_ms"] == window.median(gaps)
    assert got["gaps_over_3x_median_ms"] == window.outliers(gaps)
    assert sum(got["long_gaps_by_100_ms"].values()) == sum(
        g > 3 * got["serve_itl_p50_ms"] for g in gaps)
    assert window.reduce_gaps([], 0.0, 1.0) == ([], None, None, [], {})


def test_a_window_whose_median_gap_is_nought_has_no_tail():
    """A degenerate log (more than half of the gaps are 0): the
    reduction reports the median and the 99th percentile and no tail,
    in either of the two fields; the first expression agreed on the
    largest and counted every gap above 0 in the histogram."""
    log = [[0.0, 0.0, 0.0, 0.0, 0.5], [0.1, 0.1, 0.1]]
    gaps, p50, p99, longest, by_100 = window.reduce_gaps(log, -1.0, 1.0)
    assert gaps == [0.0] * 5 + [500.0] and p50 == 0.0 and p99 == 500.0
    assert longest == [] and by_100 == {}
    first = _window_fields_as_first_written(log, -1.0, 1.0)
    assert first["gaps_over_3x_median_ms"] == []
    assert first["long_gaps_by_100_ms"] == {500: 1}
