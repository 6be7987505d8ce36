"""Window arithmetic on event logs: work-aligned edges, rates over
exactly the interval between two events, percentiles that say how many
samples they rest on. Pure Python and numpy; nothing here touches JAX.
"""
import bisect
import math


def percentile(values, q):
    """(value, n, beyond): the q-th percentile (0..100) by the nearest
    rank above, the sample count, and how many samples lie beyond it.
    `math.inf` entries (failed requests) sort last. None when empty."""
    n = len(values)
    if not n:
        return None, 0, 0
    s = sorted(values)
    rank = min(n, max(1, math.ceil(q / 100.0 * n)))
    return s[rank - 1], n, n - rank


def median(values):
    """Median as the mean of the middle pair; None when empty."""
    n = len(values)
    if not n:
        return None
    s = sorted(values)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def aligned_edges(marks, start, seconds):
    """(open, close) among the sorted event times `marks`: the first
    mark at or after `start`, and the last mark at or before `seconds`
    after it. None if no such pair spans at least half of `seconds`."""
    i = bisect.bisect_left(marks, start)
    if i >= len(marks):
        return None
    t_open = marks[i]
    j = bisect.bisect_right(marks, t_open + seconds) - 1
    if j <= i or marks[j] - t_open < 0.5 * seconds:
        return None
    return t_open, marks[j]


def count_in(times, t_open, t_close):
    """Events in (t_open, t_close]."""
    return bisect.bisect_right(times, t_close) - \
        bisect.bisect_right(times, t_open)


def block_marks(completions, block):
    """Times at which the running count of completions reaches each
    multiple of `block`: the stream's work-aligned boundaries."""
    s = sorted(completions)
    return [s[k - 1] for k in range(block, len(s) + 1, block)]


def per_second(times, t_open, t_close):
    """Events in each whole second of the window."""
    n = int(t_close - t_open)
    return [count_in(times, t_open + k, t_open + k + 1) for k in range(n)]


def gaps_in(token_times, t_open, t_close):
    """Gaps between consecutive tokens of each request whose later
    token arrived in (t_open, t_close]. `token_times`: one sorted list
    of arrival times per request."""
    out = []
    for ts in token_times:
        for a, b in zip(ts, ts[1:]):
            if t_open < b <= t_close:
                out.append(b - a)
    return out


def reduce_gaps(token_times, t_open, t_close):
    """The window's gaps in milliseconds, sorted once, with what the
    serve drives log of them: (sorted gaps, median, 99th percentile,
    the ten largest over three medians, those over three medians
    counted by 100 ms). One sort, and the sorted list's tail: the cost
    is that of the sort however many gaps the window holds. A window
    with no gap, or whose median gap is 0 (tokens that all arrive in
    one frame: no real log), has no tail: both of the last are empty,
    where the drive's first expression counted every gap above 0 in
    the histogram and none among the largest."""
    gaps = sorted(1e3 * g for g in gaps_in(token_times, t_open, t_close))
    p50 = median(gaps)                    # sorting a sorted list: linear
    p99 = percentile(gaps, 99)[0]
    tail = gaps[bisect.bisect_right(gaps, 3 * p50):] if p50 else []
    return gaps, p50, p99, tail[::-1][:10], histogram(tail, 100)


def outliers(values, factor=3.0, limit=10):
    """The values over `factor` times the median, largest first."""
    m = median(values)
    if not m:
        return []
    return sorted((v for v in values if v > factor * m),
                  reverse=True)[:limit]


def histogram(values, width):
    """Counts per bucket of `width`, keyed by the bucket's lower edge."""
    out = {}
    for v in values:
        k = int(v // width) * width
        out[k] = out.get(k, 0) + 1
    return dict(sorted(out.items()))


def spread(values):
    """Interquartile distance over the median, as the contract takes
    it (statistics.quantiles, n=4)."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
