"""Source kind: the clients' clock. Reads a percentile of one series of
the run's event log (`gap_ms`: gaps between consecutive tokens of a
request)."""
import math

from cellbench import window


def read(readings, series, q):
    values = readings.get("series", {}).get(series)
    if not values:
        return None
    value = window.median(values) if q == 50 else \
        window.percentile(values, q)[0]
    return None if value is None or math.isinf(value) else value
