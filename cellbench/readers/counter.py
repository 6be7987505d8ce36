"""Source kind: the program's counters (`decoder.stats()`, telemetry
histograms, `profiler.host_sync_count`, compile events), as differences
over the window. Reads `scale * num / den`, or `scale * num` where no
`den` is named. Exact on any backend."""


def read(readings, num, den=None, scale=1.0):
    if num not in readings:
        return None
    if den is None:
        return scale * readings[num]
    if not readings.get(den):
        return None
    return scale * readings[num] / readings[den]
