"""Source kind: `device.memory_stats()["peak_bytes_in_use"]`, the
fullest device, read when the window closes and before the reference
runs."""


def read(readings, scale=1e-9):
    peak = readings.get("memory.peak_bytes")
    return None if not peak else scale * peak
