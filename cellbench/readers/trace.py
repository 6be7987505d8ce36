"""Source kind: the device trace. The reduction from a profiler trace
(`*.xplane.pb`, read with `jax.profiler.ProfileData` and nothing else)
to busy time, idle share, the operations that took most time, and the
idle gaps by what the host was doing in them.

`reduce_events` works on plain lists of (name, start_ns, duration_ns),
so the arithmetic is tested without a trace; `load` is the only part
that knows the file's layout and is tested on the recorded trace in
`cellbench/testdata/`.
"""
import bisect
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NO_SPAN = "_no_span_"


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return found[-1]


def load(path):
    """({device plane name: [(name, start_ns, dur_ns)]}, host events).
    Device events are the `XLA Ops` line of each TPU plane; host events
    are every event with a duration on the host's thread lines."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns),
                             float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return devices, host


def union(events):
    """Merged busy intervals [(start, end)] of (name, start, dur)."""
    out = []
    for _n, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def _stem(name):
    """A name the ledger can hold: `%copy.3 = bf16[...] copy(...)` ->
    `copy.3`, `PjitFunction(step)` -> `PjitFunction_step_`."""
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    return "".join(c if c.isalnum() or c in "._-" else "_"
                   for c in name)[:64]


def _is_program_span(name):
    """Host events that say what the program's host code was doing:
    JAX's dispatch of a jitted function (`PjitFunction(name)`) and
    `TraceAnnotation`s. The runtime's own events (allocator, transfer,
    sync flags) lie under these and say nothing of the cause."""
    return name.startswith(("PjitFunction(", "cellbench.", "mxnet."))


def attribute(gaps, host):
    """Idle seconds by the program span (see `_is_program_span`)
    covering each gap's middle: the shortest such span, `_no_span_`
    where none does."""
    by = {}
    host = sorted((e for e in host if _is_program_span(e[0])),
                  key=lambda e: e[1])
    starts = [e[1] for e in host]
    longest = max((e[2] for e in host), default=0.0)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        best = None
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and starts[i] >= mid - longest:
            n, hs, hd = host[i]
            if hs + hd >= mid and (best is None or hd < best[1]):
                best = (n, hd)
            i -= 1
        key = _stem(best[0]) if best else NO_SPAN
        by[key] = by.get(key, 0.0) + (e - s) * 1e-9
    return sorted(by.items(), key=lambda kv: -kv[1])


def reduce_events(devices, host, window_s=None):
    """The summary of one traced window. Busy time is the union of
    device-op intervals, averaged over devices; the idle share is that
    of the worst device; the window is the span from the first to the
    last device event unless `window_s` gives the traced length."""
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    spans, busy, tops, gaps_all = [], [], {}, []
    for name, events in devices.items():
        if not events:
            continue
        merged = union(events)
        spans.append((merged[0][0], merged[-1][1]))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for n, _s, d in events:
            tops[_stem(n)] = tops.get(_stem(n), 0.0) + d * 1e-9
        gaps_all.append([(a[1], b[0]) for a, b in zip(merged, merged[1:])])
    span = max(e for _s, e in spans) - min(s for s, _e in spans)
    win = float(window_s) if window_s else span * 1e-9
    win = max(win, span * 1e-9)
    idle_share = [1.0 - b / win for b in busy]
    worst = max(range(len(busy)), key=lambda i: idle_share[i])
    n_dev = len(busy)
    return {"busy_s": sum(busy) / n_dev, "window_s": win,
            "idle_share": idle_share[worst],
            "device_ops": [[n, s / n_dev] for n, s in sorted(
                tops.items(), key=lambda kv: -kv[1])],
            "idle_gaps": [[n, s] for n, s in
                          attribute(gaps_all[worst], host)]}


def summarize(handle):
    """From a finished trace handle (`dir`, `window_s`) to the summary."""
    devices, host = load(newest_xplane(handle["dir"]))
    return reduce_events(devices, host, handle.get("window_s"))


def read(readings, what="idle_share", scale=100.0):
    summary = readings.get("trace")
    return None if not summary else scale * summary[what]
