"""Source kind: the program's own host spans (`mxnet.*`, written by
`mxnet_tpu.trace.phase` as `TraceAnnotation`s) on the clock of the
device trace this process just took.

The arithmetic works on plain lists of (name, start_ns, duration_ns),
one list per host thread, so it is tested without a trace; `load_lines`
and `find_trace` are the only parts that know the file's layout and
are tested on the trace recorded in `cellbench/testdata/`.

A span's **self time** is its duration less the part of it that its
child spans cover. Children are found by containment on one thread.

`readings` carries the trace's summary and not its directory, so the
reader takes the newest `*.xplane.pb` under `<checkout>/cellbench_out/`
and refuses one written before this process started. With a program
that has no such spans (the parent of the PR that added them) every
metric reads `None` and is left out of the line.
"""
import bisect
import glob
import json
import os
import statistics

from cellbench.readers.trace import HOST_PLANE, load, union

PREFIX = "mxnet."
NO_PHASE = "_no_phase_"
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "cellbench_out")


# -- arithmetic on plain lists ----------------------------------------------

def nest(events):
    """For one thread's events, the index of each event's parent (the
    innermost other event that contains it) or None."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parents, stack = [None] * len(events), []
    for i in order:
        _n, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] < s + d:
            stack.pop()
        parents[i] = stack[-1] if stack else None
        stack.append(i)
    return parents


def covered(span, others):
    """Nanoseconds of `span` that the union of `others` covers."""
    _n, s, d = span
    clipped = [(n, max(s, os_), min(s + d, os_ + od) - max(s, os_))
               for n, os_, od in others
               if os_ < s + d and os_ + od > s]
    return sum(e - b for b, e in union(clipped))


def self_times(events):
    """[(name, duration_ns, self_ns)] for one thread's events: self is
    the duration less what the direct children cover (children that
    overlap one another count once)."""
    parents = nest(events)
    kids = {}
    for i, p in enumerate(parents):
        if p is not None:
            kids.setdefault(p, []).append(events[i])
    return [(ev[0], ev[2], ev[2] - covered(ev, kids.get(i, [])))
            for i, ev in enumerate(events)]


def less_child(events, span, less):
    """For each event named `span` on one thread: its duration less
    the part that the events named `less` inside it cover, in ns."""
    inner = [e for e in events if e[0] == less]
    return [e[2] - covered(e, inner) for e in events if e[0] == span]


def wall_share(lines, span, window_s):
    """Sum of the durations of the events named `span`, over the
    traced seconds; None where there is no such event."""
    durs = [d for ev in lines for n, _s, d in ev if n == span]
    return sum(durs) * 1e-9 / window_s if durs and window_s else None


def median_less_child(lines, span, less):
    vals = [v for ev in lines for v in less_child(ev, span, less)]
    return statistics.median(vals) if vals else None


def over(spans):
    """A function from an instant to the spans that cover it: `spans`
    sorted once, then a walk back from the instant no further than
    the longest span reaches."""
    spans = sorted(spans, key=lambda e: e[1])
    starts = [e[1] for e in spans]
    longest = max((e[2] for e in spans), default=0.0)

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and starts[i] >= t - longest:
            if spans[i][1] + spans[i][2] >= t:
                yield spans[i]
            i -= 1
    return at


def gaps_of(device_events):
    """Idle intervals [(start, end)] between a device's operations."""
    merged = union(device_events)
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def idle_by_phase(gaps, lines):
    """Idle seconds by the innermost `mxnet.*` span over each gap's
    middle, most first; `_no_phase_` where none covers it. The sum is
    the sum of the gaps."""
    at = over(e for ev in lines for e in ev)
    by = {}
    for s, e in gaps:
        inner = min(at(0.5 * (s + e)), key=lambda sp: sp[2],
                    default=None)
        key = inner[0] if inner else NO_PHASE
        by[key] = by.get(key, 0.0) + (e - s) * 1e-9
    return sorted(by.items(), key=lambda kv: -kv[1])


def uncovered_by_place(gaps, lines):
    """The `_no_phase_` seconds by where they lie: before the first
    phase the trace holds, after the last one, or between phases. A
    phase that was open when the trace started or stopped is not in
    the trace (a `TraceAnnotation` is recorded only whole), so the
    edges are the trace's to lose and `between` is the program's."""
    spans = [e for ev in lines for e in ev]
    if not spans:
        return None
    first = min(s for _n, s, _d in spans)
    last = max(s + d for _n, s, d in spans)
    at = over(spans)
    out = {"before": 0.0, "between": 0.0, "after": 0.0}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        if next(at(mid), None) is None:
            place = "before" if mid < first else \
                "after" if mid > last else "between"
            out[place] += (e - s) * 1e-9
    return out


def idle_share_under(gaps, lines, span):
    """Share of the idle seconds whose gap's middle lies under an
    event named `span`, at any depth; None where there is none."""
    under = [e for ev in lines for e in ev if e[0] == span]
    total = sum(e - s for s, e in gaps)
    if not under or not total:
        return None
    at = over(under)
    return sum(e - s for s, e in gaps
               if next(at(0.5 * (s + e)), None)) / total


def phase_table(lines):
    """{name: [count, median ms, total s, self s]} over all threads."""
    rows = {}
    for ev in lines:
        for n, d, self_ns in self_times(ev):
            rows.setdefault(n, []).append((d, self_ns))
    return {n: [len(v), statistics.median(d for d, _s in v) * 1e-6,
                sum(d for d, _s in v) * 1e-9,
                sum(s for _d, s in v) * 1e-9]
            for n, v in sorted(rows.items())}


# -- the file ---------------------------------------------------------------

def load_lines(path):
    """[[(name, start_ns, dur_ns)]]: the `mxnet.*` events of each host
    thread that has any."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            ev = [(e.name, float(e.start_ns), float(e.duration_ns))
                  for e in line.events
                  if e.duration_ns > 0 and e.name.startswith(PREFIX)]
            if ev:
                lines.append(ev)
    return lines


def process_started():
    """Wall-clock second at which this process started (Linux), or
    None where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f
                        if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return None


def find_trace(out=OUT, not_before=None):
    """The newest `*.xplane.pb` under `out`, or None where there is
    none or it was written before `not_before` (another run's)."""
    found = sorted(glob.glob(os.path.join(
        out, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not found:
        return None
    if not_before is not None and \
            os.path.getmtime(found[-1]) < not_before - 1.0:
        return None
    return found[-1]


def view(path, window_s):
    """Everything the metrics read, from one trace file."""
    devices, _host = load(path)
    lines = load_lines(path)
    # the device the summary's idle share and idle gaps are of
    idlest = min(devices.values(),
                 key=lambda ev: sum(e - s for s, e in union(ev)))
    return {"lines": lines, "gaps": gaps_of(idlest),
            "window_s": window_s}


def read(readings, what, span, less=None, scale=1.0):
    summary = readings.get("trace")
    if not summary:
        return None
    v = readings.get("_host_spans")
    if v is None:
        path = find_trace(not_before=process_started())
        if path is None:
            print("cellbench: host_spans no trace of this process "
                  "under %s" % OUT, flush=True)
            return None
        v = readings["_host_spans"] = view(path, summary["window_s"])
        by = idle_by_phase(v["gaps"], v["lines"])
        print("cellbench: idle_by_phase %s" % json.dumps(
            {"idle_s": sum(e - s for s, e in v["gaps"]) * 1e-9,
             "by": by,
             "no_phase_at": uncovered_by_place(v["gaps"], v["lines"])}),
            flush=True)
        print("cellbench: phase_n_medianms_totals_selfs %s"
              % json.dumps(phase_table(v["lines"])), flush=True)
    if what == "wall_share":
        out = wall_share(v["lines"], span, v["window_s"])
    elif what == "idle_share_under":
        out = idle_share_under(v["gaps"], v["lines"], span)
    elif what == "median_less_child_ms":
        out = median_less_child(v["lines"], span, less)
        out = None if out is None else out * 1e-6
    else:
        raise ValueError("host_spans: no reading %r" % what)
    return None if out is None else scale * out
