"""Source kind: the chunk forwards of a chunked prefill, by the
program's own `mxnet.serve.decode.prefill_chunk` spans in the device
trace this process just took. A span carries the chunk's edges (`lo`,
`hi`: positions of the prompt) and, where the program writes it,
`run`: the rows the forward ran, of which one is real.

One reading, `roofline`: as `device_scope`'s, over the executions of
`module` (the chunk forward's program) that a chunk span dispatched:
the least seconds for what `need(tokens, rows)` of
`cellbench/ops/<family>.py` asks (`tokens` = `hi - lo`, `rows` =
`run`), over the device seconds under `scope` inside them (of the
whole execution where no scope is given), in per cent. (How many
chunks a request takes and how many of their rows are real are the
program's counters, read over the whole window by `counter`.)

A program that writes no such span, or no `run` on it (the parent of
the PR that added it), gives nothing to read: `None`, and the metric is
left out of the line. The trace's layout is `device_scope`'s to know:
its loader, its arithmetic and its roofline are used as they are.
"""
import importlib

from cellbench.readers import device_scope, host_spans
from cellbench.readers.trace import HOST_PLANE

CHUNK_SPAN = "mxnet.serve.decode.prefill_chunk"


def load_chunks(path):
    """[(start_ns, lo, hi, run)] of the chunk spans on the host, in
    order; `run` None where the span has none."""
    space = device_scope._xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            for e in line.events:
                if plane.event_metadata[e.metadata_id].name != CHUNK_SPAN:
                    continue
                stats = {names.get(s.metadata_id):
                         device_scope._stat_value(s, names)
                         for s in e.stats}
                if "lo" in stats and "hi" in stats:
                    run = stats.get("run")
                    out.append((line.timestamp_ns + e.offset_ps * 1e-3,
                                int(stats["lo"]), int(stats["hi"]),
                                None if run is None else int(run)))
    return sorted(out)


# -- arithmetic on plain lists ----------------------------------------------

def as_prefills(chunks):
    """The chunk spans in the form `device_scope.roofline` takes a
    prefill's in: (start_ns, tokens, rows)."""
    return [(s, hi - lo, run) for s, lo, hi, run in chunks
            if run is not None]


def read(readings, what, scope=None, module=None, need=None):
    summary = readings.get("trace")
    if not summary:
        return None
    chunks = readings.get("_chunk_spans")
    if chunks is None:
        path = host_spans.find_trace(
            not_before=host_spans.process_started())
        if path is None:
            print("cellbench: chunk_spans no trace of this process "
                  "under %s" % host_spans.OUT, flush=True)
            return None
        chunks = readings["_chunk_spans"] = load_chunks(path)
        if "_device_scope" not in readings:
            readings["_device_scope"] = device_scope.load(path)
    if what != "roofline":
        raise ValueError("chunk_spans: no reading %r" % what)
    spans = as_prefills(chunks)
    if not spans or "device_kind" not in readings:
        return None
    fn = getattr(importlib.import_module(
        "cellbench.ops." + readings["cfg"]["family"]), need)
    cfg, traffic = readings["cfg"], readings["traffic"]
    view = dict(readings["_device_scope"], prefills=spans)
    return device_scope.roofline(
        view, lambda *p: fn(cfg, traffic, *p), readings["device_kind"],
        module, scope, by_prompt=True)
