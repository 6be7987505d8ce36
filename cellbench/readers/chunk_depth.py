"""Source kind: the chunk forwards of a chunked prefill whose work
depends on how deep the chunk lies, by the program's own
`mxnet.serve.decode.prefill_chunk` spans in the device trace this
process just took.

One reading, `roofline`: `chunk_spans`'s, but `need(span, rows)` of
`cellbench/ops/<family>.py` is handed the chunk's SPAN, `(lo, hi)`:
the positions of the prompt the forward ran, so that a need can count
the rows each of its queries sees (`lo + 1` .. `hi` of them) and not a
buffer's width. `chunk_spans` hands `hi - lo` alone, which is all a
family whose chunk forward computes every column needs; this family's
indexer and attention must do work that grows with the depth. The
spans' loader is `chunk_spans`'s, the trace's loader, the arithmetic
and the roofline `device_scope`'s, each used as it is.

A program that writes no such span, or no `run` on it, gives nothing
to read: `None`, and the metric is left out of the line.
"""
import importlib

from cellbench.readers import chunk_spans, device_scope, host_spans


def as_prefills(chunks):
    """The chunk spans in the form `device_scope.roofline` takes a
    prefill's in, the span in the prompt length's place: (start_ns,
    (lo, hi), rows)."""
    return [(s, (lo, hi), run) for s, lo, hi, run in chunks
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
            print("cellbench: chunk_depth no trace of this process "
                  "under %s" % host_spans.OUT, flush=True)
            return None
        chunks = readings["_chunk_spans"] = chunk_spans.load_chunks(path)
        if "_device_scope" not in readings:
            readings["_device_scope"] = device_scope.load(path)
    if what != "roofline":
        raise ValueError("chunk_depth: no reading %r" % what)
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
