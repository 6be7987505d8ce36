"""Turn a telemetry run journal into a human-readable run summary.

The journal (schema v1, ``mxnet_tpu/telemetry.py``, written when
``MXNET_TELEMETRY`` names a directory) holds one JSONL record per
training step plus one per notable event. This tool reconstructs:

* step-time quantiles (p50/p95/p99, exact — computed over the raw
  per-step records, not histogram buckets) and the data-wait vs
  window-wait breakdown;
* the throughput curve (samples/sec over the run, bucketed);
* a fault/guardrail event table (retries, reconnects, dead workers,
  masked steps, rollbacks, preemption checkpoints, compiles);
* the final metrics-registry snapshot, when the journal was closed
  cleanly.

    python tools/telemetry_report.py runs/telemetry-1234.jsonl
    python tools/telemetry_report.py --json runs/telemetry-1234.jsonl
    python tools/telemetry_report.py --stats 127.0.0.1:9911
    python tools/telemetry_report.py --stats h1:9911 h2:9911 h3:9911
    python tools/telemetry_report.py --diff old.jsonl new.jsonl

``--diff OLD NEW`` compares two journals regression-first: step-time
quantile and throughput deltas, the wait-breakdown shift, per-counter
deltas, and event-vocabulary changes (events that appeared or
disappeared between the runs) — the human companion to the automated
``tools/perf_gate.py`` gate (docs/perf_gates.md).

The summary's ``samples_per_sec`` is sum(samples) / sum(wall_ms):
step walls are measured boundary-to-boundary in the fit loops, so the
figure reconstructs what a Speedometer callback reports (asserted
within 5% in tests/test_telemetry.py).

With ``MXNET_PEAK_FLOPS`` set (peak accelerator FLOP/s), the
steady-state section also prints achieved FLOP/s and MFU from the
``step.model_flops`` gauge the Executor records at each compile event.

``--stats host:port`` instead queries a live ``ServeServer``'s
introspection frame (telemetry registry snapshot + engine queue/bucket
state) — same trusted-cluster pickle wire as the serving transport.
Several targets render as ONE fleet table (per-replica queue depth,
in-flight, active decode slots, warmed buckets, shed counts — the
operator's imbalance eyeball for a replicated serve fleet, a dead
replica shown as unreachable instead of sinking the table).
"""
import argparse
import json
import os

SCHEMA_VERSION = 1

_CURVE_BUCKETS = 20


def load_jsonl(path, schema=None, what="record"):
    """Torn-final-line-tolerant JSONL loader — THE one read side of
    the journal/spill write contract (one flushed line per record, so
    a crash tears at most the FINAL line; a parse failure there is
    tolerated, anywhere earlier is real corruption and raises). With
    ``schema`` set, every record's ``v`` must match or the file is
    refused. Shared by this tool, ``tools/trace_report.py`` and
    ``tools/perf_gate.py`` — evolve the contract here, once."""
    with open(path) as f:
        lines = [ln.strip() for ln in f]
    while lines and not lines[-1]:
        lines.pop()
    records = []
    for i, line in enumerate(lines):
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            if i == len(lines) - 1:
                break            # torn final line: the crash signature
            raise ValueError("%s:%d: corrupt %s" % (path, i + 1, what))
        if schema is not None and rec.get("v") != schema:
            raise ValueError(
                "%s:%d: %s schema v%r, this reader understands v%d"
                % (path, i + 1, what, rec.get("v"), schema))
        records.append(rec)
    return records


def load(path):
    """Parse a journal into a record list (schema-checked)."""
    return load_jsonl(path, schema=SCHEMA_VERSION,
                      what="journal record")


def _quantile(sorted_vals, q):
    """Exact quantile of an already-sorted list (nearest-rank with the
    numpy 'linear' convention's index rounding). Mirrors
    mxnet_tpu.telemetry.quantile — kept standalone so this tool never
    drags the framework/jax import."""
    if not sorted_vals:
        return None
    idx = int(round(q * (len(sorted_vals) - 1)))
    return sorted_vals[idx]


def _curve(steps):
    """samples/sec over the run, in up to _CURVE_BUCKETS equal step
    spans: [{"step": first step of span, "samples_per_sec": ...}]."""
    if not steps:
        return []
    span = max(1, (len(steps) + _CURVE_BUCKETS - 1) // _CURVE_BUCKETS)
    out = []
    for i in range(0, len(steps), span):
        chunk = steps[i:i + span]
        wall_s = sum(float(s.get("wall_ms", 0.0)) for s in chunk) / 1e3
        samples = sum(int(s.get("samples", 0)) for s in chunk)
        out.append({
            "step": i,
            "samples_per_sec": round(samples / wall_s, 2) if wall_s
            else None})
    return out


def summarize(records):
    """Aggregate a record list (from :func:`load`, optionally filtered
    by the caller — e.g. to one run's records) into the summary dict
    format_report renders."""
    steps = [r for r in records if r.get("kind") == "step"]
    events = [r for r in records if r.get("kind") == "event"]
    snap = next((r.get("metrics") for r in reversed(records)
                 if r.get("kind") == "snapshot"), None)

    out = {"schema": SCHEMA_VERSION, "steps": len(steps),
           "events": {}}
    for e in events:
        name = e.get("event", "?")
        out["events"][name] = out["events"].get(name, 0) + 1

    if steps:
        # steady-state view: steps flagged compile=True carried an XLA
        # compile (the journal marks them at write time) — one-off wall
        # that would otherwise poison every quantile and the
        # throughput figure. They are reported separately below.
        steady = [s for s in steps if not s.get("compile")] or steps
        compile_ms = sum(float(s.get("wall_ms", 0.0)) for s in steps
                         if s.get("compile"))
        walls = sorted(float(s.get("wall_ms", 0.0)) for s in steady)
        total_s = sum(walls) / 1e3
        samples = sum(int(s.get("samples", 0)) for s in steady)
        out["samples"] = samples
        out["wall_s"] = round(total_s, 3)
        out["compile_steps"] = sum(1 for s in steps
                                   if s.get("compile"))
        out["compile_ms"] = round(compile_ms, 3)
        out["samples_per_sec"] = round(samples / total_s, 3) \
            if total_s else None
        out["step_ms"] = {
            "mean": round(sum(walls) / len(walls), 3),
            "p50": round(_quantile(walls, 0.50), 3),
            "p95": round(_quantile(walls, 0.95), 3),
            "p99": round(_quantile(walls, 0.99), 3),
            "min": round(walls[0], 3),
            "max": round(walls[-1], 3)}
        for key in ("data_wait_ms", "window_wait_ms"):
            tot = sum(float(s.get(key, 0.0)) for s in steady)
            out[key + "_total"] = round(tot, 3)
            out[key + "_share"] = round(tot / (total_s * 1e3), 4) \
                if total_s else None
        out["throughput_curve"] = _curve(steady)

        # MFU: achieved FLOP/s = the compiled
        # step's cost-analysis FLOPs (step.model_flops gauge) times
        # steady-state steps/sec; MFU against the MXNET_PEAK_FLOPS
        # hint (read here, at report time — the journal predates it)
        g = (snap or {}).get("step.model_flops", {})
        flops = g.get("value") if g.get("type") == "gauge" else None
        if flops and total_s:
            out["model_flops"] = flops
            out["flops_per_sec"] = flops * len(steady) / total_s
            try:
                peak = float(os.environ.get("MXNET_PEAK_FLOPS") or 0.0)
            except ValueError:
                peak = 0.0
            if peak > 0:
                out["peak_flops"] = peak
                out["mfu"] = round(out["flops_per_sec"] / peak, 4)

    serving = _serving_section(events, snap)
    if serving:
        out["serving"] = serving

    if snap is not None:
        out["counters"] = {k: v["value"] for k, v in sorted(snap.items())
                           if v.get("type") == "counter"}
        out["gauges"] = {k: v["value"] for k, v in sorted(snap.items())
                         if v.get("type") == "gauge"
                         and v.get("value") is not None}
    return out


def _serving_section(events, snap):
    """Aggregate the serving engine's journal events (serve.batch /
    serve.shed / serve.timeout / serve.decode.finish) and serve.*
    snapshot counters into the report's serving block. Empty dict =
    no serving activity in this journal."""
    out = {}
    batches = [e.get("fields", {}) for e in events
               if e.get("event") == "serve.batch"]
    if batches:
        fills = sorted(float(b.get("fill", 0)) for b in batches)
        waits = sorted(float(b.get("wait_ms", 0.0)) for b in batches)
        fwd = sorted(float(b.get("forward_ms", 0.0)) for b in batches)
        out["forwards"] = len(batches)
        out["rows"] = int(sum(fills))
        out["mean_fill"] = round(sum(fills) / len(fills), 3)
        out["batch_wait_ms"] = {
            "p50": round(_quantile(waits, 0.50), 3),
            "p95": round(_quantile(waits, 0.95), 3)}
        out["forward_ms"] = {
            "p50": round(_quantile(fwd, 0.50), 3),
            "p95": round(_quantile(fwd, 0.95), 3)}
    finishes = [e.get("fields", {}) for e in events
                if e.get("event") == "serve.decode.finish"]
    if finishes:
        toks = sorted(int(f.get("tokens", 0)) for f in finishes)
        ms = sorted(float(f.get("ms", 0.0)) for f in finishes)
        out["decode_sequences"] = len(finishes)
        out["decode_tokens"] = int(sum(toks))
        out["decode_ms"] = {"p50": round(_quantile(ms, 0.50), 3),
                            "p95": round(_quantile(ms, 0.95), 3)}
    if snap is not None:
        # streaming latency first-class: TTFT and inter-token gap
        # quantiles straight off the registry histograms (populated
        # by every decode emission, streamed or not)
        for name, key in (("serve.ttft_ms", "ttft_ms"),
                          ("serve.inter_token_ms",
                           "inter_token_ms")):
            h = snap.get(name) or {}
            if h.get("type") == "histogram" and h.get("count"):
                out[key] = {"count": h["count"], "p50": h.get("p50"),
                            "p95": h.get("p95"), "p99": h.get("p99")}
        # speculative decoding: per-round-row acceptance (the knob
        # that decides whether the draft is earning its keep —
        # docs/serving.md §speculative)
        h = snap.get("serve.spec.accept_rate") or {}
        if h.get("type") == "histogram" and h.get("count"):
            out["spec_accept_rate"] = {
                "count": h["count"],
                "mean": round(h["sum"] / h["count"], 4)
                if h.get("sum") is not None else None,
                "p50": h.get("p50"), "p95": h.get("p95"),
                "p99": h.get("p99")}
        counters = {k: v["value"] for k, v in snap.items()
                    if k.startswith("serve.")
                    and v.get("type") == "counter" and v.get("value")}
        if counters:
            out["counters"] = dict(sorted(counters.items()))
        # attach only alongside real serving activity: the gauge is
        # published by every Generator construction, and a bare
        # kv-bytes figure must not conjure a serving section into a
        # journal that never served
        kvb = snap.get("serve.decode.kv_bytes_per_slot",
                       {}).get("value")
        if kvb and out:
            out["kv_bytes_per_slot"] = int(kvb)
    for name in ("serve.shed", "serve.timeout", "serve.drain"):
        n = sum(1 for e in events if e.get("event") == name)
        if n:
            out[name.split(".", 1)[1] + "_events"] = n
    return out


def format_report(summary):
    """The summary dict as a human-readable text report."""
    lines = ["telemetry run summary (journal schema v%d)"
             % summary["schema"],
             "=" * 46, ""]
    if summary["steps"]:
        sm = summary["step_ms"]
        lines += [
            "steps: %d   samples: %d   wall: %.2fs   throughput: "
            "%.1f samples/sec (steady state)"
            % (summary["steps"], summary["samples"], summary["wall_s"],
               summary["samples_per_sec"] or 0.0)]
        if summary.get("compile_steps"):
            lines.append(
                "compile: %d step(s) carried an XLA compile "
                "(%.1f ms total) — excluded from the figures above"
                % (summary["compile_steps"], summary["compile_ms"]))
        lines += [
            "",
            "step time (ms):",
            "| mean | p50 | p95 | p99 | min | max |",
            "|---|---|---|---|---|---|",
            "| %.2f | %.2f | %.2f | %.2f | %.2f | %.2f |"
            % (sm["mean"], sm["p50"], sm["p95"], sm["p99"], sm["min"],
               sm["max"]),
            "",
            "wait breakdown: data %.1f%%, dispatch window %.1f%% of "
            "step wall"
            % (100.0 * (summary.get("data_wait_ms_share") or 0.0),
               100.0 * (summary.get("window_wait_ms_share") or 0.0)),
        ]
        if summary.get("flops_per_sec"):
            mfu_line = ("model FLOPs/step: %.4g — achieved %.4g "
                        "FLOP/s" % (summary["model_flops"],
                                    summary["flops_per_sec"]))
            if summary.get("mfu") is not None:
                mfu_line += ("   MFU: %.1f%% of %.4g peak "
                             "(MXNET_PEAK_FLOPS)"
                             % (100.0 * summary["mfu"],
                                summary["peak_flops"]))
            lines.append(mfu_line)
        curve = summary.get("throughput_curve") or []
        if len(curve) > 1:
            lines += ["", "throughput curve (samples/sec by step span):"]
            for pt in curve:
                lines.append("  step %5d+  %s" % (
                    pt["step"],
                    "%.1f" % pt["samples_per_sec"]
                    if pt["samples_per_sec"] is not None else "-"))
    else:
        lines.append("no step records (events-only journal)")

    serving = summary.get("serving")
    if serving:
        lines += ["", "serving:"]
        if "forwards" in serving:
            lines.append(
                "  %d engine forward(s) served %d row(s) — mean batch "
                "fill %.2f" % (serving["forwards"], serving["rows"],
                               serving["mean_fill"]))
            lines.append(
                "  batch wait p50/p95: %.2f/%.2f ms   forward p50/p95: "
                "%.2f/%.2f ms"
                % (serving["batch_wait_ms"]["p50"],
                   serving["batch_wait_ms"]["p95"],
                   serving["forward_ms"]["p50"],
                   serving["forward_ms"]["p95"]))
        if "decode_sequences" in serving:
            lines.append(
                "  continuous decode: %d sequence(s), %d token(s), "
                "request p50/p95: %.1f/%.1f ms"
                % (serving["decode_sequences"],
                   serving["decode_tokens"],
                   serving["decode_ms"]["p50"],
                   serving["decode_ms"]["p95"]))
        if serving.get("ttft_ms"):
            t = serving["ttft_ms"]
            lines.append(
                "  TTFT p50/p95/p99: %.1f/%.1f/%.1f ms over %d first "
                "token(s)" % (t["p50"], t["p95"], t["p99"],
                              t["count"]))
        if serving.get("inter_token_ms"):
            t = serving["inter_token_ms"]
            lines.append(
                "  inter-token p50/p95/p99: %.2f/%.2f/%.2f ms over "
                "%d gap(s)" % (t["p50"], t["p95"], t["p99"],
                               t["count"]))
        if serving.get("spec_accept_rate"):
            a = serving["spec_accept_rate"]
            lines.append(
                "  speculative accept rate: mean %.2f   p50/p95: "
                "%.2f/%.2f over %d round-row(s) (docs/serving.md "
                "§speculative — below ~0.4 the draft costs more "
                "than it saves)"
                % (a["mean"] or 0.0, a["p50"], a["p95"], a["count"]))
        if serving.get("kv_bytes_per_slot"):
            kvb = serving["kv_bytes_per_slot"]
            lines.append(
                "  decode state: %d bytes/slot (%.2f MiB — int8 "
                "quantize_kv halves KV rows; block_type='ssm' makes "
                "it O(1) in max_len; see docs/serving.md)"
                % (kvb, kvb / 2.0 ** 20))
        for key, label in (("shed_events", "shed"),
                           ("timeout_events", "timed out"),
                           ("drain_events", "drain(s)")):
            if serving.get(key):
                lines.append("  %d request(s) %s"
                             % (serving[key], label))
        if serving.get("counters"):
            for name, val in serving["counters"].items():
                lines.append("  %-36s %d" % (name, val))

    if summary["events"]:
        lines += ["", "events:",
                  "| event | count |", "|---|---|"]
        for name in sorted(summary["events"]):
            lines.append("| %s | %d |" % (name, summary["events"][name]))

    if summary.get("counters"):
        lines += ["", "final counters (registry snapshot):"]
        for name, val in summary["counters"].items():
            lines.append("  %-36s %d" % (name, val))
    if summary.get("gauges"):
        lines += ["", "gauges:"]
        for name, val in summary["gauges"].items():
            lines.append("  %-36s %g" % (name, val))
    return "\n".join(lines)


def _pct(old, new):
    """Signed percent change new vs old; None when undefined."""
    if old is None or new is None or not old:
        return None
    return round(100.0 * (float(new) - float(old)) / float(old), 1)


def diff_summaries(old, new):
    """Regression-oriented diff of two :func:`summarize` outputs.
    Positive step-time deltas and negative throughput deltas are the
    regression directions; ``suspects`` collects the headline fields
    that moved the wrong way by more than 10%."""
    out = {"steps": [old.get("steps"), new.get("steps")],
           "suspects": []}
    for key, worse_when in (("samples_per_sec", "down"),
                            ("wall_s", "up"),
                            ("compile_steps", "up"),
                            ("compile_ms", "up")):
        o, n = old.get(key), new.get(key)
        if o is None and n is None:
            continue
        pct = _pct(o, n)
        out[key] = {"old": o, "new": n, "pct": pct}
        if pct is not None and (pct < -10 if worse_when == "down"
                                else pct > 10):
            out["suspects"].append(key)
    sm_o, sm_n = old.get("step_ms") or {}, new.get("step_ms") or {}
    if sm_o or sm_n:
        out["step_ms"] = {}
        for q in ("mean", "p50", "p95", "p99", "min", "max"):
            pct = _pct(sm_o.get(q), sm_n.get(q))
            out["step_ms"][q] = {"old": sm_o.get(q), "new": sm_n.get(q),
                                 "pct": pct}
            if q in ("p50", "p95") and pct is not None and pct > 10:
                out["suspects"].append("step_ms." + q)
    for key in ("data_wait_ms_share", "window_wait_ms_share"):
        o, n = old.get(key), new.get(key)
        if o is not None or n is not None:
            out[key] = {"old": o, "new": n}
    # counter deltas over the union (a counter that disappears entirely
    # usually marks deleted instrumentation — a gate-worthy smell)
    co = old.get("counters") or {}
    cn = new.get("counters") or {}
    deltas = {}
    for k in sorted(set(co) | set(cn)):
        ov, nv = co.get(k), cn.get(k)
        if ov != nv:
            deltas[k] = {"old": ov, "new": nv}
    if deltas:
        out["counter_deltas"] = deltas
    ev_o = set(old.get("events") or {})
    ev_n = set(new.get("events") or {})
    out["events_added"] = sorted(ev_n - ev_o)
    out["events_removed"] = sorted(ev_o - ev_n)
    if out["events_removed"]:
        out["suspects"].append("events_removed")
    ev_counts = {}
    for k in sorted(ev_o & ev_n):
        ov = (old.get("events") or {}).get(k)
        nv = (new.get("events") or {}).get(k)
        if ov != nv:
            ev_counts[k] = {"old": ov, "new": nv}
    if ev_counts:
        out["event_count_changes"] = ev_counts
    return out


def format_diff(diff, old_path="OLD", new_path="NEW"):
    """The diff dict as a regression-oriented text table."""
    lines = ["telemetry journal diff", "=" * 46,
             "  old: %s" % old_path, "  new: %s" % new_path, ""]

    def row(label, o, n, pct=None):
        tail = "" if pct is None else "  (%+.1f%%)" % pct
        return "| %-18s | %10s | %10s |%s" % (label, o, n, tail)

    lines += ["| field              |        old |        new |",
              "|---|---|---|",
              row("steps", diff["steps"][0], diff["steps"][1])]
    for key in ("samples_per_sec", "wall_s", "compile_steps",
                "compile_ms"):
        if key in diff:
            d = diff[key]
            lines.append(row(key, d["old"], d["new"], d["pct"]))
    for q, d in (diff.get("step_ms") or {}).items():
        lines.append(row("step_ms." + q, d["old"], d["new"], d["pct"]))
    for key in ("data_wait_ms_share", "window_wait_ms_share"):
        if key in diff:
            d = diff[key]
            lines.append(row(key, d["old"], d["new"]))
    if diff.get("counter_deltas"):
        lines += ["", "counters that changed:",
                  "| counter | old | new |", "|---|---|---|"]
        for k, d in diff["counter_deltas"].items():
            lines.append("| %s | %s | %s |" % (k, d["old"], d["new"]))
    if diff.get("event_count_changes"):
        lines += ["", "event counts that changed:",
                  "| event | old | new |", "|---|---|---|"]
        for k, d in diff["event_count_changes"].items():
            lines.append("| %s | %s | %s |" % (k, d["old"], d["new"]))
    if diff.get("events_added"):
        lines += ["", "events only in new: "
                  + ", ".join(diff["events_added"])]
    if diff.get("events_removed"):
        lines += ["", "events only in old (deleted instrumentation?): "
                  + ", ".join(diff["events_removed"])]
    lines.append("")
    if diff.get("suspects"):
        lines.append("regression suspects (>10%% the wrong way): %s"
                     % ", ".join(diff["suspects"]))
    else:
        lines.append("no regression suspects (>10% thresholds)")
    return "\n".join(lines)


def fetch_stats(addr, timeout=10.0):
    """Query a live ServeServer's ``stats`` introspection frame.
    Speaks the serving wire directly (4-byte length prefix + pickle) so
    this tool still needs no framework import. Trusted cluster only —
    the reply unpickles, exactly like the serving transport itself."""
    import pickle
    import socket
    import struct

    host, _, port = str(addr).rpartition(":")
    if not host:
        raise ValueError("--stats wants HOST:PORT, got %r" % (addr,))
    with socket.create_connection((host, int(port)),
                                  timeout=timeout) as sock:
        payload = pickle.dumps(("stats", None), protocol=4)
        sock.sendall(struct.pack(">I", len(payload)) + payload)
        hdr = b""
        while len(hdr) < 4:
            chunk = sock.recv(4 - len(hdr))
            if not chunk:
                raise ConnectionError("server closed during stats reply")
            hdr += chunk
        (n,) = struct.unpack(">I", hdr)
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(min(1 << 20, n - len(buf)))
            if not chunk:
                raise ConnectionError("server closed mid stats reply")
            buf += chunk
    reply = pickle.loads(bytes(buf))
    if not reply or reply[0] != "ok":
        raise RuntimeError("stats query failed: %r" % (reply,))
    return reply[1]


def format_fleet(rows):
    """Multi-target stats replies as one fleet table — the operator's
    imbalance eyeball (per-replica queue depth, in-flight, active
    decode slots, warmed buckets, shed counts) without Perfetto.
    ``rows``: ``[(addr, stats-or-None)]`` — a None/failed fetch
    renders as unreachable rather than sinking the table."""
    def gauge(snap, name):
        v = (snap.get(name) or {}).get("value")
        return "-" if v is None else ("%g" % v)

    header = ("| replica | role | model | queue | in-flight | streams "
              "| admitted | shed | shed/s | req/s | timeouts | "
              "active slots | warmed |")
    lines = ["serve fleet stats (%d target(s))" % len(rows),
             "=" * 46, "", header,
             "|---|---|---|---|---|---|---|---|---|---|---|---|---|"]
    for addr, stats in rows:
        if not stats:
            lines.append("| %s | unreachable | - | - | - | - | - | - "
                         "| - | - | - | - | - |" % addr)
            continue
        eng = stats.get("engine") or {}
        snap = stats.get("telemetry") or {}
        warmed = eng.get("warmed")
        lines.append("| %s | %s | %s | %s | %s | %s | %s | %s | %s "
                     "| %s | %s | %s | %s |"
                     % (addr,
                        eng.get("role", "engine"),
                        eng.get("model_id") or "-",
                        eng.get("queue_depth", "-"),
                        eng.get("in_flight", "-"),
                        eng.get("streams_in_flight", "-"),
                        eng.get("admitted", eng.get("dispatched",
                                                    "-")),
                        eng.get("shed", "-"),
                        # windowed rates (per router poll window):
                        # router targets aggregate them fleet-wide,
                        # plain engine targets have no poller -> "-"
                        eng.get("shed_rate", "-"),
                        eng.get("req_rate", "-"),
                        eng.get("timeouts", "-"),
                        gauge(snap, "serve.decode.active_slots"),
                        ",".join(str(b) for b in warmed)
                        if warmed else "-"))
    reach = [(a, s) for a, s in rows if s]
    if reach:
        engines = [s.get("engine") or {} for _, s in reach]
        lines += ["", "fleet totals: queue=%s in-flight=%s "
                  "admitted=%s shed=%s over %d reachable replica(s)"
                  % (sum(int(e.get("queue_depth") or 0)
                         for e in engines),
                     sum(int(e.get("in_flight") or 0)
                         for e in engines),
                     # same admitted-or-dispatched fallback as the
                     # per-row column: a router target counts
                     # dispatched, an engine counts admitted
                     sum(int(e.get("admitted",
                                   e.get("dispatched")) or 0)
                         for e in engines),
                     sum(int(e.get("shed") or 0) for e in engines),
                     len(reach))]
    return "\n".join(lines)


def format_stats(stats):
    """A live-server stats reply as a text report."""
    lines = ["serve server stats", "=" * 46, "", "engine:"]
    for key, val in sorted((stats.get("engine") or {}).items()):
        lines.append("  %-24s %s" % (key, val))
    snap = stats.get("telemetry") or {}
    counters = {k: v["value"] for k, v in sorted(snap.items())
                if v.get("type") == "counter" and v.get("value")}
    if counters:
        lines += ["", "counters:"]
        for name, val in counters.items():
            lines.append("  %-36s %d" % (name, val))
    gauges = {k: v["value"] for k, v in sorted(snap.items())
              if v.get("type") == "gauge" and v.get("value") is not None}
    if gauges:
        lines += ["", "gauges:"]
        for name, val in gauges.items():
            lines.append("  %-36s %g" % (name, val))
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("journal", nargs="?",
                   help="path to a telemetry *.jsonl journal")
    p.add_argument("--json", action="store_true",
                   help="emit the summary dict as JSON instead of text")
    p.add_argument("--stats", metavar="HOST:PORT", nargs="+",
                   help="query live ServeServer stats frames instead "
                        "of reading a journal; several targets render "
                        "as one fleet table (per-replica queue depth, "
                        "in-flight, warmed buckets, shed counts)")
    p.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                   help="compare two journals (regression-oriented "
                        "table; the human companion to tools/"
                        "perf_gate.py)")
    args = p.parse_args(argv)
    try:
        if args.diff:
            old_p, new_p = args.diff
            diff = diff_summaries(summarize(load(old_p)),
                                  summarize(load(new_p)))
            print(json.dumps(diff, indent=2) if args.json
                  else format_diff(diff, old_p, new_p))
            return
        if args.stats:
            if len(args.stats) == 1:
                stats = fetch_stats(args.stats[0])
                print(json.dumps(stats, indent=2, default=str)
                      if args.json else format_stats(stats))
                return
            rows = []
            for addr in args.stats:
                try:
                    rows.append((addr, fetch_stats(addr)))
                except Exception:  # noqa: BLE001 — one dead replica
                    rows.append((addr, None))   # must not sink the
                    #                             fleet table
            print(json.dumps({a: s for a, s in rows}, indent=2,
                             default=str)
                  if args.json else format_fleet(rows))
            return
        if not args.journal:
            p.error("give a journal path (or --stats HOST:PORT, or "
                    "--diff OLD NEW)")
        summary = summarize(load(args.journal))
        if args.json:
            print(json.dumps(summary, indent=2))
        else:
            print(format_report(summary))
    except BrokenPipeError:        # `... | head` is a normal usage
        pass


if __name__ == "__main__":
    main()
