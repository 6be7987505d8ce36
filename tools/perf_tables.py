"""Render markdown performance tables from bench_out/ artifacts.

Keeps docs/performance.md honest: every number in the docs should trace
to a committed capture, and regenerating the tables after a bench
session is one command:

    python tools/perf_tables.py            # prints markdown to stdout
    python tools/perf_tables.py --json     # machine-readable summary

Reads every *.json / *.jsonl under bench_out/ (one JSON object per
line), groups by metric, and prints the most recent record per
(metric, variant-ish key). Records with value=null are skipped, and so
are A/B experiment rows (`ab_config` tag, as in the 2026-08-01
bench_out/ab_regression.jsonl, each row one since-deleted variant of
BatchNorm or Pooling) — they measure deliberately non-default configs
and must never shadow the numbers of record in these tables.
"""
from __future__ import annotations

import argparse
import glob
import json
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def is_experiment_row(rec):
    """True for A/B experiment records (tagged ab_config, as the rows
    of bench_out/ab_regression.jsonl are) — deliberately non-default
    configurations that must never be selected as a number of
    record."""
    return bool(rec.get("ab_config"))


def _mtime(path):
    try:
        return os.path.getmtime(path)
    except OSError:
        return 0.0


def load_records(out_dir):
    """Records in best-effort chronological order: files sorted by
    mtime (then name as the tiebreak — e.g. a fresh checkout where all
    mtimes match), lines within a file in append order. Downstream
    newest-wins dedup relies on this ordering."""
    recs = []
    paths = glob.glob(os.path.join(out_dir, "*.json*"))
    for path in sorted(paths, key=lambda p: (_mtime(p),
                                             os.path.basename(p))):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line.startswith("{"):
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if rec.get("value") is None:
                        continue
                    if is_experiment_row(rec):
                        continue
                    rec["_file"] = os.path.basename(path)
                    recs.append(rec)
        except OSError:
            continue
    return recs


def _fmt(v):
    if isinstance(v, float):
        return "%.4g" % v
    return str(v)


def _dedupe_newest(rows, keyfn):
    """Newest capture wins. load_records orders files by mtime and
    lines by append order, so the LAST record per key is the most
    recent — iterate reversed for the dedup, then restore encounter
    order for stable table layout (advisor r4: the old first-wins scan
    rendered the OLDEST record)."""
    newest = {}
    for r in reversed(rows):
        newest.setdefault(keyfn(r), r)
    out = []
    for r in rows:
        k = keyfn(r)
        if newest.get(k) is r:
            out.append(r)
    return out


def training_table(recs):
    rows = [r for r in recs
            if r.get("metric", "").endswith("_train_throughput")]
    if not rows:
        return ""
    out = ["## Training (one chip)", "",
           "| workload | value | unit | vs baseline | MFU | step ms |",
           "|---|---|---|---|---|---|"]
    for r in _dedupe_newest(rows, lambda r: (
            r["metric"], r.get("seq_len"), r.get("window"),
            r.get("remat"))):
        name = r["metric"].replace("_train_throughput", "")
        if r.get("seq_len"):
            name += " T=%d" % r["seq_len"]
        if r.get("window"):
            name += " W=%d" % r["window"]
        if r.get("remat"):
            name += " (remat)"
        out.append("| %s | %s | %s | %s | %s | %s |" % (
            name, _fmt(r["value"]), r.get("unit", ""),
            _fmt(r.get("vs_baseline", "")),
            _fmt(r["mfu"]) if r.get("mfu") is not None else "",
            _fmt(r.get("step_time_ms", ""))))
    return "\n".join(out)


def decode_table(recs):
    rows = [r for r in recs if "decode_throughput" in
            r.get("metric", "")]
    if not rows:
        return ""
    out = ["## Decode / serving (one chip)", "",
           "| mode | tokens/s | ms/token | batch | quantize | notes |",
           "|---|---|---|---|---|---|"]
    for r in _dedupe_newest(rows, lambda r: (
            r["metric"], r.get("quantize"), r.get("batch"),
            r.get("prompt_len"), r.get("new_tokens"))):
        mode = "greedy"
        if r.get("beam"):
            mode = "beam-%d" % r["beam"]
        if r.get("speculative_lookahead"):
            mode = "speculative-%d" % r["speculative_lookahead"]
        if r.get("kv_heads"):
            mode += " gqa-%d" % r["kv_heads"]
        if r.get("quantize"):
            mode += " " + str(r["quantize"])
        notes = ""
        if r.get("spec_accepted_per_round") is not None:
            notes = "%.2f accepted/round" % r["spec_accepted_per_round"]
        out.append("| %s | %s | %s | %s | %s | %s |" % (
            mode, _fmt(r["value"]), _fmt(r.get("ms_per_token", "")),
            r.get("batch", ""), r.get("quantize") or "-", notes))
    return "\n".join(out)


def bn_table(recs):
    rows = [r for r in recs
            if r.get("metric") == "batchnorm_train_fwd_bwd"]
    if not rows:
        return ""
    out = ["## BatchNorm one-pass vs two-pass (fwd+bwd)", "",
           "| shape | one-pass ms | two-pass ms | speedup |",
           "|---|---|---|---|"]
    for r in _dedupe_newest(rows, lambda r: tuple(r["shape"])):
        out.append("| %s | %s | %s | %sx |" % (
            "x".join(str(d) for d in r["shape"]),
            _fmt(r["one_pass_ms"]), _fmt(r["two_pass_ms"]),
            _fmt(r["speedup"])))
    return "\n".join(out)


def pipeline_table(recs):
    rows = [r for r in recs if r.get("metric", "").startswith(
        "input_pipeline")]
    if not rows:
        return ""
    out = ["## Input pipeline", "",
           "| variant | img/s | threads | batch |",
           "|---|---|---|---|"]
    for r in _dedupe_newest(rows, lambda r: (
            r["metric"], r.get("variant"), r.get("threads"))):
        name = r.get("variant") or r["metric"].replace(
            "input_pipeline_", "")
        out.append("| %s | %s | %s | %s |" % (
            name, _fmt(r["value"]), r.get("threads", ""),
            r.get("batch", "")))
    return "\n".join(out)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out-dir", default=os.path.join(_REPO,
                                                     "bench_out"))
    p.add_argument("--json", action="store_true")
    args = p.parse_args()
    recs = load_records(args.out_dir)
    if args.json:
        print(json.dumps(recs, indent=1))
        return
    sections = [t for t in (training_table(recs), decode_table(recs),
                            bn_table(recs), pipeline_table(recs)) if t]
    if not sections:
        raise SystemExit("no records with values under %s"
                         % args.out_dir)
    print("\n\n".join(sections))


if __name__ == "__main__":
    main()
