"""Summarize a jax.profiler xplane trace: device time per HLO category.

The reproducible half of docs/mfu_analysis.md: turns a trace directory
into the BN-vs-matmul breakdown table.

    python - <<'PY'
    import jax
    jax.profiler.start_trace("/tmp/trace")
    ...  # run a few steps, sync with np.asarray(jax.device_get(x))
    jax.profiler.stop_trace()
    PY
    python tools/xplane_summary.py /tmp/trace

Parses the raw *.xplane.pb protos. On TPU the "/device:TPU:N" planes'
"XLA Ops" line holds the HLO-op events and the table is exact; on the
CPU backend the single "/host:CPU" plane also carries runtime/compile
events, so CPU output is indicative only. Two environment quirks this tool handles
(learned the hard way — see docs/mfu_analysis.md):
- must run under PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python (the
  tool re-execs itself to set this before importing the proto);
- uses tensorflow.tsl.profiler.protobuf.xplane_pb2 directly — the
  tensorboard_plugin_profile conversion API is broken against the
  installed TF 2.21.
"""
import collections
import glob
import os
import re
import sys

# the proto parse needs the pure-python protobuf backend; re-exec is
# only safe when WE are the program (an importer would restart itself)
if __name__ == "__main__" and \
        os.environ.get("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION") != \
        "python":
    os.environ["PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION"] = "python"
    os.execv(sys.executable, [sys.executable] + sys.argv)

# one quantile rule across the observability tools (run as a script,
# sys.path[0] is tools/; imported as a package module, it is the repo
# root — hence the two spellings)
try:
    from tools.telemetry_report import _quantile
except ImportError:
    from telemetry_report import _quantile


# op-name -> coarse category. Order matters: first match wins, so the
# specific multi-word keys (all-reduce, reduce-window) must precede the
# bare "reduce" of the bn-stats bucket.
_CATEGORIES = (
    ("collectives", ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")),
    ("pooling", ("reduce-window", "select-and-scatter", "pool")),
    # reductions BEFORE the convert row: bf16->f32 statistics lower as
    # "%convert_reduce_fusion" — they are reduce work (the BN-stats
    # share this table exists to expose), not layout casts
    ("bn-stats / reductions", ("reduce", "variance", "norm")),
    # "convert" (dtype cast) before the "conv" substring would claim it
    ("copies / layout", ("convert",)),
    ("convolution", ("conv",)),
    ("matmul", ("dot", "einsum", "matmul")),
    ("copies / layout", ("copy", "transpose", "bitcast", "reshape",
                         "pad", "slice", "concatenate")),
    ("elementwise fusion", ("fusion", "add", "multiply", "subtract",
                            "divide", "tanh", "exp", "maximum")),
    ("custom / pallas", ("custom-call",)),
)


def _step_label(name, ev, stat_names):
    """Group key for one StepTraceAnnotation event: the annotation
    name plus its step_num/group_id stat when the plane carries one
    ('train_step#12'); TraceMe-encoded metadata ('name#k=v#') falls
    back to the raw name."""
    base = name.split("#", 1)[0]
    for st in ev.stats:
        if stat_names.get(st.metadata_id) in ("step_num", "group_id",
                                              "step_id"):
            v = st.int64_value or st.uint64_value
            return "%s#%d" % (base, v)
    return name


def _category(name):
    # events carry full HLO text ("%divide_subtract_fusion = (f32[...])
    # fusion(f32[...] %param), kind=kLoop ..."); match only the
    # instruction name plus the opcode token after "=", not operand text
    # (shape strings contain "slice"/"convert"-like substrings)
    low = name.lower()
    head = low.split(" = ", 1)
    if len(head) == 2:
        # opcode = the identifier right before the operand list, i.e.
        # after the result type (which itself contains parens/braces:
        # "(f32[8]{0:T(1024)}, ...) fusion(...)")
        m = re.search(r"[)}\]]\s+([a-z][a-z0-9._-]*)\(", head[1])
        low = head[0] + " " + (m.group(1) if m else "")
    for cat, keys in _CATEGORIES:
        if any(k in low for k in keys):
            return cat
    return "other"


def summarize(trace_dir):
    # check the backend protobuf ACTUALLY picked (the env var only
    # matters before the first protobuf import — a caller who imported
    # tensorflow first is already locked to the C++/upb backend)
    from google.protobuf.internal import api_implementation
    if api_implementation.Type() != "python":
        raise RuntimeError(
            "protobuf is running the %r backend, which mis-parses "
            "these planes; set PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION="
            "python before the FIRST protobuf/tensorflow import "
            "(running this file as a script does it automatically)"
            % api_implementation.Type())
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise SystemExit("no *.xplane.pb under %s" % trace_dir)

    per_cat = collections.Counter()
    per_op = collections.Counter()
    step_ps = collections.Counter()     # StepTraceAnnotation groups
    total = 0
    async_ps = 0
    for path in paths:
        xspace = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xspace.ParseFromString(f.read())
        # when a real accelerator plane exists (TPU runs), host planes
        # must be ignored wholesale: their python-activity events (e.g.
        # "np.asarray(jax.Array)" blocking on a readback) span the whole
        # trace and would swamp the device table. /host:CPU is only the
        # compute plane on the CPU backend, where no device plane exists.
        has_device = any(p.name.startswith("/device:") and
                         any(ln.events for ln in p.lines)
                         for p in xspace.planes)
        for plane in xspace.planes:
            if has_device:
                if not plane.name.startswith("/device:"):
                    continue
            elif not (re.search(r"/device:|tpu|gpu", plane.name,
                                re.IGNORECASE)
                      or plane.name == "/host:CPU"):
                continue
            ev_names = {eid: em.name
                        for eid, em in plane.event_metadata.items()}
            stat_names = {sid: sm.name
                          for sid, sm in plane.stat_metadata.items()}
            # step groups (ISSUE 8 satellite): device planes carry a
            # "Steps" line with one event per StepTraceAnnotation (the
            # markers PR 2's profiler.step_scope emits) — aggregate
            # them into the per-step device-time table. These lines
            # overlap the per-op line, so they stay OUT of the
            # category/total tally below.
            for line in plane.lines:
                if line.name.lower() != "steps":
                    continue
                for ev in line.events:
                    name = ev_names.get(ev.metadata_id, "?")
                    step_ps[_step_label(name, ev, stat_names)] += \
                        ev.duration_ps
            # device planes carry overlapping lines: XLA Modules / Steps
            # span the same wall time as the per-op line, and "Async XLA
            # Ops" holds in-flight copy spans that overlap compute — keep
            # exactly the HLO-op line when one exists, else every line
            # (CPU backend)
            lines = [ln for ln in plane.lines
                     if ln.name.lower() == "xla ops"] or list(plane.lines)
            for line in lines:
                for ev in line.events:
                    name = ev_names.get(ev.metadata_id, "?")
                    # python host-activity frames leak into /host:CPU on
                    # the CPU backend and into any trace where no
                    # /device: plane exists (the round-3 capture's
                    # "np.asarray(jax.Array)" 73% artifact); keep
                    # HLO-op events only
                    if ".py:" in name or name.startswith("$") or \
                            name.startswith(("np.", "jax.",
                                             "PjitFunction",
                                             "PyArray", "Thread")):
                        continue
                    if name.split("#", 1)[0].split(" = ", 1)[0] == \
                            "train_step":
                        # a step marker leaking onto an op/host line
                        # (CPU backend has no Steps line): count it as
                        # a step group, never as device op work
                        step_ps[_step_label(name, ev, stat_names)] += \
                            ev.duration_ps
                        continue
                    dur = ev.duration_ps
                    # async copy/slice pairs (HBM<->VMEM prefetches from
                    # XLA's memory-space assignment, S(1) layouts) span
                    # wall time OVERLAPPED with compute — counting them
                    # as device work double-books the window (they
                    # dominated this table as "copies / layout" before
                    # this split). Track separately, out of the share
                    # denominator.
                    head = name.split(" = ", 1)[0]
                    if re.search(r"%(copy|slice|collective-permute|"
                                 r"all-reduce|all-gather|"
                                 r"reduce-scatter|all-to-all)"
                                 r"-(start|done)",
                                 head):
                        async_ps += dur
                        continue
                    per_cat[_category(name)] += dur
                    per_op[name] += dur
                    total += dur
    return per_cat, per_op, total, async_ps, dict(step_ps)


def _print_steps(step_ps):
    """Per-step device-time table from the StepTraceAnnotation groups
    (empty when the trace carries no step markers)."""
    if not step_ps:
        return
    print("\nstep groups (StepTraceAnnotation):")
    print("| step | device ms |")
    print("|---|---|")
    def _key(item):
        base, _, num = item[0].partition("#")
        return (base, int(num)) if num.isdigit() else (item[0], -1)

    shown = sorted(step_ps.items(), key=_key)
    for name, ps in shown[:30]:
        print("| %s | %.2f |" % (name, ps / 1e9))
    if len(shown) > 30:
        print("| ... %d more steps ... | |" % (len(shown) - 30))
    durs = sorted(ps / 1e9 for _, ps in shown)
    print("(%d steps; mean %.2f ms, p50 %.2f, p95 %.2f)"
          % (len(durs), sum(durs) / len(durs),
             _quantile(durs, 0.50), _quantile(durs, 0.95)))


def main():
    if len(sys.argv) != 2:
        raise SystemExit("usage: xplane_summary.py <trace_dir>")
    per_cat, per_op, total, async_ps, step_ps = summarize(sys.argv[1])
    if not total and not step_ps:
        raise SystemExit("no device events found (trace too short, or "
                         "only host planes present)")
    if total:
        print("device time by category:")
        print("| category | ms | share |")
        print("|---|---|---|")
        for cat, ps in per_cat.most_common():
            print("| %s | %.2f | %.1f%% |" % (cat, ps / 1e9,
                                              100.0 * ps / total))
        if async_ps:
            print("(async copy/collective start-done spans — HBM<->VMEM "
                  "prefetches and in-flight comm, overlapped with compute "
                  "— excluded above: %.2f ms)" % (async_ps / 1e9))
    _print_steps(step_ps)
    if total:
        print("\ntop 15 ops:")
        for name, ps in per_op.most_common(15):
            print("  %8.2f ms  %4.1f%%  %s" % (
                ps / 1e9, 100.0 * ps / total, name[:90]))


if __name__ == "__main__":
    main()
