"""The benchmark's one command.

    python cellbench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

One run of one cell, in a new process that holds the chip: load, warm
the cell's own shapes, measure for `--seconds`, check what the timed
path produced against the plain reference, print one JSON object as
the last line of stdout, exit. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file found
by its name in `BENCHMARK.json`; this file holds no table of them.
"""
import argparse
import importlib
import json
import os
import sys
import threading
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "cellbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(manifest, workload):
    """(cell, config entry, configuration, traffic) for a workload
    name, each from the file its name points to."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit("cellbench: no workload %r in BENCHMARK.json "
                         "(have %s)" % (workload, sorted(cells)))
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, entry, cfg, traffic


def metrics_for(manifest, group, workload):
    """The metrics of `group` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def require_chips(chips):
    """The device as JAX reports it; exits non-zero, with no result,
    unless it is a TPU with exactly the chips the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != chips:
        sys.stderr.write(
            "cellbench: this cell needs %d TPU chip(s); JAX reports %d "
            "device(s) of platform %r\n" % (chips, len(devs),
                                            devs[0].platform))
        sys.exit(3)
    return devs


class Context:
    """What a drive gets: the cell's files, the run's arguments, and
    the harness's services (earlier-line logging, compile events, the
    profiler)."""

    def __init__(self, cfg, traffic, seed, seconds, trace, out_dir,
                 control=False, program_hook=None, prefixes=()):
        self.cfg, self.traffic = cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.out_dir, self.control = out_dir, control
        self.program_hook = program_hook or (lambda obj: obj)
        self.prefixes = tuple(prefixes)
        self.trace_seconds = 4.0
        self.t0 = T0
        self.compiles = []
        self._listener = None
        self.stalls = []
        self._watching = threading.Event()

    def log(self, what, payload):
        print("cellbench: %s %s" % (what, json.dumps(payload)),
              flush=True)

    def watch_compiles(self):
        """Every backend compile from here on leaves its time in
        `self.compiles` (a program served from the persistent cache
        is not one)."""
        import jax.monitoring

        def on_event(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles.append(time.perf_counter())

        self._listener = on_event
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def unwatch_compiles(self):
        if self._listener is not None:
            import jax.monitoring
            jax.monitoring.unregister_event_duration_listener(
                self._listener)
            self._listener = None

    def watch_stalls(self):
        """A thread that sleeps 50 ms at a time and keeps every sleep
        that took over half a second, as [process age, seconds]: a
        stop of the whole process (or of whoever holds the interpreter)
        shows here, a stop of the device alone does not."""
        def watch():
            last = time.perf_counter()
            while not self._watching.wait(0.05):
                now = time.perf_counter()
                if now - last > 0.5:
                    self.stalls.append([now - self.t0, now - last])
                last = now

        threading.Thread(target=watch, daemon=True).start()

    def unwatch_stalls(self):
        self._watching.set()
        self.log("host_stalls_s_at_age_s",
                 [[secs, age] for age, secs in self.stalls])

    def memory_peak(self):
        """Peak bytes on the fullest chip, as the allocator reports
        them: buffers in use plus what compiled programs reserved for
        their temporaries (the two peaks need not coincide, so this is
        an upper bound)."""
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.log("memory_stats", stats[0])
        return max(s.get("peak_bytes_in_use", 0) +
                   s.get("peak_bytes_reserved", 0) for s in stats)

    def start_trace(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the host loop is the subject
        os.makedirs(self.out_dir, exist_ok=True)
        handle = {"dir": self.out_dir, "t_start": time.perf_counter()}
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        return handle

    def stop_trace(self, handle):
        import jax
        t = time.perf_counter()
        jax.profiler.stop_trace()
        handle["window_s"] = t - handle["t_start"]
        return handle


def judge(checks):
    """`correct`, and one printed line per number compared, beside its
    limit."""
    correct = True
    for c in checks:
        if "ok" not in c:
            c["ok"] = True if c["limit"] is None \
                else bool(c["value"] <= c["limit"])
        if c["limit"] is not None or not c["ok"]:
            correct = correct and c["ok"]
        print("cellbench: compared %s = %r, limit %r: %s"
              % (c["name"], c["value"], c["limit"],
                 "ok" if c["ok"] else "NOT OK"), flush=True)
    return correct


def read_layer_metrics(metrics, readings):
    """Each per-layer metric through the reader its own file names. A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in metrics:
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "cellbench.readers." + spec["reader"])
        value = reader.read(readings, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cfg, traffic, seed, seconds, trace=False, out_dir=None,
             control=False, program_hook=None, prefixes=()):
    """Drive one run and return everything it found, before any
    choice of which metrics go on the line. Needs no chip: the tests
    call it at toy size."""
    import jax
    # every program, however small, is served from the cache next time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ctx = Context(cfg, traffic, seed, seconds, trace,
                  out_dir or os.path.join(ROOT, "cellbench_out"),
                  control, program_hook, prefixes)
    ctx.watch_compiles()
    ctx.watch_stalls()
    try:
        drive = importlib.import_module(
            "cellbench.drive." + traffic["kind"])
        res = drive.run(ctx)
    finally:
        ctx.unwatch_compiles()
        ctx.unwatch_stalls()
    res["end_to_end"]["setup_s"] = res.pop("setup_end") - T0
    res["correct"] = judge(res["checks"])
    res["readings"].update(
        {"e2e." + k: v for k, v in res["end_to_end"].items()})
    res["readings"]["cfg"] = cfg
    res["readings"]["traffic"] = traffic
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1 puts the control of PERF.md in the program's "
                         "place (its own lower-precision path, or the "
                         "reference computed in the next precision down "
                         "read beside it): it has to come out not "
                         "correct, and is never a benchmark run")
    ap.add_argument("--prefixes", default="",
                    help="comma-separated shorter windows to reduce "
                         "the same run at (the spread study)")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell, _, cfg, traffic = resolve(manifest, args.workload)
    import mxnet_tpu  # noqa: F401 — sets the compile cache directory
    devs = require_chips(int(cell["chips"]))
    import jax

    out_dir = os.path.join(ROOT, "cellbench_out", args.workload)
    res = run_cell(cfg, traffic, args.seed, args.seconds,
                   trace=bool(args.trace), out_dir=out_dir,
                   control=bool(args.control),
                   prefixes=[float(x) for x in
                             args.prefixes.split(",") if x])
    readings = res["readings"]
    readings["device_kind"] = devs[0].device_kind
    readings["chips"] = len(devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    line = {"correct": bool(res["correct"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    if args.trace:
        from cellbench.readers import trace as trace_reader
        summary = trace_reader.summarize(res["trace"])
        readings["trace"] = summary
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["metrics"] = read_layer_metrics(
            metrics_for(manifest, "per_layer", args.workload), readings)
        line["breakdown"] = {"device_ops": summary["device_ops"][:10],
                             "idle_gaps": summary["idle_gaps"][:10]}
    else:
        line["metrics"] = {
            m["name"]: {"value": res["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in metrics_for(manifest, "end_to_end", args.workload)}
        print("cellbench: all %s" % json.dumps(res["end_to_end"]),
              flush=True)
    line["device"] = device
    missing = [k for k, v in line["metrics"].items()
               if v["value"] is None]
    if missing:
        sys.stderr.write("cellbench: no value for %s\n" % missing)
        sys.exit(4)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
