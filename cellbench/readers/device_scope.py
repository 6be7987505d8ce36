"""Source kind: device operations selected by the scope they were
traced under (`jax.named_scope`, or a kernel's name), within the
executions of one compiled program.

The profiler keeps, for every operation on a TPU plane's `XLA Ops`
line, the JAX name stack it was lowered from (`tf_op` on the event's
metadata: `jit(decode_step)/mamba2.step/reduce_sum:`), and one event
per program execution on the `XLA Modules` line
(`jit_decode_step(<id>)`). `jax.profiler.ProfileData` shows an event's
own stats but not its metadata's, so this reader parses the XSpace
protocol buffer itself, with the generated module the installation
already has (loaded by path: importing the package around it takes
seconds and is not needed).

Readings (`what`):

- `scope_share`: device seconds of the operations under `scope`, over
  the device's busy seconds (the union the `trace` reader reports), in
  per cent.
- `roofline`: over the whole executions of the program `module` in the
  trace: the least seconds the chip could take for what `need` (a
  function of `cellbench/ops/<family>.py`, from shapes: operations and
  bytes) asks of each execution, over the device seconds of the
  operations under `scope` inside it (of the whole execution where no
  scope is given), in per cent. A prefill's shapes follow its prompt
  length and the rows it runs: each execution takes `P` from the
  program's own `mxnet.admit.prefill` span that dispatched it, and the
  rows from that span's `run` where the program writes one
  (`serve/decode.py`: the rows the forward ran; a diffusion pool's
  `_admit_blocks` does, `_admit_batch` runs the pool's width and says
  nothing). A span's `rows` is the real prompts of the group and is
  never read as the rows run: where there is no `run`, `need` is asked
  with no row count and falls back to the pool's width.

With a program that has no such scope or program (the parent of the PR
that added them) there is nothing to read: `None`, and the metric is
left out of the line. The arithmetic works on plain lists and is tested
without a trace; `load` is tested on the trace in `cellbench/testdata/`.
"""
import bisect
import importlib
import importlib.util
import os

from cellbench.readers import host_spans, utilization
from cellbench.readers.trace import (DEVICE_PLANE, HOST_PLANE, OPS_LINE,
                                     union)

MODULES_LINE = "XLA Modules"
SCOPE_STAT = "tf_op"
PREFILL_SPAN = "mxnet.admit.prefill"


def _xplane_pb2():
    """The generated XSpace module, without importing the package that
    ships it."""
    name = "tensorflow.tsl.profiler.protobuf.xplane_pb2"
    spec = importlib.util.find_spec("tensorflow")
    if spec is not None and spec.submodule_search_locations:
        path = os.path.join(list(spec.submodule_search_locations)[0],
                            "tsl", "profiler", "protobuf",
                            "xplane_pb2.py")
        if os.path.exists(path):
            sub = importlib.util.spec_from_file_location(
                "_cellbench_xplane_pb2", path)
            mod = importlib.util.module_from_spec(sub)
            sub.loader.exec_module(mod)
            return mod
    return importlib.import_module(name)


def _stat_value(stat, names):
    which = stat.WhichOneof("value")
    if which == "ref_value":
        return names.get(stat.ref_value, "")
    return getattr(stat, which) if which else None


def load(path):
    """From one trace file: for the busiest TPU plane, `ops`
    [(scope, start_ns, dur_ns)] of its `XLA Ops` line (scope: the
    operation's JAX name stack, "" where it has none) and `modules`
    [(name, start_ns, dur_ns)] of its `XLA Modules` line; and
    `prefills` [(start_ns, P, run)], the program's
    `mxnet.admit.prefill` spans on the host (`run`: the rows the
    forward ran, None where the span does not say)."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    best, prefills = None, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name.startswith(DEVICE_PLANE):
            scope_of = {}
            for mid, md in plane.event_metadata.items():
                scope_of[mid] = next(
                    (str(_stat_value(s, names)) for s in md.stats
                     if names.get(s.metadata_id) == SCOPE_STAT), "")
            ops, modules = [], []
            for line in plane.lines:
                t0 = line.timestamp_ns
                if line.name == OPS_LINE:
                    ops = [(scope_of.get(e.metadata_id, ""),
                            t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(plane.event_metadata[e.metadata_id].name,
                                t0 + e.offset_ps * 1e-3,
                                e.duration_ps * 1e-3)
                               for e in line.events]
            busy = _seconds(ops)
            if best is None or busy > best[0]:
                best = (busy, ops, modules)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if plane.event_metadata[e.metadata_id].name != \
                            PREFILL_SPAN:
                        continue
                    stats = {names.get(s.metadata_id):
                             _stat_value(s, names) for s in e.stats}
                    if "P" in stats:
                        run = stats.get("run")
                        prefills.append(
                            (line.timestamp_ns + e.offset_ps * 1e-3,
                             int(stats["P"]),
                             None if run is None else int(run)))
    _busy, ops, modules = best or (0.0, [], [])
    return {"ops": ops, "modules": modules,
            "prefills": sorted(prefills, key=lambda span: span[0])}


# -- arithmetic on plain lists ----------------------------------------------

def under(ops, scope):
    """The operations whose name stack has `scope` as one of its parts
    (`a/mamba2.step/b` is under `mamba2.step` and under `mamba2.`: a
    scope that ends in a dot selects by prefix of a part)."""
    def has(stack):
        parts = stack.rstrip(":").split("/")
        if scope.endswith("."):
            return any(p.startswith(scope) for p in parts)
        return scope in parts
    return [op for op in ops if has(op[0])]


def _seconds(ops):
    """Device seconds the operations cover: the union of their
    intervals, so an operation nested in another (a loop's body in the
    loop) counts once."""
    return sum(e - s for s, e in union(ops)) * 1e-9


def scope_seconds(ops, scope):
    return _seconds(under(ops, scope))


def executions(modules, module, ops, scope=None):
    """[(start_ns, device seconds)] for every execution of the program
    `module` (`jit_<module>(<id>)`): the seconds of the operations
    under `scope` that start inside it, or its own length where no
    scope is given."""
    picked = sorted(under(ops, scope), key=lambda op: op[1]) \
        if scope else ()
    starts = [op[1] for op in picked]
    out = []
    for name, start, dur in modules:
        if name.split("(", 1)[0] != "jit_" + module:
            continue
        if scope is None:
            out.append((start, dur * 1e-9))
        else:
            lo = bisect.bisect_left(starts, start)
            hi = bisect.bisect_left(starts, start + dur)
            out.append((start, _seconds(picked[lo:hi])))
    return out


def prefill_at(prefills, start_ns):
    """(`P`, `run`) of the last `mxnet.admit.prefill` span that began
    before `start_ns` (the program dispatches a prefill inside that
    span), or None."""
    best = None
    for s, p, run in prefills:
        if s <= start_ns:
            best = (p, run)
    return best


def least_seconds(flops, nbytes, device_kind):
    """The roofline: the larger of operations over the peak rate and
    bytes over the peak bandwidth."""
    return max(flops / utilization.peak(device_kind, "bf16_flops_per_s"),
               nbytes / utilization.peak(device_kind, "hbm_bytes_per_s"))


def roofline(view, need, device_kind, module, scope=None,
             by_prompt=False):
    """Per cent: least seconds over device seconds, summed over the
    executions of `module`. With `by_prompt`, those a prefill span is
    known for: `need(P, run)` where the span says how many rows ran,
    `need(P)` where it does not."""
    least = took = 0.0
    for start, secs in executions(view["modules"], module, view["ops"],
                                  scope):
        if by_prompt:
            span = prefill_at(view["prefills"], start)
            if span is None:
                continue
            p, run = span
            flops, nbytes = need(p) if run is None else need(p, run)
        else:
            flops, nbytes = need()
        if secs <= 0.0:
            continue
        least += least_seconds(flops, nbytes, device_kind)
        took += secs
    return None if took <= 0.0 else 100.0 * least / took


def read(readings, what, scope=None, module=None, need=None,
         by_prompt=False):
    summary = readings.get("trace")
    if not summary:
        return None
    view = readings.get("_device_scope")
    if view is None:
        path = host_spans.find_trace(
            not_before=host_spans.process_started())
        if path is None:
            print("cellbench: device_scope no trace of this process "
                  "under %s" % host_spans.OUT, flush=True)
            return None
        view = readings["_device_scope"] = load(path)
    if what == "scope_share":
        secs = scope_seconds(view["ops"], scope)
        return 100.0 * secs / summary["busy_s"] if secs else None
    if what != "roofline":
        raise ValueError("device_scope: no reading %r" % what)
    if "device_kind" not in readings:
        return None
    fn = getattr(importlib.import_module(
        "cellbench.ops." + readings["cfg"]["family"]), need)
    cfg, traffic = readings["cfg"], readings["traffic"]
    return roofline(
        view, lambda *p: fn(cfg, traffic, *p), readings["device_kind"],
        module, scope, by_prompt)
