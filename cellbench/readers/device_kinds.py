"""Source kind: device operations by the graph node and the kind of
operator they were lowered from.

Since PR 36 `executor._graph_eval_fn` lowers every node of a `Symbol`
graph under two nested scopes, the node's name and `op.<Operator>`, and
`TrainStep`'s step names what is no graph node `train.fwd` (the
differentiated function, over the nodes), `train.cast`, `train.guard`,
`train.clip`, `train.update`, `train.metric`. The profiler keeps the
name stack on every device operation (`tf_op`, as
`cellbench/readers/device_scope.py` reads it):

    jit(step_with_metric)/jvp(train.fwd)/stage3_unit2_conv2/op.Convolution/conv_general_dilated:
    jit(step_with_metric)/transpose(jvp(train.fwd))/stage3_unit2_conv2/op.Convolution/transpose:
    jit(step_with_metric)/train.update/mul:

A transform wraps the outermost scope below it (`jvp(...)` forward,
`transpose(jvp(...))` backward) and leaves the parts further in plain,
so the **kind** of an operation is its first `op.` part, else its
innermost `train.` part (wrappers taken off); its **node** is the part
before the `op.` part; it runs **backward** when a part is wrapped in
`transpose(`. A hand-placed scope (`mamba2.step`, `moe.experts`) lies
below its node's two parts and is no kind.

One XLA fusion is one device operation with one name stack: where a
batch norm's apply is fused into the next convolution its time is that
convolution's, and a fusion that took a neighbour's name counts under
the neighbour. So beside the program's names the tables give the
compiler's own view, each operation's `hlo_category` (`convolution
fusion`, `loop fusion`, ...), and how far the two differ is there to
read.

Readings (`what`), built on `device_scope`'s `under`, `executions` and
`least_seconds`:

- `set_share`: device seconds of the operations under any of `scopes`
  (a whole part, or the prefix of a part where the scope ends in a dot,
  as `device_scope.under`; wrappers taken off), over the device's busy
  seconds, in per cent; with `direction` `forward` or `backward`, of
  those operations alone.
- `outside_share`: the complement: operations under none of `scopes`.
- `roofline`: as `device_scope`'s, with the need taken from the module
  the metric's file names (`need_module`, `need`), not from
  `cellbench/ops/<family>.py`.

The first reading of a run prints `cellbench: device_by_kind` (kind:
[operations, forward s, backward s, of both the s in convolution
fusions]) and `cellbench: device_by_node` (the twelve nodes with most
device seconds: [node, kind, forward s, backward s]). The same two from
any `jax.profiler` trace of a program built on this repo:

    python3 -m cellbench.readers.device_kinds <file.xplane.pb>

With a program that has no such scopes (the parent of the PR that added
them, or an executable served from a compile cache written before them:
the cache's key strips debug information and the executable keeps the
names of whoever compiled it) there is nothing to read: `None`, no
table, and the metric is left out of the line. The arithmetic works on plain lists and is tested
without a trace; `load` is tested on the trace in `cellbench/testdata/`.
"""
import functools
import importlib
import json
import sys

from cellbench.readers import device_scope as ds
from cellbench.readers import host_spans
from cellbench.readers.trace import DEVICE_PLANE, OPS_LINE

CATEGORY_STAT = "hlo_category"
CONV_CATEGORY = "convolution fusion"
KINDS = ("op.", "train.")
UNSCOPED = "_unscoped_"
TOP_NODES = 12


def load(path):
    """As `device_scope.load` for the busiest TPU plane, `ops` and
    `modules`, and beside them `categories`: each operation's
    `hlo_category` in the order of `ops` ("" where it has none)."""
    space = ds._xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    best = None
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for mid, md in plane.event_metadata.items():
            stats = {names.get(s.metadata_id): s for s in md.stats}
            meta[mid] = tuple(
                str(ds._stat_value(stats[k], names)) if k in stats else ""
                for k in (ds.SCOPE_STAT, CATEGORY_STAT))
        ops, cats, modules = [], [], []
        for line in plane.lines:
            t0 = line.timestamp_ns
            if line.name == OPS_LINE:
                for e in line.events:
                    scope, cat = meta.get(e.metadata_id, ("", ""))
                    ops.append((scope, t0 + e.offset_ps * 1e-3,
                                e.duration_ps * 1e-3))
                    cats.append(cat)
            elif line.name == ds.MODULES_LINE:
                modules = [(plane.event_metadata[e.metadata_id].name,
                            t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3)
                           for e in line.events]
        busy = ds._seconds(ops)
        if best is None or busy > best[0]:
            best = (busy, ops, cats, modules)
    _busy, ops, cats, modules = best or (0.0, [], [], [])
    return {"ops": ops, "categories": cats, "modules": modules,
            "prefills": []}


# -- arithmetic on plain lists ----------------------------------------------

@functools.lru_cache(maxsize=1 << 16)     # a trace repeats few stacks
def parts(stack):
    """The parts of a name stack, each without the wrappers a transform
    put around it (`transpose(jvp(train.fwd))` -> `train.fwd`). A
    `jit(...)` part names a program, not a scope, and stays whole."""
    out = []
    for p in stack.rstrip(":").split("/"):
        while p.endswith(")") and "(" in p and not p.startswith("jit("):
            p = p[p.index("(") + 1:-1]
        out.append(p)
    return tuple(out)


def is_backward(stack):
    return any(p.startswith("transpose(") or "(transpose(" in p
               for p in stack.split("/"))


def _under(stack, scopes):
    return any(p.startswith(s) if s.endswith(".") else p == s
               for p in parts(stack) for s in scopes)


def under_any(ops, scopes):
    """The operations with a part under any of `scopes`."""
    return [op for op in ops if _under(op[0], scopes)]


def outside(ops, scopes):
    """The operations with no part under any of `scopes`."""
    return [op for op in ops if not _under(op[0], scopes)]


def one_way(ops, direction):
    """The operations that run `forward` or `backward`; all of them
    where `direction` is None."""
    if direction is None:
        return list(ops)
    if direction not in ("forward", "backward"):
        raise ValueError("device_kinds: no direction %r" % (direction,))
    back = direction == "backward"
    return [op for op in ops if is_backward(op[0]) == back]


def kind_of(stack):
    """`op.<Operator>` of the outermost graph node the operation was
    lowered from, else its innermost `train.*` scope, else None."""
    ps = parts(stack)
    for p in ps:
        if p.startswith("op."):
            return p
    for p in reversed(ps):
        if p.startswith("train."):
            return p
    return None


def node_of(stack):
    """The name of the outermost graph node the operation was lowered
    from: the part before its first `op.` part; None where it has
    none."""
    ps = parts(stack)
    for i, p in enumerate(ps):
        if p.startswith("op."):
            return ps[i - 1] if i else None
    return None


def share(picked, busy_s):
    """Per cent of the busy seconds that `picked` cover; None where
    they cover none."""
    secs = ds._seconds(picked)
    return 100.0 * secs / busy_s if secs and busy_s else None


def by_kind(ops, categories):
    """{kind: [operations, forward s, backward s, of both the seconds
    in operations whose `hlo_category` is a convolution fusion]}, the
    operations of no kind under `_unscoped_`; most seconds first."""
    groups = {}
    for op, cat in zip(ops, categories):
        g = groups.setdefault(kind_of(op[0]) or UNSCOPED,
                              ([], [], []))
        g[1 if is_backward(op[0]) else 0].append(op)
        if cat == CONV_CATEGORY:
            g[2].append(op)
    rows = {k: [len(f) + len(b), ds._seconds(f), ds._seconds(b),
                ds._seconds(c)] for k, (f, b, c) in groups.items()}
    return dict(sorted(rows.items(), key=lambda kv: -(kv[1][1] +
                                                      kv[1][2])))


def by_node(ops, top=TOP_NODES):
    """[[node, kind, forward s, backward s]] for the `top` graph nodes
    with most device seconds."""
    groups = {}
    for op in ops:
        node = node_of(op[0])
        if node is not None:
            g = groups.setdefault((node, kind_of(op[0])), ([], []))
            g[1 if is_backward(op[0]) else 0].append(op)
    rows = [[node, kind, ds._seconds(f), ds._seconds(b)]
            for (node, kind), (f, b) in groups.items()]
    return sorted(rows, key=lambda r: -(r[2] + r[3]))[:top]


def by_category(ops, categories):
    """{hlo_category: [operations, seconds]}, most seconds first."""
    groups = {}
    for op, cat in zip(ops, categories):
        groups.setdefault(cat or "_none_", []).append(op)
    rows = {c: [len(v), ds._seconds(v)] for c, v in groups.items()}
    return dict(sorted(rows.items(), key=lambda kv: -kv[1][1]))


def tables(view):
    """The two printed tables and, for what the names do not cover, the
    compiler's categories."""
    ops, cats = view["ops"], view["categories"]
    loose = [i for i, op in enumerate(ops) if kind_of(op[0]) is None]
    return {
        "device_by_kind": {"busy_s": ds._seconds(ops),
                           "kinds": by_kind(ops, cats)},
        "device_by_node": by_node(ops),
        "unscoped_by_category": by_category(
            [ops[i] for i in loose], [cats[i] for i in loose])}


def report(view):
    for name, table in tables(view).items():
        print("cellbench: %s %s" % (name, json.dumps(table)), flush=True)


def need_from(need_module, need, cfg, traffic):
    fn = getattr(importlib.import_module(need_module), need)
    return lambda: fn(cfg, traffic)


# -- the reader ---------------------------------------------------------------

def read(readings, what, scopes=(), direction=None, scope=None,
         module=None, need_module=None, need=None):
    summary = readings.get("trace")
    if not summary:
        return None
    view = readings.get("_device_kinds")
    if view is None:
        path = host_spans.find_trace(
            not_before=host_spans.process_started())
        if path is None:
            print("cellbench: device_kinds no trace of this process "
                  "under %s" % host_spans.OUT, flush=True)
            return None
        view = readings["_device_kinds"] = load(path)
        if under_any(view["ops"], KINDS):
            report(view)
        else:
            # the parent of the PR that added the scopes, or a program
            # served from a compile cache written before they existed:
            # an executable keeps the names of whoever compiled it
            print("cellbench: device_kinds no `op.` or `train.` scope on "
                  "any of %d operations" % len(view["ops"]), flush=True)
    if what in ("set_share", "outside_share"):
        named = under_any(view["ops"], scopes)
        if not named:
            return None                  # a program without the scopes
        if what == "set_share":
            return share(one_way(named, direction), summary["busy_s"])
        # names that cover everything read 0, not nothing
        return share(one_way(outside(view["ops"], scopes), direction),
                     summary["busy_s"]) or 0.0
    if what != "roofline":
        raise ValueError("device_kinds: no reading %r" % what)
    if "device_kind" not in readings:
        return None
    fn = need_from(need_module, need, readings["cfg"],
                   readings["traffic"])
    kind = readings["device_kind"]
    flops, nbytes = fn()
    print("cellbench: device_kinds_need %s" % json.dumps(
        {"need": need_module + "." + need, "flops": flops,
         "bytes": nbytes,
         "least_s_by_operations": ds.least_seconds(flops, 0, kind),
         "least_s_by_bytes": ds.least_seconds(0, nbytes, kind)}),
        flush=True)
    return ds.roofline(view, fn, kind, module, scope)


if __name__ == "__main__":
    report(load(sys.argv[1]))
