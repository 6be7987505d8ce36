"""How `kinds.xplane.pb` and `kinds.expected.json` were made (on the
chip, once): a toy convolutional net (two convolutions, each with its
batch norm and ReLU, a pool and a classifier) trained by `TrainStep.fit`
with SGD momentum in bfloat16, traced with the settings
`cellbench/run.py` uses. `TrainStep` differentiates the graph with
`jax.vjp`, so the device plane holds operations lowered under
`jvp(train.fwd)/<node>/op.<Operator>` and
`transpose(jvp(train.fwd))/<node>/op.<Operator>`, and under
`train.cast`, `train.update` and `train.metric`, inside executions of
`jit_step_with_metric`. `reduce` keeps what
`cellbench/readers/device_kinds.py` reads (each TPU plane's `XLA Ops`
and `XLA Modules` lines with the operations' name stacks and
`hlo_category`) and nothing else, and `main` checks that the reader
sees the same numbers in both. Run from the root of the repo:

    python3 cellbench/testdata/record_kinds.py <output directory>
"""
import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BATCH, SIDE, BATCHES = 64, 56, 4
CHANNELS = (32, 64, 64)             # data, conv1 (3x3), conv2 (1x1)
CLASSES = 10
DEVICE_KIND = "TPU v5 lite"
MODULE = "step_with_metric"
NEED = ("cellbench.testdata.record_kinds", "convs_need")


def convs_need(_cfg, _traffic):
    """(operations, bytes) of one step's two convolutions, counted as
    `cellbench/ops/resnet_convs.py` counts: three passes, each moving
    its input map, its output map and its weight once in two bytes."""
    c0, c1, c2 = CHANNELS
    area = SIDE * SIDE
    macs = (c1 * c0 * 9 + c2 * c1) * area
    maps = (c0 + c1 + c1 + c2) * area
    weights = c1 * c0 * 9 + c2 * c1
    return 3 * 2 * macs * BATCH, 3 * 2 * (maps * BATCH + weights)


def build():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_train_step
    net = mx.sym.Variable("data")
    for i, (c, k) in enumerate(zip(CHANNELS[1:], (3, 1)), 1):
        net = mx.sym.Convolution(net, name="conv%d" % i, num_filter=c,
                                 kernel=(k, k), pad=(k // 2, k // 2),
                                 no_bias=True)
        net = mx.sym.BatchNorm(net, name="bn%d" % i, fix_gamma=False)
        net = mx.sym.Activation(net, name="relu%d" % i, act_type="relu")
    net = mx.sym.Pooling(net, name="pool", global_pool=True,
                         kernel=(SIDE, SIDE), pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net, name="flat"),
                                name="fc", num_hidden=CLASSES)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(5)
    n = BATCH * BATCHES
    feed = mx.io.NDArrayIter(
        mx.nd.array(rng.normal(size=(n, CHANNELS[0], SIDE, SIDE))
                    .astype(np.float32)),
        mx.nd.array(rng.integers(0, CLASSES, n).astype(np.float32)),
        batch_size=BATCH)
    step = make_train_step(net, optimizer="sgd",
                           optimizer_params={"momentum": 0.9,
                                             "wd": 1e-4},
                           compute_dtype="bfloat16")
    return step, feed


def fit(step, feed, state=None):
    import mxnet_tpu as mx
    from mxnet_tpu.initializer import Xavier
    state, _ = step.fit(feed, num_epoch=1, state=state,
                        initializer=Xavier(), lr=0.05,
                        eval_metric=mx.metric.CrossEntropy())
    return state


def reduce(raw):
    """The bytes of an `XSpace` with only what `device_kinds.load`
    reads: the TPU planes' two lines, names and times as recorded, the
    operations' `tf_op` and `hlo_category` kept, everything else
    dropped."""
    from cellbench.readers import device_kinds as dk
    from cellbench.readers import device_scope as ds
    pb2 = ds._xplane_pb2()
    space = pb2.XSpace()
    space.ParseFromString(raw)
    out = pb2.XSpace()
    keep = (ds.SCOPE_STAT, dk.CATEGORY_STAT)
    for plane in space.planes:
        if not plane.name.startswith(ds.DEVICE_PLANE):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if line.name not in (ds.OPS_LINE, ds.MODULES_LINE):
                continue
            kept = new.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            for e in line.events:
                kept.events.add(metadata_id=e.metadata_id,
                                offset_ps=e.offset_ps,
                                duration_ps=e.duration_ps)
                if e.metadata_id in new.event_metadata:
                    continue
                md = plane.event_metadata[e.metadata_id]
                new.event_metadata[e.metadata_id].id = e.metadata_id
                new.event_metadata[e.metadata_id].name = md.name
                for s in md.stats:
                    name = plane.stat_metadata[s.metadata_id].name
                    if name not in keep:
                        continue
                    new.stat_metadata[s.metadata_id].id = s.metadata_id
                    new.stat_metadata[s.metadata_id].name = name
                    which = s.WhichOneof("value")
                    value = plane.stat_metadata[s.ref_value].name \
                        if which == "ref_value" else getattr(s, which)
                    new.event_metadata[e.metadata_id].stats.add(
                        metadata_id=s.metadata_id, str_value=str(value))
    return out.SerializeToString()


def expected(path):
    """The reader's own numbers at recording time; the test checks
    them again and cross-checks them by other routes."""
    from cellbench.readers import device_kinds as dk
    from cellbench.readers import device_scope as ds
    v = dk.load(path)
    ops, busy = v["ops"], ds._seconds(v["ops"])
    conv = dk.under_any(ops, ["op.Convolution"])
    t = dk.tables(v)
    return {
        "ops": len(ops), "busy_s": busy,
        "modules": sorted({m[0].split("(", 1)[0] for m in v["modules"]}),
        "steps": len(ds.executions(v["modules"], MODULE, ops)),
        "categories": sorted(set(v["categories"])),
        "device_by_kind": t["device_by_kind"]["kinds"],
        "device_by_node": t["device_by_node"],
        "unscoped_by_category": t["unscoped_by_category"],
        "named_share": dk.share(dk.under_any(ops, dk.KINDS), busy),
        "outside_share": dk.share(dk.outside(ops, dk.KINDS), busy),
        "conv_share": dk.share(conv, busy),
        "conv_forward_share": dk.share(dk.one_way(conv, "forward"), busy),
        "conv_backward_share": dk.share(dk.one_way(conv, "backward"),
                                        busy),
        "conv_roofline": ds.roofline(
            v, dk.need_from(*NEED, None, None), DEVICE_KIND, MODULE,
            "op.Convolution")}


def main(out):
    import jax
    os.makedirs(out, exist_ok=True)
    step, feed = build()
    state = fit(step, feed)                  # compiles out here
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    state = fit(step, feed, state)
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    raw = os.path.join(out, "kinds.raw.xplane.pb")
    dest = os.path.join(out, "kinds.xplane.pb")
    shutil.copy(found[0], raw)
    shutil.rmtree(tmp)
    with open(raw, "rb") as f, open(dest, "wb") as g:
        g.write(reduce(f.read()))
    want = expected(dest)
    if want != expected(raw):
        sys.exit("record_kinds: the reduced trace reads differently")
    want["how"] = ("recorded on a %s by cellbench/testdata/"
                   "record_kinds.py and reduced there to what the "
                   "device_kinds reader reads; numbers are the "
                   "reader's own at recording time, the same on the "
                   "raw trace, and cross-checked in the test by other "
                   "routes" % jax.devices()[0].device_kind)
    with open(os.path.join(out, "kinds.expected.json"), "w") as f:
        json.dump(want, f, indent=1)
    print("record_kinds: %d bytes of %d, %s" % (
        os.path.getsize(dest), os.path.getsize(raw), json.dumps(want)))


if __name__ == "__main__":
    main(sys.argv[1])
