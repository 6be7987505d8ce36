"""How `spans.xplane.pb` and `spans.expected.json` were made (on the
chip, once): a toy `ContinuousDecoder` serving three requests and a toy
`TrainStep.fit`, traced with the settings `cellbench/run.py` uses, so
the host plane holds the program's own nested `mxnet.*` phases beside
the device's operations. The raw trace is 1.2 MB, half of it the HLO
protos of the programs that ran; `reduce` keeps what the readers read
(each device's `XLA Ops` line, the host's program spans, their names)
and nothing else, about 100 kB, and `main` checks that the readers see
the same numbers in both. Run from the root of the repo:

    python3 cellbench/testdata/record_spans.py <output directory>
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

OPT = {"family": "opt", "hidden_size": 256, "num_attention_heads": 4,
       "ffn_dim": 1024, "num_hidden_layers": 1, "vocab_size": 1024,
       "max_position_embeddings": 512, "init_std": 0.02,
       "compute_dtype": "bfloat16"}
POOL = {"slots": 2, "max_len": 512, "queue_cap": 8}
REQUESTS = ((128, 3), (256, 4), (128, 2))     # prompt, output tokens


def serve(decoder, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    futs = [decoder.submit(rng.integers(1, OPT["vocab_size"], p), n)
            for p, n in REQUESTS]
    return [f.result(timeout=600) for f in futs]


def fit(step, feed, state=None):
    from mxnet_tpu.initializer import Xavier
    state, _ = step.fit(feed, num_epoch=2, state=state,
                        initializer=Xavier(), lr=0.05)
    return state


def build():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_train_step
    from cellbench.models import opt as model
    from cellbench.reference import opt as ref

    params = ref.make_params(OPT, 7, OPT["compute_dtype"])
    _gen, decoder, server = model.build_server(OPT, POOL, params)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=256)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=16)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.default_rng(7)
    X = rng.standard_normal((256, 256)).astype(np.float32)
    y = rng.integers(0, 16, 256).astype(np.float32)
    feed = mx.io.NDArrayIter(mx.nd.array(X), mx.nd.array(y),
                             batch_size=128)
    return decoder, server, make_train_step(net), feed


def reduce(raw):
    """The bytes of an `XSpace` with only what `cellbench/readers/`
    read: the `XLA Ops` line of every TPU plane and, on the host plane,
    the events `trace._is_program_span` accepts; names and times as
    recorded, every stat and every other plane dropped."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from cellbench.readers import trace
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        names = plane.event_metadata
        if plane.name.startswith(trace.DEVICE_PLANE):
            def keep(line, _ev):
                return line.name == trace.OPS_LINE
        elif plane.name == trace.HOST_PLANE:
            def keep(_line, ev):
                return trace._is_program_span(names[ev.metadata_id].name)
        else:
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            events = [e for e in line.events if keep(line, e)]
            if not events:
                continue
            kept = new.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            for e in events:
                kept.events.add(metadata_id=e.metadata_id,
                                offset_ps=e.offset_ps,
                                duration_ps=e.duration_ps)
                new.event_metadata[e.metadata_id].id = e.metadata_id
                new.event_metadata[e.metadata_id].name = \
                    names[e.metadata_id].name
    return out.SerializeToString()


def expected(path, window_s):
    """The reader's own numbers at recording time; the test checks them
    again and cross-checks them by other routes."""
    from cellbench.readers import host_spans as hs
    v = hs.view(path, window_s)
    lines = v["lines"]
    names = sorted({n for ev in lines for n, _s, _d in ev})
    edges = sorted({"%s>%s" % (ev[p][0], ev[i][0])
                    for ev in lines
                    for i, p in enumerate(hs.nest(ev)) if p is not None})
    return {
        "names": names, "edges": edges,
        "decode_steps": sum(n == "mxnet.serve.decode.step"
                            for ev in lines for n, _s, _d in ev),
        "train_steps": sum(n == "mxnet.train.step"
                           for ev in lines for n, _s, _d in ev),
        "idle_s": sum(e - s for s, e in v["gaps"]) * 1e-9,
        "idle_by_phase": hs.idle_by_phase(v["gaps"], lines),
        "admit_wall_share": hs.wall_share(
            lines, "mxnet.serve.decode.admit", window_s),
        "idle_under_admit_share": hs.idle_share_under(
            v["gaps"], lines, "mxnet.serve.decode.admit"),
        "decode_step_host_ms": 1e-6 * hs.median_less_child(
            lines, "mxnet.serve.decode.step", "mxnet.step.wait"),
        "fit_step_host_ms": 1e-6 * hs.median_less_child(
            lines, "mxnet.train.step", "mxnet.step.window_wait"),
        "window_s": window_s}


def main(out):
    import time
    import jax
    os.makedirs(out, exist_ok=True)
    decoder, server, step, feed = build()
    serve(decoder, 1)                 # every shape compiles out here
    state = fit(step, feed)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out, "_trace")
    t0 = time.perf_counter()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    serve(decoder, 2)
    fit(step, feed, state)
    jax.profiler.stop_trace()
    window_s = time.perf_counter() - t0
    server.close()
    decoder.close()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    raw = os.path.join(out, "spans.raw.xplane.pb")
    dest = os.path.join(out, "spans.xplane.pb")
    shutil.copy(found[0], raw)
    shutil.rmtree(tmp)
    with open(raw, "rb") as f, open(dest, "wb") as g:
        g.write(reduce(f.read()))
    want = expected(dest, window_s)
    if want != expected(raw, window_s):
        sys.exit("record_spans: the reduced trace reads differently")
    want["how"] = ("recorded on a %s by cellbench/testdata/"
                   "record_spans.py and reduced there to what the "
                   "readers read; numbers are the reader's own at "
                   "recording time, the same on the raw trace, and "
                   "cross-checked in the test by other routes"
                   % jax.devices()[0].device_kind)
    with open(os.path.join(out, "spans.expected.json"), "w") as f:
        json.dump(want, f, indent=1)
    print("record_spans: %d bytes, %d names, %d decode steps, %d train "
          "steps" % (os.path.getsize(dest), len(want["names"]),
                     want["decode_steps"], want["train_steps"]))


if __name__ == "__main__":
    main(sys.argv[1])
