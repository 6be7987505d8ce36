"""How `scopes.xplane.pb` and `scopes.expected.json` were made (on the
chip, once): a toy Granite `ContinuousDecoder` (Mamba-2 and attention
layers) serving a few requests of two prompt lengths, traced with the
settings `cellbench/run.py` uses, so the device plane holds operations
lowered under the `mamba2.*` scopes inside `generator_step` and
`decode_step` executions, and the host plane the program's
`mxnet.admit.prefill` spans with their `P`. `reduce` keeps what
`cellbench/readers/device_scope.py` reads (each TPU plane's `XLA Ops`
and `XLA Modules` lines with the operations' name stacks, the host's
prefill spans with their stats) and nothing else, and `main` checks
that the reader sees the same numbers in both. Run from the root of
the repo:

    python3 cellbench/testdata/record_scopes.py <output directory>
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

GRANITE = {
    "family": "granite", "hidden_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "shared_intermediate_size": 512,
    "vocab_size": 1024, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"],
    "max_position_embeddings": 512, "mamba_n_heads": 8,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 256,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.125,
    "logits_scaling": 8, "initializer_range": 0.02,
    "compute_dtype": "bfloat16"}
POOL = {"slots": 2, "max_len": 512, "queue_cap": 8,
        "prompt_lengths": [128, 256], "output_lengths": [3, 4]}
REQUESTS = ((128, 3), (256, 4), (128, 2))     # prompt, output tokens
DEVICE_KIND = "TPU v5 lite"


def serve(decoder, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for p, n in REQUESTS:            # one at a time: one prefill each
        out.append(decoder.submit(
            rng.integers(1, GRANITE["vocab_size"], p), n
        ).result(timeout=600))
    return out


def reduce(raw):
    """The bytes of an `XSpace` with only what `device_scope.load`
    reads: names and times as recorded, the operations' `tf_op` and the
    prefill spans' own stats kept, everything else dropped."""
    from cellbench.readers import device_scope as ds
    pb2 = ds._xplane_pb2()
    space = pb2.XSpace()
    space.ParseFromString(raw)
    out = pb2.XSpace()

    def copy_stat(src_plane, dst_plane, stat, into):
        name = src_plane.stat_metadata[stat.metadata_id].name
        dst_plane.stat_metadata[stat.metadata_id].id = stat.metadata_id
        dst_plane.stat_metadata[stat.metadata_id].name = name
        kept = into.add(metadata_id=stat.metadata_id)
        which = stat.WhichOneof("value")
        if which == "ref_value":
            kept.str_value = src_plane.stat_metadata[stat.ref_value].name
        elif which:
            setattr(kept, which, getattr(stat, which))

    for plane in space.planes:
        device = plane.name.startswith(ds.DEVICE_PLANE)
        if not device and plane.name != ds.HOST_PLANE:
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device:
                events = list(line.events) if line.name in (
                    ds.OPS_LINE, ds.MODULES_LINE) else []
            else:
                events = [e for e in line.events
                          if plane.event_metadata[e.metadata_id].name
                          == ds.PREFILL_SPAN]
            if not events:
                continue
            kept = new.lines.add(id=line.id, name=line.name,
                                 timestamp_ns=line.timestamp_ns)
            for e in events:
                ev = kept.events.add(metadata_id=e.metadata_id,
                                     offset_ps=e.offset_ps,
                                     duration_ps=e.duration_ps)
                md = plane.event_metadata[e.metadata_id]
                if e.metadata_id not in new.event_metadata:
                    new.event_metadata[e.metadata_id].id = e.metadata_id
                    new.event_metadata[e.metadata_id].name = md.name
                    for s in md.stats:
                        if plane.stat_metadata[s.metadata_id].name == \
                                ds.SCOPE_STAT:
                            copy_stat(plane, new, s, new.event_metadata[
                                e.metadata_id].stats)
                if not device:
                    for s in e.stats:
                        copy_stat(plane, new, s, ev.stats)
    return out.SerializeToString()


def expected(path):
    """The reader's own numbers at recording time; the test checks
    them again and cross-checks them by other routes."""
    from cellbench.ops import granite as ops
    from cellbench.readers import device_scope as ds
    v = ds.load(path)
    scopes = sorted({part for stack, _s, _d in v["ops"]
                     for part in stack.rstrip(":").split("/")
                     if part.startswith("mamba2.")})
    steps = ds.executions(v["modules"], "decode_step", v["ops"],
                          "mamba2.step")
    prefills = ds.executions(v["modules"], "generator_step", v["ops"],
                             "mamba2.scan")
    need = lambda fn: (lambda *p: fn(GRANITE, POOL, *p))
    return {
        "scopes": scopes, "ops": len(v["ops"]),
        "modules": sorted({m[0].split("(", 1)[0] for m in v["modules"]}),
        "decode_steps": len(steps), "prefills": len(prefills),
        "prefill_lengths": [p for _s, p, _run in v["prefills"]],
        "lengths_by_execution": [ds.prefill_at(v["prefills"], s)[0]
                                 for s, _d in prefills],
        "mamba2_seconds": ds.scope_seconds(v["ops"], "mamba2."),
        "step_seconds": ds.scope_seconds(v["ops"], "mamba2.step"),
        "scan_seconds": ds.scope_seconds(v["ops"], "mamba2.scan"),
        "conv_seconds": ds.scope_seconds(v["ops"], "mamba2.conv"),
        "all_seconds": ds._seconds(v["ops"]),
        "step_roofline": ds.roofline(
            v, need(ops.mamba2_step_need), DEVICE_KIND, "decode_step",
            "mamba2.step"),
        "scan_roofline": ds.roofline(
            v, need(ops.mamba2_scan_need), DEVICE_KIND,
            "generator_step", "mamba2.scan", by_prompt=True),
        "decode_roofline": ds.roofline(
            v, need(ops.decode_step_need), DEVICE_KIND, "decode_step")}


def main(out):
    import jax
    from cellbench.models import granite as model
    from cellbench.reference import granite as ref
    os.makedirs(out, exist_ok=True)
    params = ref.make_params(GRANITE, 7, GRANITE["compute_dtype"])
    _gen, decoder, server = model.build_server(GRANITE, POOL, params)
    serve(decoder, 1)                 # every shape compiles out here
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    serve(decoder, 2)
    jax.profiler.stop_trace()
    server.close()
    decoder.close()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    raw = os.path.join(out, "scopes.raw.xplane.pb")
    dest = os.path.join(out, "scopes.xplane.pb")
    shutil.copy(found[0], raw)
    shutil.rmtree(tmp)
    with open(raw, "rb") as f, open(dest, "wb") as g:
        g.write(reduce(f.read()))
    want = expected(dest)
    if want != expected(raw):
        sys.exit("record_scopes: the reduced trace reads differently")
    want["how"] = ("recorded on a %s by cellbench/testdata/"
                   "record_scopes.py and reduced there to what the "
                   "device_scope reader reads; numbers are the "
                   "reader's own at recording time, the same on the "
                   "raw trace, and cross-checked in the test by other "
                   "routes" % jax.devices()[0].device_kind)
    with open(os.path.join(out, "scopes.expected.json"), "w") as f:
        json.dump(want, f, indent=1)
    print("record_scopes: %d bytes, %s" % (
        os.path.getsize(dest), json.dumps(want)))


if __name__ == "__main__":
    main(sys.argv[1])
