"""Weak-scaling harness for the SPMD train step — the analogue of the
reference's multi-GPU/multi-node scaling tables
(example/image-classification/README.md:302-319, AlexNet/Inception-v3/
ResNet-152 on 1..256 K80s at ~90% efficiency).

Runs the same per-device batch on growing device counts and reports
step time, weak-scaling efficiency, and the collective traffic XLA
inserted (parsed from the optimized HLO: all-reduce / all-gather /
reduce-scatter / collective-permute / all-to-all output bytes).

It takes the devices JAX gives it and names them in every line. Step
times and rates are device metrics, so timing on the host CPU is an
error. The collective BYTES are a property of the partitioning, not
the fabric: `--compile-only` reports them without running a step, and
is the one mode that also works on a virtual CPU mesh
(`JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8`).

    python bench_scaling.py --devices 1,4        # one four-chip host
    python bench_scaling.py --devices 1,4 --network transformer_lm
    python bench_scaling.py --zero1              # + sharded optimizer

Prints one JSON line per device count, a GSPMD one-jit row (the
`data × fsdp` SpecLayout + ZeRO-sharded optimizer path of
docs/parallelism.md "One-jit GSPMD path"; --skip-gspmd drops it), a
summary line {"metric": "scaling_sweep", ...} the driver can archive,
then a markdown table. A failed run prints one diagnostic summary line
(value null) and exits 1.
"""
import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--devices", default="1,2,4,8",
                   help="comma-separated device counts")
    p.add_argument("--network", default="resnet",
                   choices=["resnet", "transformer_lm"])
    p.add_argument("--seq-parallel", action="store_true",
                   help="transformer_lm over an 'sp' mesh (ring "
                        "attention) instead of a data mesh")
    p.add_argument("--window", type=int, default=0,
                   help="banded (windowed) attention for "
                        "transformer_lm, all rows incl. the GSPMD one; "
                        "with --seq-parallel the ring communication "
                        "scales with the window")
    p.add_argument("--expert-parallel", action="store_true",
                   help="transformer_lm MoE over an 'expert' mesh "
                        "(all_to_all token exchange); experts = 2x "
                        "devices")
    p.add_argument("--per-device-batch", type=int, default=8)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--zero1", action="store_true",
                   help="shard optimizer state (ZeRO-1)")
    p.add_argument("--skip-gspmd", action="store_true",
                   help="drop the one-jit GSPMD (data x fsdp "
                        "SpecLayout + sharded-optimizer) row")
    p.add_argument("--fsdp", type=int, default=0,
                   help="fsdp axis size for the GSPMD row (0 = auto: "
                        "largest of 4/2/1 dividing the device count)")
    p.add_argument("--full-size", action="store_true",
                   help="the REAL bench.py configs (resnet-50 224px "
                        "batch 128/dev; transformer dim 2048): exact "
                        "collective bytes for the roofline in "
                        "docs/scaling.md. Pair with --compile-only on "
                        "a CPU host")
    p.add_argument("--compile-only", action="store_true",
                   help="lower+compile and report collective bytes "
                        "without running the step")
    return p.parse_args()


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s64": 8, "u64": 8, "pred": 1, "s8": 1,
                "u8": 1}
# every `dtype[dims]` group in an instruction's output shape (tuple
# outputs like `(f32[8], /*index=1*/f32[8]) all-reduce(...)` list many,
# with index comments interleaved)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# the executing op: whitespace-preceded collective name followed by its
# operand list paren. Operand REFERENCES (`get-tuple-element(%all-reduce
# .82)`) don't match: there the name is followed by `)` or `,`, not `(`.
_OP_RE = re.compile(
    r"\s((?:%s)[\w.-]*)\(" % "|".join(_COLLECTIVES))


def collective_bytes(hlo_text):
    """Sum output bytes of collective ops in optimized HLO, per op kind.

    Caveat: a collective INSIDE a while/fori loop appears once in the
    text but executes once per trip — e.g. the plain ring's ppermute
    (n-1 trips) vs the windowed ring's unrolled ceil((W-1)/Tb) hops
    count the same here despite very different wire traffic. Loop-free
    programs (dp/zero1/MoE) are exact; ring comparisons need the trip
    count applied by the reader (or real-fabric timing).

    Reads lines like
      %all-reduce = f32[64,128]{1,0} all-reduce(%dot), replica_groups=...
    incl. variadic tuple outputs. Bytes are per-device (each device
    materializes its own output buffer); multiply by the group size for
    fabric-level traffic. Async `-done` halves of start/done pairs are
    skipped so traffic isn't counted twice."""
    out = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        shapes_part = line.split(" = ", 1)[1]
        m = _OP_RE.search(shapes_part)
        if not m or m.group(1).endswith("-done"):
            continue
        kind = next(c for c in _COLLECTIVES
                    if m.group(1).startswith(c))
        total = 0
        for dt, dims in _SHAPE_RE.findall(shapes_part[:m.start()]):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    return out


def build_step(network, mesh, global_batch, zero1, seq_parallel=False,
               seq_len=64, num_experts=0, full_size=False, window=0,
               layout=None):
    from mxnet_tpu import models
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.parallel import make_train_step

    kw = dict(optimizer="sgd", optimizer_params={"momentum": 0.9},
              mesh=mesh)
    if layout is not None:
        # the GSPMD one-jit row: SpecLayout placement + the optimizer
        # state folded across the data x fsdp replicas
        kw = dict(optimizer="adam", optimizer_params={},
                  layout=layout, optimizer_sharding="zero1")
    elif zero1:
        kw.update(optimizer="adam", optimizer_params={},
                  optimizer_sharding="zero1")
    if full_size:
        kw["compute_dtype"] = "bfloat16"   # match bench.py exactly
    if network == "resnet":
        if full_size:
            sym = models.get_symbol(network="resnet", num_classes=1000,
                                    num_layers=50,
                                    image_shape=(3, 224, 224))
            shapes = {"data": (global_batch, 3, 224, 224),
                      "softmax_label": (global_batch,)}
        else:
            sym = models.get_symbol(network="resnet", num_classes=10,
                                    num_layers=8, image_shape=(3, 8, 8))
            shapes = {"data": (global_batch, 3, 8, 8),
                      "softmax_label": (global_batch,)}
    else:
        if full_size:
            sym = models.get_symbol(
                network="transformer", vocab_size=32768,
                seq_len=seq_len, num_layers=4, num_heads=16, dim=2048,
                seq_axis="sp" if seq_parallel else None,
                num_experts=num_experts,
                expert_axis="expert" if num_experts else None,
                attention_window=window)
        else:
            sym = models.get_symbol(
                network="transformer", vocab_size=256, seq_len=seq_len,
                num_layers=2, num_heads=4, dim=64,
                seq_axis="sp" if seq_parallel else None,
                num_experts=num_experts,
                expert_axis="expert" if num_experts else None,
                attention_window=window)
        shapes = {"data": (global_batch, seq_len),
                  "softmax_label": (global_batch, seq_len)}
    step = make_train_step(sym, **kw)
    state = step.init_state(Xavier(), shapes)
    return step, state, shapes


def _telemetry_row(step, state, bd, rng, iters, gb, n):
    """Per-step telemetry journal for one device count: a short extra
    pass where each step is waited for, so the recorded walls are true
    per-step times. Returns (summary dict for the JSON row, live
    state)."""
    import tempfile

    import jax
    from mxnet_tpu import telemetry
    jr = telemetry.journal()
    if jr is None:
        jr = telemetry.start_journal(
            tempfile.mkdtemp(prefix="bench-scaling-telemetry-"),
            run="bench_scaling")
    walls = []
    # short pass — each step is waited for, so don't repeat the whole
    # headline iteration count (same cap bench.py uses)
    for i in range(max(3, min(int(iters), 10))):
        t0 = telemetry.now_ms()
        state, outs = step(state, bd, 0.1, rng)
        jax.block_until_ready(outs)
        walls.append(telemetry.now_ms() - t0)
        telemetry.journal_step(loop="bench_scaling", devices=n,
                               step=i, wall_ms=round(walls[-1], 3),
                               samples=gb)
    walls.sort()
    return {"journal": jr.path,
            "step_ms_p50": round(telemetry.quantile(walls, 0.5), 3),
            "step_ms_p95": round(telemetry.quantile(walls, 0.95), 3),
            "samples_per_sec": round(
                gb * len(walls) / (sum(walls) / 1e3), 1)}, state


def _make_batch(network, shapes, gb):
    import numpy as np
    rng_np = np.random.RandomState(0)
    if network == "resnet":
        return {"data": rng_np.standard_normal(
            shapes["data"]).astype(np.float32),
            "softmax_label": rng_np.randint(
                0, 10, gb).astype(np.float32)}
    toks = rng_np.randint(0, 256, shapes["data"]).astype(np.float32)
    return {"data": toks, "softmax_label": np.roll(toks, -1, axis=1)}


def _measure(step, state, bd, rng, iters):
    """Warmup + the headline timed loop, closed by block_until_ready.
    Returns (sec/step, live state)."""
    import jax
    state, outs = step(state, bd, 0.1, rng)   # warmup (cached)
    jax.block_until_ready(outs)
    t0 = time.time()
    for _ in range(iters):
        state, outs = step(state, bd, 0.1, rng)
    jax.block_until_ready(outs)
    return (time.time() - t0) / iters, state


def _gspmd_row(args, devices, n):
    """The one-jit GSPMD row (docs/parallelism.md "One-jit GSPMD
    path"): data x fsdp mesh, SpecLayout auto rules, optimizer state
    folded across ALL replicas."""
    import jax
    import numpy as np
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.sharding import SpecLayout

    # explicit --fsdp divisibility was validated in _run, pre-sweep;
    # the auto pick divides by construction
    f = args.fsdp or max(d for d in (4, 2, 1) if n % d == 0)
    mesh = make_mesh({"data": n // f, "fsdp": f},
                     devices=devices[:n])
    # min_shard_size=0: the smoke-size nets are tiny — on a real run
    # the MXNET_FSDP_MIN_SIZE default keeps tiny tensors replicated
    layout = SpecLayout(mesh, min_shard_size=0 if not args.full_size
                        else None)
    gb = args.per_device_batch * n
    seq_len = 2048 if (args.full_size
                       and args.network == "transformer_lm") else 64
    step, state, shapes = build_step(
        args.network, None, gb, False, seq_len=seq_len,
        full_size=args.full_size, window=args.window, layout=layout)
    opt_bytes = int(telemetry.gauge(
        "gspmd.opt_state_bytes_per_dev").value or 0)
    bd = step.place_batch(_make_batch(args.network, shapes, gb))
    rng = jax.random.PRNGKey(0)

    lowered = step.lower(state, bd, 0.1, rng)
    compiled = lowered.compile()
    coll = collective_bytes(compiled.as_text())
    row = {"devices": n, "mode": "gspmd",
           "mesh": {"data": n // f, "fsdp": f},
           "global_batch": gb, "zero1": True,
           "opt_state_bytes_per_dev": opt_bytes,
           "collective_bytes_per_dev": coll,
           "full_size": bool(args.full_size)}
    if args.compile_only:
        row["step_ms"] = None
        return row
    dt, state = _measure(step, state, bd, rng, args.iters)
    telemetry_row, state = _telemetry_row(step, state, bd, rng,
                                          args.iters, gb, n)
    row.update(step_ms=round(dt * 1e3, 2),
               samples_s=round(gb / dt, 1), telemetry=telemetry_row)
    if args.network == "transformer_lm":
        row["seq_len"] = seq_len
        row["tokens_s"] = round(gb * seq_len / dt, 1)
    return row


def main():
    args = _parse_args()
    counts = sorted({int(c) for c in args.devices.split(",")})
    # killed mid-run -> still exactly one parseable JSON line; any
    # other failure prints the same shape and then raises
    from bench_common import fail_payload, install_death_stub
    install_death_stub("scaling_sweep", "samples/s")
    try:
        rows, gspmd_row, dev_fields = _run(args, counts)
    except Exception as e:
        print(json.dumps(fail_payload("scaling_sweep", "samples/s", e)))
        raise

    best = max((r for r in rows + ([gspmd_row] if gspmd_row else [])
                if r.get("samples_s")),
               key=lambda r: r["samples_s"], default=None)
    rate = "tokens_s" if rows and "tokens_s" in rows[0] else "samples_s"
    print(json.dumps({
        "metric": "scaling_sweep",
        "value": best["samples_s"] if best else None,
        "unit": "samples/s", "vs_baseline": None,
        **dev_fields, "network": args.network,
        "rows": rows, "gspmd": gspmd_row}))

    if args.compile_only or not rows:
        return
    base = rows[0]["step_ms"]
    print("\n| devices | global batch | step ms | %s | "
          "weak-scaling eff | collective bytes/dev |"
          % rate.replace("_s", "/s"))
    print("|---|---|---|---|---|---|")
    for r in rows + ([gspmd_row] if gspmd_row else []):
        if r.get("step_ms") is None:
            continue
        if r.get("mode") == "gspmd" and not args.zero1:
            # the GSPMD row always runs adam+zero1; without --zero1
            # the baseline row ran sgd+momentum, and a step-time ratio
            # would charge the optimizer difference to scaling loss
            eff_cell = "n/a (adam vs sgd base)"
        else:
            eff_cell = "%.0f%%" % (base / r["step_ms"] * 100)
        tot = sum(r["collective_bytes_per_dev"].values())
        print("| %s | %d | %.2f | %.1f | %s | %s |" % (
            "%d (gspmd)" % r["devices"] if r.get("mode") == "gspmd"
            else "%d" % r["devices"],
            r["global_batch"], r["step_ms"],
            r[rate if rate in r else "samples_s"], eff_cell,
            "{:,}".format(tot)))


def _run(args, counts):
    import jax
    import numpy as np  # noqa: F401 (helpers import their own)
    from bench_common import device_fields, require_accelerator
    from mxnet_tpu.parallel import make_mesh

    # only the collective-bytes mode has anything to say on a CPU mesh
    dev_fields = device_fields() if args.compile_only \
        else require_accelerator("bench_scaling.py (without "
                                 "--compile-only)")
    devices = jax.devices()
    if len(devices) < max(counts):
        raise SystemExit("only %d devices visible, need %d"
                         % (len(devices), max(counts)))

    if (args.seq_parallel or args.expert_parallel) and \
            args.network != "transformer_lm":
        raise SystemExit("--seq-parallel/--expert-parallel need "
                         "--network transformer_lm")
    if args.seq_parallel and args.expert_parallel:
        raise SystemExit("pick one of --seq-parallel/--expert-parallel "
                         "(composition lives in the test suite)")
    # pure arg math — fail BEFORE the sweep, not in _gspmd_row after
    # every count has been measured
    if args.fsdp and not args.skip_gspmd and not args.seq_parallel \
            and not args.expert_parallel \
            and max(counts) % args.fsdp != 0:
        raise SystemExit("--fsdp %d does not divide %d devices (the "
                         "GSPMD row runs at the largest sweep count)"
                         % (args.fsdp, max(counts)))

    rows = []
    for n in counts:
        num_experts = 0
        if args.seq_parallel:
            # weak scaling in SEQUENCE length: 64 tokens per device on
            # an sp mesh, batch fixed — the long-context axis
            mesh = make_mesh({"sp": n}, devices=devices[:n])
            gb, seq_len = args.per_device_batch, 64 * n
        elif args.expert_parallel:
            # weak scaling in EXPERTS: 2 experts per device, tokens
            # fixed per device — the MoE capacity axis
            mesh = make_mesh({"expert": n}, devices=devices[:n])
            gb, seq_len = args.per_device_batch * n, 64
            num_experts = 2 * n
        else:
            mesh = make_mesh({"data": n}, devices=devices[:n])
            gb, seq_len = args.per_device_batch * n, 64
        if args.full_size:
            seq_len = 2048 if args.network == "transformer_lm" \
                else seq_len
        step, state, shapes = build_step(args.network, mesh, gb,
                                         args.zero1, args.seq_parallel,
                                         seq_len, num_experts,
                                         args.full_size, args.window)
        bd = step.place_batch(_make_batch(args.network, shapes, gb))
        rng = jax.random.PRNGKey(0)

        lowered = step.lower(state, bd, 0.1, rng)
        compiled = lowered.compile()
        coll = collective_bytes(compiled.as_text())

        if args.compile_only:
            rows.append({"devices": n, "global_batch": gb,
                         "step_ms": None,
                         "collective_bytes_per_dev": coll,
                         "zero1": bool(args.zero1),
                         "full_size": bool(args.full_size)})
            print(json.dumps(rows[-1]))
            continue

        dt, state = _measure(step, state, bd, rng, args.iters)
        telemetry_row, state = _telemetry_row(step, state, bd, rng,
                                              args.iters, gb, n)

        row = {"devices": n, "global_batch": gb,
               "step_ms": round(dt * 1e3, 2),
               "samples_s": round(gb / dt, 1),
               "collective_bytes_per_dev": coll,
               "zero1": bool(args.zero1),
               "telemetry": telemetry_row}
        if args.network == "transformer_lm":
            # under --seq-parallel the per-sample token count grows
            # with n, so tokens/s is the honest weak-scaling metric
            row["seq_len"] = seq_len
            row["tokens_s"] = round(gb * seq_len / dt, 1)
        rows.append(row)
        print(json.dumps(rows[-1]))

    gspmd_row = None
    if not args.skip_gspmd and not args.seq_parallel and \
            not args.expert_parallel:
        try:
            gspmd_row = _gspmd_row(args, devices, max(counts))
        except SystemExit:
            raise
        except Exception as e:  # noqa: BLE001 — a GSPMD-row failure
            # must not discard the sweep already measured above; the
            # row carries the error instead
            gspmd_row = {"devices": max(counts), "mode": "gspmd",
                         "step_ms": None,
                         "error": "%s: %s" % (type(e).__name__,
                                              str(e)[:300])}
        print(json.dumps(gspmd_row))
    return rows, gspmd_row, dev_fields


if __name__ == "__main__":
    main()
