"""Headline benchmark. Default: ResNet-50 training throughput (img/s) on
one chip — same contract as always, ONE JSON line
{"metric", "value", "unit", "vs_baseline"}.

--network selects any catalog workload, mirroring the reference's
baseline table (example/image-classification/README.md:147-156) plus the
compute-dense transformer LM:

    python bench.py                          # resnet-50 (driver default)
    python bench.py --network resnet-18      # other depths: 34/101/152
    python bench.py --network inception-v3   # also inception-bn, alexnet
    python bench.py --network transformer_lm # MFU workload (tokens/s)

Baselines are the reference's published 1x K80 img/s numbers (BASELINE.md).
The transformer has no reference baseline (the reference predates it);
vs_baseline reports MFU against the 0.45 north-star instead.

Every result names the device it ran on (platform, device_kind,
device_count). The metrics are device rates, so a run that finds no
accelerator exits with an error; a failed run prints one diagnostic
JSON line (value null) and exits 1.
"""
import argparse
import json
import os
import sys
import time

# MXU-friendly matmul precision for the perf path (see mxnet_tpu/__init__)
os.environ.setdefault("MXNET_MATMUL_PRECISION", "default")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

# bf16 peak FLOP/s per chip, keyed by the device_kind string JAX
# reports (Google Cloud TPU documentation: v4, v5e, v5p, v6e). A device
# that is not here is an error, not a default.
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# image workloads: name -> (models.get_symbol kwargs, default batch,
# reference 1xK80 img/s baseline from BASELINE.md, fwd GMACs/image for
# the flops fallback, input size). inception-v3's baseline and GMACs are
# 299px figures — benching it at 224 would overstate vs_baseline ~1.8x.
_IMAGE_NETS = {
    "resnet-18": (dict(network="resnet", num_layers=18), 128, 185.0,
                  1.8, 224),
    "resnet-34": (dict(network="resnet", num_layers=34), 128, 172.0,
                  3.6, 224),
    "resnet-50": (dict(network="resnet", num_layers=50), 128, 109.0,
                  3.86, 224),
    "resnet-101": (dict(network="resnet", num_layers=101), 96, 78.0,
                   7.6, 224),
    "resnet-152": (dict(network="resnet", num_layers=152), 64, 57.0,
                   11.3, 224),
    "inception-bn": (dict(network="inception-bn"), 128, 152.0, 1.6, 224),
    "inception-v3": (dict(network="inception-v3"), 64, 30.4, 5.7, 299),
    "alexnet": (dict(network="alexnet"), 512, 457.0, 0.7, 224),
}

# transformer LM defaults: compute-dense enough that one v5e chip can
# reach the >=0.45 MFU north star (big matmuls, flash attention)
_TLM = dict(vocab=32768, seq_len=2048, layers=4, heads=16, dim=2048,
            batch=8)


def _backend():
    """Initialise the backend once, in this process, and refuse the
    host CPU: (jax, the result's device fields)."""
    import jax
    from bench_common import require_accelerator
    return jax, require_accelerator("bench.py")


def _timed_loop(jax, step, state, batch_dev, iters, lr=0.1):
    """Warmup (2 steps, waited for) then the timed loop, closed by
    block_until_ready on the last step's outputs.

    Returns (elapsed seconds, live state) — the state handed in is
    donated by the step, so the caller must carry the returned one."""
    rng = jax.random.PRNGKey(0)
    for _ in range(2):
        state, outs = step(state, batch_dev, lr, rng)
    jax.block_until_ready(outs)

    t0 = time.time()
    for _ in range(iters):
        state, outs = step(state, batch_dev, lr, rng)
    jax.block_until_ready(outs)
    return time.time() - t0, state


def _telemetry_pass(jax, step, state, batch_dev, lr, iters, samples,
                    metric):
    """Per-step telemetry journal for the run: a short extra pass
    where each step is waited for, so the recorded wall times are true
    per-step times (the headline timed loop stays sync-free and is
    untouched). Writes the journal (MXNET_TELEMETRY, else a temp dir)
    and returns the summary dict folded into the BENCH json."""
    import tempfile

    from mxnet_tpu import telemetry
    from tools.telemetry_report import load, summarize

    jr = telemetry.journal()
    if jr is None:
        jr = telemetry.start_journal(
            tempfile.mkdtemp(prefix="bench-telemetry-"), run=metric)
    rng = jax.random.PRNGKey(0)
    n = max(3, min(int(iters), 10))
    last = telemetry.now_ms()
    for i in range(n):
        state, outs = step(state, batch_dev, lr, rng)
        jax.block_until_ready(outs)
        now = telemetry.now_ms()
        telemetry.journal_step(loop="bench", run=metric, step=i,
                               wall_ms=round(now - last, 3),
                               samples=samples)
        last = now
    recs = [r for r in load(jr.path)
            if r.get("kind") == "step" and r.get("run") == metric]
    s = summarize(recs)
    return {"journal": jr.path, "synced_steps": n,
            "step_ms_p50": s["step_ms"]["p50"],
            "step_ms_p95": s["step_ms"]["p95"],
            "samples_per_sec": s["samples_per_sec"]}


def _mfu(step, state, batch_vals, kind, sec_per_step, fallback_flops,
         jax, model_flops_only=False):
    """Actual FLOPs of the compiled step (XLA cost analysis; the analytic
    fallback covers kernels the analysis can't see) over the chip peak.

    model_flops_only (remat runs): cost analysis would count the
    recomputed forward too — that's HFU, not MFU — so use the analytic
    MODEL flops alone and a slower remat run can never report a higher
    MFU."""
    step_flops = 0.0
    if not model_flops_only:
        cost = step.cost_analysis(state, batch_vals, 0.1,
                                  jax.random.PRNGKey(0))
        step_flops = float((cost or {}).get("flops") or 0.0)
    step_flops = max(step_flops, fallback_flops)
    if kind not in _PEAK_FLOPS:
        raise KeyError("no peak FLOP/s on record for device_kind %r; "
                       "add it to _PEAK_FLOPS with its source" % (kind,))
    mfu = (step_flops / sec_per_step) / _PEAK_FLOPS[kind]
    return mfu, step_flops


def bench_image(name, args):
    metric = _metric_for(name)
    net_kwargs, def_batch, baseline, gmacs, image = _IMAGE_NETS[name]
    jax, dev_fields = _backend()

    batch = args.batch or int(os.environ.get("BENCH_BATCH", def_batch))
    dtype = args.dtype or os.environ.get("BENCH_DTYPE", "bfloat16")
    from mxnet_tpu import models
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.initializer import Xavier

    kwargs = dict(net_kwargs)
    kwargs.setdefault("num_classes", 1000)
    if kwargs["network"] == "resnet":
        kwargs["image_shape"] = (3, image, image)
    sym = models.get_symbol(**kwargs)
    step = make_train_step(
        sym, optimizer="sgd",
        optimizer_params={"momentum": 0.9, "wd": 1e-4,
                          "rescale_grad": 1.0 / batch},
        compute_dtype=None if dtype == "float32" else dtype,
        remat=args.remat or None)
    x = np.random.RandomState(0).standard_normal(
        (batch, 3, image, image)).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 1000, (batch,)).astype(
        np.float32)
    batch_vals = {"data": x, "softmax_label": y}

    state = step.init_state(Xavier(factor_type="in", magnitude=2.0),
                            {"data": (batch, 3, image, image),
                             "softmax_label": (batch,)})
    batch_dev = step.place_batch(batch_vals)

    iters = args.iters or int(os.environ.get("BENCH_ITERS", "20"))
    dt, state = _timed_loop(jax, step, state, batch_dev, iters)

    img_s = batch * iters / dt
    # fwd GMACs x2 flops/MAC x3 (fwd + ~2x bwd)
    fallback = 3 * 2 * gmacs * 1e9 * batch
    mfu, _flops = _mfu(step, state, batch_vals,
                       dev_fields["device_kind"], dt / iters,
                       fallback, jax, model_flops_only=args.remat)
    # after _mfu: the telemetry pass keeps stepping (donating) the state
    telemetry = _telemetry_pass(jax, step, state, batch_dev, 0.1,
                                iters, batch, metric)
    print(json.dumps({
        "metric": metric,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / baseline, 3),
        "step_time_ms": round(dt / iters * 1e3, 2),
        "batch": batch,
        "compute_dtype": dtype,
        "window": args.window,
        "remat": bool(args.remat),
        **dev_fields,
        "mfu": round(mfu, 4),
        "telemetry": telemetry}))


def _metric_for(network, decode=False, beam=0, spec=0):
    """The payload metric name for a bench configuration — ONE place,
    shared by the branch benches and the death stub (a drifted copy
    files a killed run's diagnostic under the wrong metric). The
    ``_gqa%d`` suffix follows BENCH_TLM_KV_HEADS like the live
    branches always did."""
    if network != "transformer_lm":
        return "%s_train_throughput" % network.replace("-", "")
    if not decode:
        metric = "transformer_lm_train_throughput"
    elif beam:
        metric = "transformer_lm_beam%d_decode_throughput" % beam
    elif spec:
        metric = "transformer_lm_spec%d_decode_throughput" % spec
    else:
        metric = "transformer_lm_decode_throughput"
    kv_heads = int(os.environ.get("BENCH_TLM_KV_HEADS", "0")) or None
    if kv_heads:
        metric += "_gqa%d" % kv_heads
    return metric


def bench_transformer(args):
    """Compute-dense LM workload: tokens/s + MFU. vs_baseline = measured
    MFU / 0.45 north star (BASELINE.md; the reference has no transformer)."""
    metric = _metric_for("transformer_lm")
    kv_heads = int(os.environ.get("BENCH_TLM_KV_HEADS", "0")) or None
    jax, dev_fields = _backend()

    c = dict(_TLM)
    for k in c:   # BENCH_TLM_DIM=256 etc. (smoke tests on CPU)
        c[k] = int(os.environ.get("BENCH_TLM_%s" % k.upper(), c[k]))
    if args.batch:
        c["batch"] = args.batch
    if args.seq_len:
        c["seq_len"] = args.seq_len
    B, T, D, L = c["batch"], c["seq_len"], c["dim"], c["layers"]
    V, F = c["vocab"], 4 * c["dim"]
    dtype = args.dtype or os.environ.get("BENCH_DTYPE", "bfloat16")
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.initializer import Xavier

    # BENCH_TLM_LOSS_CHUNK=N: chunked fused CE head — bounds the
    # head's live memory at (N, vocab) instead of (B*T, vocab),
    # the enabler for 64k-token training on one chip
    loss_chunk = int(os.environ.get("BENCH_TLM_LOSS_CHUNK", "0"))
    sym = transformer.get_symbol(V, T, num_layers=L,
                                 num_heads=c["heads"], dim=D,
                                 ffn_hidden=F,
                                 num_kv_heads=kv_heads,
                                 attention_window=args.window or 0,
                                 loss_chunk=loss_chunk)
    step = make_train_step(
        sym, optimizer="adam",
        optimizer_params={"rescale_grad": 1.0 / B},
        compute_dtype=None if dtype == "float32" else dtype,
        remat=args.remat or None)
    rng_np = np.random.RandomState(0)
    toks = rng_np.randint(0, V, (B, T)).astype(np.float32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    batch_vals = {"data": toks, "softmax_label": labels}

    state = step.init_state(Xavier(), {"data": (B, T),
                                       "softmax_label": (B, T)})
    batch_dev = step.place_batch(batch_vals)

    iters = args.iters or int(os.environ.get("BENCH_ITERS", "20"))
    dt, state = _timed_loop(jax, step, state, batch_dev, iters,
                            lr=1e-4)

    tok_s = B * T * iters / dt
    # analytic train flops (fwd x3): dense projections 8D^2+4DF per
    # token per layer (with GQA the k/v projections shrink to
    # Hkv*hd columns: 4D^2 + 4*D*kvdim), attention 4*Teff*D per token
    # per layer (QK^T + PV; Teff = min(T, window) under sliding-window
    # attention), vocab head 2DV per token. Matches the scaling-book
    # accounting; used as the floor under cost_analysis (the Pallas
    # flash kernel's internal flops are invisible to XLA's analysis).
    t_eff = min(T, args.window) if args.window else T
    kvdim = (D // c["heads"]) * kv_heads if kv_heads else D
    fwd = B * T * (L * (4 * D * D + 4 * D * kvdim + 4 * D * F
                        + 4 * t_eff * D)
                   + 2 * D * V)
    mfu, flops = _mfu(step, state, batch_vals,
                      dev_fields["device_kind"], dt / iters, 3 * fwd,
                      jax, model_flops_only=args.remat)
    # samples = tokens for the LM metric (tokens/s is the unit)
    telemetry = _telemetry_pass(jax, step, state, batch_dev, 1e-4,
                                iters, B * T, metric)
    print(json.dumps({
        "metric": metric,
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 3),
        "step_time_ms": round(dt / iters * 1e3, 2),
        "batch": B, "seq_len": T, "dim": D, "layers": L,
        "compute_dtype": dtype,
        "window": args.window,
        "remat": bool(args.remat),
        "loss_chunk": loss_chunk or None,
        **dev_fields,
        "step_tflops": round(flops / 1e12, 2),
        "mfu": round(mfu, 4),
        "telemetry": telemetry}))


def bench_decode(args):
    """KV-cache decode throughput: the whole prefill+scan generation
    runs as ONE device program (Generator.generate_on_device), so the
    measurement is chip decode speed, not dispatch round-trips.
    Decode is memory-bandwidth-bound (every step streams the full
    parameter set + caches), so tokens/s is the metric; no baseline
    (the reference predates transformer serving)."""
    beam = int(args.beam or 0)
    spec = int(args.speculative or 0)
    # BENCH_TLM_KV_HEADS: grouped-query decode (cache holds Hkv heads
    # instead of H — the decode path is cache-bandwidth-bound, so this
    # measures the GQA win directly). Named before the probe so early
    # failures report under the right metric.
    metric = _metric_for("transformer_lm", decode=True, beam=beam,
                         spec=spec)
    kv_heads = int(os.environ.get("BENCH_TLM_KV_HEADS", "0")) or None
    jax, dev_fields = _backend()

    c = dict(_TLM)
    for k in c:
        c[k] = int(os.environ.get("BENCH_TLM_%s" % k.upper(), c[k]))
    if args.batch:
        c["batch"] = args.batch
    B, D, L, V = c["batch"], c["dim"], c["layers"], c["vocab"]
    # --seq-len sets the prompt length for decode
    P = args.seq_len or int(os.environ.get("BENCH_DECODE_PROMPT",
                                           "128"))
    N = int(os.environ.get("BENCH_DECODE_TOKENS", "256"))
    # on-device speculative needs P + N + lookahead cache headroom on
    # both models (fixed-shape rounds may overrun by up to lookahead)
    max_len = P + N + (spec if spec else 0)
    dtype = args.dtype or os.environ.get("BENCH_DTYPE", "bfloat16")
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.initializer import Xavier

    sym = transformer.get_symbol(V, max_len, num_layers=L,
                                 num_heads=c["heads"], dim=D,
                                 ffn_hidden=4 * D,
                                 num_kv_heads=kv_heads)
    step = make_train_step(sym, optimizer="sgd")
    state = step.init_state(Xavier(), {
        "data": (B, max_len), "softmax_label": (B, max_len)})
    qz = args.quantize or ""
    gen = Generator(state[0], V, max_len=max_len, num_layers=L,
                    num_heads=c["heads"], dim=D,
                    batch_size=B, num_kv_heads=kv_heads,
                    dtype=None if dtype == "float32" else dtype,
                    quantize="int8" if "int8" in qz else None,
                    quantize_kv="kv8" in qz)
    draft = None
    if spec:
        # draft = same vocab/batch, quarter the layers and half the
        # width (the classic small-proposer setup); its own random
        # init is fine — the bench measures the mechanism's cost,
        # and a random draft gives the WORST-case acceptance, so
        # the reported tokens/s is a floor
        dL = max(1, L // 4)
        dD, dH = D // 2, max(1, c["heads"] // 2)
        dsym = transformer.get_symbol(V, max_len, num_layers=dL,
                                      num_heads=dH, dim=dD,
                                      ffn_hidden=4 * dD)
        dstep = make_train_step(dsym, optimizer="sgd")
        dstate = dstep.init_state(Xavier(), {
            "data": (B, max_len), "softmax_label": (B, max_len)})
        draft = Generator(dstate[0], V, max_len=max_len,
                          num_layers=dL, num_heads=dH, dim=dD,
                          batch_size=B,
                          dtype=None if dtype == "float32"
                          else dtype)
    prompt = np.random.RandomState(0).randint(0, V, (B, P))

    # marginal-rate measurement: time the program at two generation
    # lengths and difference them, so the (identical) prefill cost
    # cancels and the metric is PURE decode tokens/s
    N_SHORT = max(1, N // 8)
    if beam:
        run = lambda n, i: gen.beam_search_on_device(prompt, n,
                                                     beam_size=beam)
    elif spec:
        run = lambda n, i: gen.generate_speculative_on_device(
            draft, prompt, n, lookahead=spec)
    else:
        run = lambda n, i: gen.generate_on_device(prompt, n, seed=i)
    rounds = None
    if spec:   # warmup doubles as the acceptance telemetry read
        out, rounds = gen.generate_speculative_on_device(
            draft, prompt, N, lookahead=spec, return_rounds=True)
    else:
        out = run(N, 0)                       # compile + warmup
    assert out.shape == (B, P + N)
    run(N_SHORT, 0)                           # compile short

    iters = args.iters or int(os.environ.get("BENCH_ITERS", "3"))

    def timed(n_tok):
        t0 = time.time()
        for i in range(iters):
            run(n_tok, i)
        return (time.time() - t0) / iters         # output is host numpy

    dt_long = timed(N)
    dt_short = timed(N_SHORT)
    dt_decode = max(dt_long - dt_short, 1e-9)
    tok_s = B * (N - N_SHORT) / dt_decode
    print(json.dumps({
        "metric": metric,
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": None,
        "ms_per_token": round(dt_decode / (N - N_SHORT) * 1e3, 3),
        "end_to_end_tokens_s": round(B * N / dt_long, 2),
        "batch": B, "prompt_len": P, "new_tokens": N,
        "beam": beam or None,
        "speculative_lookahead": spec or None,
        "spec_rounds": rounds,
        "spec_accepted_per_round":
            round(N / rounds - 1, 3) if rounds else None,
        "kv_heads": kv_heads,
        "dim": D, "layers": L, "compute_dtype": dtype,
        "quantize": args.quantize,
        **dev_fields}))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--network", default="resnet-50",
                   choices=sorted(_IMAGE_NETS) + ["transformer_lm"])
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None,
                   help="transformer_lm only")
    p.add_argument("--iters", type=int, default=None)
    p.add_argument("--dtype", default=None,
                   choices=["float32", "bfloat16"])
    p.add_argument("--remat", action="store_true",
                   help="rematerialize the forward (activation memory "
                        "/ recompute trade — for configs that don't "
                        "fit HBM otherwise)")
    p.add_argument("--decode", action="store_true",
                   help="transformer_lm only: KV-cache generation "
                        "throughput instead of training")
    p.add_argument("--window", type=int, default=None,
                   help="transformer_lm only: sliding-window attention "
                        "width (training bench)")
    p.add_argument("--quantize", default=None,
                   choices=["int8", "kv8", "int8+kv8"],
                   help="with --decode: int8 = weight-only int8 "
                        "(halved weight HBM traffic), kv8 = int8 KV "
                        "caches with per-token scales (halved cache "
                        "traffic — the dominant stream at long "
                        "prompts), int8+kv8 = both")
    p.add_argument("--beam", type=int, default=None,
                   help="with --decode: on-device beam search width "
                        "(beams fold into the batch; tokens/s counts "
                        "emitted sequences, not beams)")
    p.add_argument("--speculative", type=int, default=None,
                   metavar="LOOKAHEAD",
                   help="with --decode: on-device speculative decoding "
                        "with a 1/4-depth half-width random-init draft "
                        "(worst-case acceptance floor); reports "
                        "acceptance telemetry")
    args = p.parse_args()
    if args.quantize and not args.decode:
        p.error("--quantize applies to --decode only")
    if args.beam and not args.decode:
        p.error("--beam applies to --decode only")
    if args.speculative and not args.decode:
        p.error("--speculative applies to --decode only")
    if args.speculative and args.beam:
        p.error("--speculative and --beam are mutually exclusive")
    # killed mid-run -> still exactly one parseable JSON line with the
    # branch's real metric name (_metric_for is the same naming the
    # branch benches use); any other failure prints the same shape and
    # then raises
    from bench_common import fail_payload, install_death_stub
    metric = _metric_for(
        args.network, decode=bool(args.decode),
        beam=int(args.beam or 0), spec=int(args.speculative or 0))
    unit = "tokens/s" if args.network == "transformer_lm" else "img/s"
    install_death_stub(metric, unit)
    lm = args.network == "transformer_lm"
    if lm and args.decode and args.remat:
        p.error("--remat is a training knob; not valid with --decode")
    try:
        if lm and args.decode:
            bench_decode(args)
        elif lm:
            bench_transformer(args)
        else:
            bench_image(args.network, args)
    except Exception as e:
        print(json.dumps(fail_payload(metric, unit, e)))
        raise


if __name__ == "__main__":
    main()
