"""Serving benchmark: closed-loop load generation against the
ServeEngine (docs/serving.md), structured like bench.py — ONE JSON
line {"metric", "value", "unit", "vs_baseline", ...}.

Offered-load sweep: for each concurrency level C, C closed-loop
clients each run `requests` submit→wait round trips against a fresh
engine; the sweep rows report throughput, request-latency
p50/p95/p99, and the mean batch fill the batcher achieved (the
whole point of the engine — fill should rise with C while per-request
latency stays bounded by the coalesce window + one forward).

    python bench_serve.py                       # default sweep 1,2,4,8,16
    python bench_serve.py --concurrency 1,8,32 --requests 200
    python bench_serve.py --buckets 1,4,16 --wait-ms 2

The headline `value` is the best throughput across the sweep (req/s);
`vs_baseline` is the batching gain — best throughput over the C=1
(unbatched closed-loop) throughput — when the sweep includes C=1.

FLEET MODE (``--replicas N``, docs/serving.md §fleet): the same
offered-load sweep against a ``ServeRouter`` over N subprocess
replicas — each replica its own process (its own GIL, its own XLA
client) behind real TCP, exactly the production topology scaled down.
Rows add per-replica dispatch fill so imbalance is visible; the
acceptance shape is req/s scaling near-linearly in replicas at
bounded p99 (ROADMAP item 2):

    python bench_serve.py --replicas 3          # fleet sweep
    python bench_serve.py --replicas 1          # same topology, N=1
                                                #   (the scaling base)

Every mode that spawns replica processes (``--replicas``,
``--controller``, ``--disagg``) runs only with ``JAX_PLATFORMS=cpu``: N
processes cannot share a chip, so the parent refuses before spawning
anywhere else. What such a run shows is the protocol and its counts
(fill, reroutes, recompiles), not speed. ``--work-ms`` (fleet default
5.0) adds a fixed per-forward sleep in each of those CPU replicas so
that queues form at all — set 0 to measure raw XLA-CPU forwards
instead; it never runs beside a device forward. The emitted metric is
``serve_fleet_throughput`` (same shape, plus ``replicas`` and
``per_replica_fill``).

Every result line names the platform, device kind and device count it
ran on.

DISAGG MODE (``--disagg P:D``, docs/serving.md §disaggregated
prefill): prefill/decode disaggregation A/B at equal chip count. Two
fleets of transformer-Generator replicas run the SAME workload —
short-prompt decode sessions measured for inter-token latency while
long-prompt generate load runs concurrently:

* disaggregated — P prefill-role + D decode-role replicas: long
  prefills run on the prefill chips, the decode replicas only scatter
  imported KV rows (zero prefill graph calls, asserted);
* colocated — P+D decode-role replicas: every long prefill stalls the
  admitting replica's (B, 1) step loop for every active slot on it.

The headline ``value`` is the disaggregated decode inter-token p99
(wall/new-token of a short session under load); ``vs_baseline`` is
its ratio to the colocated p99 — the acceptance shape is <= 0.7 at
equal replica count. The payload also carries the handoff cost micro
(export + pickle + import vs one prefill at the flagship hd=128
shape; acceptance <= 0.15) and the int8-vs-bf16 blob bytes ratio
(acceptance <= 0.55). Emitted metric: ``serve_disagg_p99``.

    python bench_serve.py --disagg 1:1      # 2 chips vs 2 chips

STREAMING MODE (``--streaming``, docs/serving.md §streaming): the
PR-17 A/B pair on one in-process decode replica — streamed frames vs
one-shot (acceptance: streamed TTFT p50 <= 0.25x one-shot total at
max_new >= 32) and chunked vs monolithic prefill under long-prompt
load (acceptance: chunked inter-token p99 <= 0.5x unchunked at equal
replica count). Every sweep row in every mode also now reports
``ttft_ms``/``inter_token_ms`` quantiles: streaming callables feed
real per-emission marks, one-shot callables degenerate to TTFT ==
request latency with null inter-token. Emitted metric:
``serve_streaming_ttft``.

    python bench_serve.py --streaming

SPECULATIVE MODE (``--speculative``, docs/serving.md §speculative):
plain vs draft/verify continuous batching on ONE doctored target
(post-layer0 residual branches downscaled so the 1-layer truncated
draft tracks it — the high-acceptance regime). The headline ``value``
is the speculative per-session effective inter-token latency p99
((wall first->last token) / (tokens-1), p99 across sessions);
``vs_baseline`` is its ratio to the plain-decode p99 — acceptance is
< 1.0 AND ``tokens_per_target_forward`` > 1.5 at gamma=4. Output is
asserted byte-identical between the phases (exactness is the
contract, not an aspiration). Emitted metric: ``serve_spec_decode``.

    python bench_serve.py --speculative
"""
import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("MXNET_MATMUL_PRECISION", "default")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def _build_predictor(feat, hidden, classes, seed=7):
    import mxnet_tpu as mx
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.predictor import Predictor

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=hidden)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=classes)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(1, feat))
    init = Xavier()
    args = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        arr = mx.nd.zeros(shp)
        init(name, arr)
        args[name] = arr
    return Predictor(net, args)


class _TimedModel:
    """Forward wrapper adding a fixed sleep per forward, so the CPU
    replica fleet forms queues (the sleep releases the GIL the way a
    device dispatch would). Replica processes exist only on the CPU
    (bench_common.require_cpu_fleet), so this never stands beside a
    device forward."""

    def __init__(self, pred, work_ms):
        self._pred = pred
        self._work_s = float(work_ms) / 1000.0

    def forward(self, *arrays):
        outs = self._pred.forward(*arrays)
        if self._work_s > 0:
            time.sleep(self._work_s)
        return outs


def _replica_child(args):
    """``--serve-replica`` subprocess body: one engine + ServeServer,
    port announced as one JSON line on stdout, serving until stdin
    closes (the parent's exit — however it exits — is the shutdown
    signal; no orphaned replicas)."""
    from mxnet_tpu.serve import ServeEngine, ServeServer

    pred = _build_predictor(args.features, args.hidden, args.classes)
    model = _TimedModel(pred, args.work_ms) if args.work_ms else pred
    buckets = tuple(int(b) for b in
                    args.buckets.replace(",", " ").split()) \
        if args.buckets else (1, 2, 4)
    eng = ServeEngine(model, buckets=buckets,
                      max_wait_ms=(0.5 if args.wait_ms is None
                                   else args.wait_ms),
                      queue_cap=512, feature_shapes=[(args.features,)],
                      install_sigterm=True)
    srv = ServeServer(eng)
    print(json.dumps({"port": srv.port, "host": srv.host}), flush=True)
    try:
        while sys.stdin.readline():       # parent holds the pipe open
            pass
    finally:
        srv.close()
        eng.close()
    return 0


def _spawn_fleet(args, n):
    """N replica subprocesses; returns (procs, [(host, port)])."""
    import subprocess
    from bench_common import require_cpu_fleet
    require_cpu_fleet("bench_serve.py fleet modes")
    cmd = [sys.executable, os.path.abspath(__file__),
           "--serve-replica",
           "--features", str(args.features),
           "--hidden", str(args.hidden),
           "--classes", str(args.classes),
           "--work-ms", str(args.work_ms)]
    if args.buckets:
        cmd += ["--buckets", args.buckets]
    if args.wait_ms is not None:
        cmd += ["--wait-ms", str(args.wait_ms)]
    procs, addrs = [], []
    for _ in range(n):
        procs.append(subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True))
    import select
    deadline = time.monotonic() + 180.0   # package import is the cost
    for p in procs:
        # bounded read: a child hung in startup must fail the bench
        # (fail_payload path), not wedge it on a blocking readline
        remain = deadline - time.monotonic()
        if remain <= 0 or not select.select([p.stdout], [], [],
                                            remain)[0]:
            raise RuntimeError(
                "replica fleet startup timed out (child rc=%s)"
                % p.poll())
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(
                "replica subprocess died before announcing its port "
                "(rc=%s)" % p.poll())
        rec = json.loads(line)
        addrs.append((rec["host"], rec["port"]))
    return procs, addrs


def _kill_fleet(procs):
    for p in procs:
        try:
            p.stdin.close()               # EOF = drain + exit
        except OSError:
            pass
    for p in procs:
        try:
            p.wait(10.0)
        except Exception:  # noqa: BLE001 — escalate to kill
            p.kill()



def _lm_params(args):
    """Deterministic transformer-LM params every generator replica
    shares (same seed in every process — the prefill replica's
    exported rows must be THIS model's rows on the decode replica
    too, or the handoff would decode garbage)."""
    import mxnet_tpu as mx
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step

    sym = transformer.get_symbol(
        args.lm_vocab, 12, num_layers=args.lm_layers,
        num_heads=args.lm_heads, dim=args.lm_dim,
        max_len=args.lm_max_len)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    state = step.init_state(Xavier(), {"data": (2, 12),
                                       "softmax_label": (2, 12)})
    return state[0]


def _lm_generator(args, batch_size):
    from mxnet_tpu.generation import Generator
    return Generator(_lm_params(args), args.lm_vocab, args.lm_max_len,
                     num_layers=args.lm_layers,
                     num_heads=args.lm_heads, dim=args.lm_dim,
                     batch_size=batch_size)


def _gen_replica_child(args):
    """``--serve-replica --role prefill|decode`` subprocess body: one
    Generator-backed engine + ServeServer (same announce/stdin-EOF
    lifecycle as the predictor replicas)."""
    from mxnet_tpu.serve import (ContinuousDecoder, PrefillEngine,
                                 ServeServer)

    if args.role == "prefill":
        eng = PrefillEngine(_lm_generator(args, 1))
    else:
        eng = ContinuousDecoder(_lm_generator(args, args.slots),
                                queue_cap=512)
    srv = ServeServer(eng)
    print(json.dumps({"port": srv.port, "host": srv.host}), flush=True)
    try:
        while sys.stdin.readline():
            pass
    finally:
        srv.close()
        eng.close(timeout=30.0)
    return 0


def _spawn_gen_fleet(args, roles):
    """One generator replica subprocess per role; returns
    (procs, [(host, port)])."""
    import select
    import subprocess
    from bench_common import require_cpu_fleet
    require_cpu_fleet("bench_serve.py --disagg")
    procs = []
    for role in roles:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--serve-replica", "--role", role,
               "--slots", str(args.slots),
               "--lm-vocab", str(args.lm_vocab),
               "--lm-dim", str(args.lm_dim),
               "--lm-layers", str(args.lm_layers),
               "--lm-heads", str(args.lm_heads),
               "--lm-max-len", str(args.lm_max_len)]
        procs.append(subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True))
    addrs = []
    deadline = time.monotonic() + 300.0   # package import is the cost
    for p in procs:
        remain = deadline - time.monotonic()
        if remain <= 0 or not select.select([p.stdout], [], [],
                                            remain)[0]:
            raise RuntimeError(
                "generator fleet startup timed out (child rc=%s)"
                % p.poll())
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(
                "generator replica died before announcing its port "
                "(rc=%s)" % p.poll())
        rec = json.loads(line)
        addrs.append((rec["host"], rec["port"]))
    return procs, addrs


def _replica_engine_stats(addrs):
    """Raw per-replica engine stats straight off the wire (the
    router's cached extract drops the decode-specific fields the
    disagg assertions need: prefills, imported)."""
    from mxnet_tpu.serve import ServeClient
    out = []
    for host, port in addrs:
        with ServeClient(host, port) as c:
            out.append(c.stats().get("engine") or {})
    return out


def _run_disagg_config(args, roles, label):
    """One side of the A/B: spawn the fleet, run short-prompt decode
    sessions (measured) under concurrent long-prompt generate load,
    return the row."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve import ServeRouter

    rng = np.random.RandomState(0)
    short = rng.randint(1, args.lm_vocab, (args.short_prompt,))
    long_p = rng.randint(1, args.lm_vocab, (args.long_prompt,))
    procs, addrs = _spawn_gen_fleet(args, roles)
    router = None
    try:
        router = ServeRouter(
            replicas=addrs,
            conns_per_replica=args.sessions + args.load_clients + 2)
        # warm both graph shapes on EVERY replica before measuring
        # (cold XLA compiles are a one-time cost, not the steady
        # state this A/B is about) — per-replica direct clients, not
        # the router, whose placement would collapse sequential warm
        # sessions onto the first replica and leave the rest cold
        from mxnet_tpu.serve import ServeClient
        handoffs = []
        for (host, port), role in zip(addrs, roles):
            if role != "prefill":
                continue
            with ServeClient(host, port) as c:
                handoffs = [c.prefill(long_p), c.prefill(short)]
        for (host, port), role in zip(addrs, roles):
            if role == "prefill":
                continue
            with ServeClient(host, port) as c:
                if handoffs:              # disagg: warm the import
                    # scatter shapes, not the local prefill graphs
                    c.generate(long_p, 2, handoff=handoffs[0])
                    c.generate(short, args.max_new,
                               handoff=handoffs[1])
                else:                     # colocated: local prefills
                    c.generate(long_p, 2)
                    c.generate(short, args.max_new)
        stop = threading.Event()
        load_done = [0] * args.load_clients

        def load_client(ci):
            while not stop.is_set():
                try:
                    router.generate(long_p, 2,
                                    session="load%d" % ci)
                    load_done[ci] += 1
                except Exception:  # noqa: BLE001 — shed under burst
                    time.sleep(0.005)

        lat = [[] for _ in range(args.sessions)]
        dec_errs = [0] * args.sessions

        def decode_client(ci):
            for _ in range(args.requests):
                t0 = telemetry.now_ms()
                try:
                    router.generate(short, args.max_new,
                                    session="sess%d" % ci)
                except Exception:  # noqa: BLE001 — shed/timeout
                    dec_errs[ci] += 1  # counts; the row reports them
                    continue
                lat[ci].append(
                    (telemetry.now_ms() - t0) / args.max_new)

        loaders = [threading.Thread(target=load_client, args=(i,))
                   for i in range(args.load_clients)]
        clients = [threading.Thread(target=decode_client, args=(i,))
                   for i in range(args.sessions)]
        for t in loaders:
            t.start()
        time.sleep(0.2)                   # load reaches steady state
        t0 = time.perf_counter()
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        wall = time.perf_counter() - t0
        stop.set()
        for t in loaders:
            t.join()
        flat = sorted(v for row in lat for v in row)
        eng_stats = _replica_engine_stats(addrs)
    finally:
        if router is not None:
            router.close()
        _kill_fleet(procs)
    if not flat:
        # every measured request failed: that is a BENCH failure (the
        # fail_payload diagnostic path), never a success-shaped
        # payload with a null p99
        raise RuntimeError(
            "disagg %s config: all %d decode requests errored "
            "(per-session errors %r)"
            % (label, args.sessions * args.requests, dec_errs))
    decode_stats = [s for s in eng_stats if "imported" in s]
    return {
        "config": label,
        "replicas": len(roles),
        "roles": list(roles),
        "decode_requests": len(flat),
        "decode_errors": sum(dec_errs),
        "long_generates": sum(load_done),
        "wall_s": round(wall, 3),
        "inter_token_ms": {
            "p50": round(telemetry.quantile(flat, 0.50), 3),
            "p95": round(telemetry.quantile(flat, 0.95), 3),
            "p99": round(telemetry.quantile(flat, 0.99), 3),
            "mean": round(sum(flat) / len(flat), 3),
        } if flat else None,
        # the disagg invariant, read off the live replicas: imported
        # admissions ran zero prefill graph calls decode-side
        "decode_replica_prefills": sum(
            s.get("prefills") or 0 for s in decode_stats),
        "decode_replica_imports": sum(
            s.get("imported") or 0 for s in decode_stats),
    }


def _handoff_micro(args):
    """Flagship-shape (hd=128) in-process handoff cost: export +
    pickle round trip + import scatter vs one prefill forward, plus
    the int8-vs-bf16 blob bytes ratio. No wire — the wire's cost is
    the pickle bytes, which the A/B fleet pays for real."""
    import pickle

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.generation import Generator, kv_blob_nbytes
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step

    V, L_, heads, dim = 64, 2, 2, 256            # head_dim 128
    P = int(os.environ.get("BENCH_DISAGG_FLAGSHIP_PROMPT", "384"))
    T_ = P + 128
    sym = transformer.get_symbol(V, 12, num_layers=L_,
                                 num_heads=heads, dim=dim,
                                 max_len=T_)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    params = step.init_state(Xavier(), {"data": (2, 12),
                                        "softmax_label": (2, 12)})[0]

    def mk(**kw):
        return Generator(params, V, T_, num_layers=L_,
                         num_heads=heads, dim=dim, batch_size=1, **kw)

    gen = mk()
    prompt = np.arange(1, P + 1).reshape(1, -1).astype(np.float32)

    def prefill_once():
        logits, aux = gen._forward(gen._fresh_aux(), prompt, 0)
        np.asarray(logits[:, -1])         # host sync, like serving
        return aux

    def med(fn, reps):
        """Median single-iteration wall — GC/scheduler spikes must
        not decide a ratio criterion."""
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append((time.perf_counter() - t0) * 1000.0)
        return sorted(times)[len(times) // 2]

    aux = prefill_once()                  # compile
    prefill_ms = med(prefill_once, 9)

    dec = mk().serving_decoder()
    wire = [None]
    try:
        blob = gen.export_kv_rows(aux, 0, P)
        dec.import_kv_rows(0, pickle.loads(pickle.dumps(blob)))
        jax.block_until_ready(list(dec._aux.values()))   # compile

        def handoff_once():
            blob = gen.export_kv_rows(aux, 0, P)
            wire[0] = pickle.dumps(blob, protocol=4)
            dec.import_kv_rows(0, pickle.loads(wire[0]))
            jax.block_until_ready(list(dec._aux.values()))
        handoff_ms = med(handoff_once, 21)
    finally:
        dec.close(timeout=10.0)

    # bytes ratio at the same shape/position: int8 rows + f32
    # per-token scales vs bf16 rows (shape math through the real
    # export path — a fresh aux has the real dtypes/shapes)
    g16, gq8 = mk(dtype="bfloat16"), mk(quantize_kv=True)
    bytes_bf16 = kv_blob_nbytes(
        g16.export_kv_rows(g16._fresh_aux(), 0, P))
    bytes_int8 = kv_blob_nbytes(
        gq8.export_kv_rows(gq8._fresh_aux(), 0, P))
    return {
        "shape": {"head_dim": dim // heads, "layers": L_,
                  "prompt": P},
        "prefill_ms": round(prefill_ms, 3),
        "handoff_ms": round(handoff_ms, 3),
        "handoff_frac": round(handoff_ms / prefill_ms, 4)
        if prefill_ms else None,
        "blob_bytes_bf16": bytes_bf16,
        "blob_bytes_int8": bytes_int8,
        "bytes_ratio_int8_vs_bf16": round(bytes_int8 / bytes_bf16, 4),
        "wire_bytes_f32": len(wire[0]),
    }


def _run_disagg(args):
    """The --disagg P:D A/B: disaggregated fleet vs colocated fleet
    at equal replica count, plus the flagship-shape handoff micro."""
    try:
        n_pre, n_dec = (int(x) for x in args.disagg.split(":"))
    except ValueError:
        raise SystemExit("--disagg wants P:D (e.g. 1:1), got %r"
                         % args.disagg)
    if n_pre < 1 or n_dec < 1:
        raise SystemExit("--disagg wants at least one prefill and one "
                         "decode replica, got %r" % args.disagg)
    disagg = _run_disagg_config(
        args, ["prefill"] * n_pre + ["decode"] * n_dec, "disagg")
    coloc = _run_disagg_config(
        args, ["decode"] * (n_pre + n_dec), "colocated")
    return disagg, coloc, _handoff_micro(args)


def _closed_loop(one_round_trip, conc, requests):
    """THE closed-loop measurement harness both sweep modes share:
    conc client threads x requests round trips of ``one_round_trip()``,
    returning the common row fields (throughput, latency quantiles,
    error count). Callers fold in their mode-specific extras.

    TTFT and inter-token quantiles ride every row: a round trip that
    returns a list of per-emission ``now_ms()`` marks (the streaming
    callables do) yields true time-to-first-token and gap quantiles;
    any other return (infer replies, one-shot rows) is a single-shot
    round trip whose first byte IS the whole reply — TTFT equals the
    request latency and inter-token is null."""
    from mxnet_tpu import telemetry

    lat = [[] for _ in range(conc)]
    ttft = [[] for _ in range(conc)]
    gaps = [[] for _ in range(conc)]
    errs = [0] * conc

    def client(ci):
        for _ in range(requests):
            t0 = telemetry.now_ms()
            try:
                marks = one_round_trip()
            except Exception:  # noqa: BLE001 — shed/timeout counts,
                errs[ci] += 1  # the row reports them
                continue
            t1 = telemetry.now_ms()
            lat[ci].append(t1 - t0)
            if isinstance(marks, list) and marks and \
                    all(type(m) is float for m in marks):
                ttft[ci].append(marks[0] - t0)
                gaps[ci].extend(b - a for a, b in
                                zip(marks, marks[1:]))
            else:
                # infer replies are LISTS of output arrays — only a
                # list of now_ms() floats is an emission-mark trail
                ttft[ci].append(t1 - t0)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(conc)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = sorted(v for row in lat for v in row)
    tflat = sorted(v for row in ttft for v in row)
    gflat = sorted(v for row in gaps for v in row)
    done = len(flat)

    def _q(vals):
        return {"p50": round(telemetry.quantile(vals, 0.50), 3),
                "p99": round(telemetry.quantile(vals, 0.99), 3)}

    return {
        "concurrency": conc,
        "requests": done,
        "errors": sum(errs),
        "throughput_rps": round(done / wall, 2) if wall else None,
        "latency_ms": {
            "p50": round(telemetry.quantile(flat, 0.50), 3),
            "p95": round(telemetry.quantile(flat, 0.95), 3),
            "p99": round(telemetry.quantile(flat, 0.99), 3),
            "mean": round(sum(flat) / done, 3),
        } if done else None,
        "ttft_ms": _q(tflat) if tflat else None,
        "inter_token_ms": _q(gflat) if gflat else None,
    }


def _run_fleet_level(router, names, feat, conc, requests):
    """One closed-loop level against the (persistent) fleet: conc
    clients x requests round trips through the router. Per-replica
    fill comes from dispatch-count deltas."""
    before = {n: r["dispatched"]
              for n, r in router.replicas().items()}
    x = np.random.RandomState(0).standard_normal(
        (1, feat)).astype(np.float32)
    row = _closed_loop(lambda: router.request([x]), conc, requests)
    after = router.replicas()
    row["per_replica_fill"] = {
        n: after[n]["dispatched"] - before.get(n, 0) for n in names}
    return row


def _run_fleet(args, levels):
    """The --replicas N sweep: router + N subprocess replicas, one
    JSON line out (metric serve_fleet_throughput)."""
    from mxnet_tpu.serve import ServeRouter

    procs, addrs = _spawn_fleet(args, args.replicas)
    router = None
    try:
        # pool enough connections for the deepest sweep level — a
        # closed-loop client holds one for its whole round trip, and
        # re-dialing per request would measure TCP setup, not serving
        conns = max(int(c) for c in
                    args.concurrency.replace(",", " ").split())
        router = ServeRouter(replicas=addrs, conns_per_replica=conns)
        names = list(router.replicas())
        router.warmup()                   # no cold compiles in level 1
        sweep = [_run_fleet_level(router, names, args.features, c,
                                  args.requests) for c in levels]
        fleet_stats = router.stats()
    finally:
        if router is not None:
            router.close()
        _kill_fleet(procs)
    return sweep, fleet_stats


def _run_controller(args, conc):
    """The --controller load-doubling autoscale bench
    (docs/serving.md §fleet controller): a 2-replica subprocess fleet
    under a baseline closed-loop load, then DOUBLED clients — the
    FleetController's background ticks must scale out mid-window on
    the sustained queue-depth signal — then the same doubled load
    against the grown fleet. Acceptance: at least one scale-out, zero
    request errors in every window (nothing dropped while capacity
    changed under load), and the tail recovered — window-3 p99 below
    the pressure window's."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve import FleetController, ServeRouter

    procs, addrs = _spawn_fleet(args, 2)
    by_addr = {"%s:%d" % a: p for p, a in zip(procs, addrs)}
    router, ctrl = None, None

    def spawn(manifest=None):
        new_procs, new_addrs = _spawn_fleet(args, 1)
        procs.extend(new_procs)
        by_addr["%s:%d" % new_addrs[0]] = new_procs[0]
        return new_addrs[0]

    def retire(name, addr):
        proc = by_addr.pop(addr, None)
        if proc is not None:
            try:
                proc.stdin.close()        # EOF = drain + exit
            except OSError:
                pass

    x = np.random.RandomState(0).standard_normal(
        (1, args.features)).astype(np.float32)
    try:
        router = ServeRouter(replicas=addrs,
                             conns_per_replica=2 * conc + 2)
        router.warmup()                   # no cold compiles in window 1
        # sustain 5 ticks @100ms: the doubled load must hold the
        # depth signal for half a second before capacity moves — the
        # inter-window idle gap is far shorter, so the controller
        # never flaps between measurement windows. The depth band
        # (in 1.0 / out 5.0) sits between the baseline's steady
        # per-replica queue (~conc/replicas - 1 in service) and the
        # doubled load's, so only window 2 crosses it.
        ctrl = FleetController(router, spawn, retire=retire,
                               min_replicas=2, max_replicas=4,
                               scale_out_depth=5.0,
                               scale_in_depth=1.0,
                               sustain=5, poll_ms=100.0)

        def rt():
            return router.request([x])
        baseline = _closed_loop(rt, conc, args.requests)
        replicas_base = len(router.replicas())
        pressure = _closed_loop(rt, 2 * conc, args.requests)
        replicas_pressure = len(router.replicas())
        recovered = _closed_loop(rt, 2 * conc, args.requests)
        scale_outs = int(telemetry.counter(
            "serve.ctrl.scale_outs").value)
        fleet = router.stats()
    finally:
        if ctrl is not None:
            ctrl.close()
        if router is not None:
            router.close()
        _kill_fleet(procs)
    errors = (baseline["errors"] + pressure["errors"]
              + recovered["errors"])
    p99_p = (pressure["latency_ms"] or {}).get("p99")
    p99_r = (recovered["latency_ms"] or {}).get("p99")
    return {
        "baseline": baseline,
        "pressure": pressure,
        "recovered": recovered,
        "replicas_baseline": replicas_base,
        "replicas_pressure": replicas_pressure,
        "replicas_final": fleet.get("replicas"),
        "scale_outs": scale_outs,
        "errors": errors,
        "p99_recovery_ratio": round(p99_r / p99_p, 4)
        if p99_r and p99_p else None,
        "ok": bool(scale_outs >= 1 and errors == 0
                   and p99_r is not None and p99_p is not None
                   and p99_r < p99_p),
    }


def _run_level(pred, feat, buckets, wait_ms, conc, requests):
    """One closed-loop level: conc clients x requests round trips
    against a FRESH engine (clean per-level stats). Returns the sweep
    row."""
    from mxnet_tpu.serve import ServeEngine

    eng = ServeEngine(pred, buckets=buckets, max_wait_ms=wait_ms,
                      feature_shapes=[(feat,)],
                      install_sigterm=False)
    eng.warmup()
    x = np.random.RandomState(0).standard_normal(
        (1, feat)).astype(np.float32)
    row = _closed_loop(lambda: eng.infer(x, timeout=60.0), conc,
                       requests)
    eng.close()
    st = eng.stats()
    row["forwards"] = st["forwards"]
    row["mean_batch_fill"] = round(st["mean_fill"], 3) \
        if st["mean_fill"] else None
    return row


def _run_streaming(args):
    """The --streaming A/B pair (docs/serving.md §streaming), one
    in-process transformer decode replica behind real TCP each side:

    * streamed vs one-shot — the SAME short-prompt generate with and
      without frames; the acceptance shape is streamed TTFT p50 <=
      0.25x the one-shot total latency p50 at max_new >= 32 (the
      whole point of frames: the first token stops waiting for the
      last);
    * chunked vs monolithic prefill — short streamed sessions
      measured for inter-token gaps while a loader injects
      long-prompt generates; the acceptance shape is chunked
      inter-token p99 <= 0.5x unchunked at equal replica count (a
      monolithic long prefill stalls every active session for its
      whole forward, a chunk stalls them for one slice).

    Every graph width is warmed before measuring in each config —
    cold XLA compiles are a one-time cost, not the steady state."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve import ContinuousDecoder, ServeClient, \
        ServeServer

    rng = np.random.RandomState(0)
    short = rng.randint(1, args.lm_vocab, (args.short_prompt,))
    long_p = rng.randint(1, args.lm_vocab, (args.long_prompt,))
    max_new = max(int(args.max_new), 32)
    reps = max(8, min(args.requests, 40))

    def _q(vals):
        vals = sorted(vals)
        return {"p50": round(telemetry.quantile(vals, 0.50), 3),
                "p99": round(telemetry.quantile(vals, 0.99), 3)}

    # -- A/B 1: streamed TTFT vs one-shot total latency ------------
    dec = ContinuousDecoder(_lm_generator(args, args.slots),
                            queue_cap=512)
    srv = ServeServer(dec)
    try:
        with ServeClient(srv.host, srv.port) as cli:
            cli.generate(short, max_new)                      # warm
            cli.generate(short, max_new, on_token=lambda t: None)
            oneshot, ttfts, sgaps = [], [], []
            for _ in range(reps):
                t0 = telemetry.now_ms()
                cli.generate(short, max_new)
                oneshot.append(telemetry.now_ms() - t0)
            for _ in range(reps):
                marks = []
                t0 = telemetry.now_ms()
                cli.generate(short, max_new, on_token=lambda t:
                             marks.append(telemetry.now_ms()))
                ttfts.append(marks[0] - t0)
                sgaps.extend(b - a for a, b in
                             zip(marks, marks[1:]))
    finally:
        srv.close()
        dec.close()

    # -- A/B 2: chunked vs monolithic prefill under long load ------
    def config(chunk):
        os.environ["MXNET_PREFILL_CHUNK"] = str(chunk)
        d = ContinuousDecoder(_lm_generator(args, args.slots),
                              queue_cap=512)
        s = ServeServer(d)
        gaps = []
        try:
            with ServeClient(s.host, s.port) as cli, \
                    ServeClient(s.host, s.port) as loader:
                cli.generate(short, max_new)              # warm the
                loader.generate(long_p, 2)    # short, long (chunked
                stop = threading.Event()      # or monolithic) + step

                def load():
                    while not stop.is_set():
                        try:
                            loader.generate(long_p, 2)
                        except Exception:  # noqa: BLE001 — shed
                            time.sleep(0.005)

                lt = threading.Thread(target=load)
                lt.start()
                time.sleep(0.1)           # load reaches steady state
                try:
                    for _ in range(reps):
                        marks = []
                        cli.generate(short, max_new, on_token=lambda
                                     t: marks.append(
                                         telemetry.now_ms()))
                        gaps.extend(b - a for a, b in
                                    zip(marks, marks[1:]))
                finally:
                    stop.set()
                    lt.join()
        finally:
            s.close()
            d.close()
            os.environ.pop("MXNET_PREFILL_CHUNK", None)
        return gaps

    chunked = sorted(config(args.prefill_chunk))
    mono = sorted(config(0))
    oneshot, ttfts = sorted(oneshot), sorted(ttfts)
    return {
        "max_new": max_new,
        "requests": reps,
        "oneshot_total_ms": _q(oneshot),
        "streamed_ttft_ms": _q(ttfts),
        "streamed_inter_token_ms": _q(sgaps),
        # acceptance: <= 0.25 at max_new >= 32
        "ttft_vs_oneshot": round(
            telemetry.quantile(ttfts, 0.5)
            / telemetry.quantile(oneshot, 0.5), 4),
        "chunk": args.prefill_chunk,
        "long_prompt": int(args.long_prompt),
        "chunked_inter_token_ms": _q(chunked),
        "unchunked_inter_token_ms": _q(mono),
        # acceptance: <= 0.5 at equal replica count
        "chunked_p99_ratio": round(
            telemetry.quantile(chunked, 0.99)
            / telemetry.quantile(mono, 0.99), 4),
    }


def _doctored_lm_params(args, scale=1e-2):
    """Target params whose post-layer0 residual branches are
    downscaled so a 1-layer truncated draft tracks the full target
    closely — the high-acceptance regime speculative decoding is
    built for, made reproducible on random weights (with every
    ``layer<k>_`` tensor for k >= 1 scaled to ~0 the pre-norm
    residual blocks contribute ~nothing, so the deep target computes
    ~its own first layer). The SAME doctored target runs on BOTH
    sides of the A/B — the comparison is spec-vs-plain decoding of
    one model, not shallow-vs-deep models."""
    params = dict(_lm_params(args))
    deep = tuple("layer%d_" % k for k in range(1, args.lm_layers))
    for name in list(params):
        if name.startswith(deep):
            params[name] = params[name] * scale
    return params


def _run_speculative(args):
    """The --speculative A/B (docs/serving.md §speculative): plain
    vs draft/verify continuous batching on one in-process decode
    replica behind real TCP, same doctored target both phases.

    Measured shape: `reps` sequential streamed short-prompt sessions
    per phase; the per-session effective inter-token latency is
    (wall first token -> last token) / (tokens - 1) — the fair
    metric, because a spec round emits its accepted tokens in a
    burst (per-gap quantiles reward the in-burst ~0ms gaps and
    punish the round boundary; the session mean is what a caller
    experiences). Acceptance: spec p99 / plain p99 < 1.0 AND
    tokens-per-target-forward > 1.5 at gamma=4 — both only hold
    when acceptance is high, which the doctored tail provides."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.serve import ContinuousDecoder, ServeClient, \
        ServeServer

    rng = np.random.RandomState(0)
    short = rng.randint(1, args.lm_vocab, (args.short_prompt,))
    max_new = max(int(args.max_new), 32)
    reps = max(8, min(args.requests, 40))
    params = _doctored_lm_params(args)

    def _q(vals):
        vals = sorted(vals)
        return {"p50": round(telemetry.quantile(vals, 0.50), 3),
                "p99": round(telemetry.quantile(vals, 0.99), 3)}

    def phase(speculative):
        gen = Generator(params, args.lm_vocab, args.lm_max_len,
                        num_layers=args.lm_layers,
                        num_heads=args.lm_heads, dim=args.lm_dim,
                        batch_size=args.slots)
        draft = gen.truncated_draft(num_layers=args.draft_layers) \
            if speculative else None
        dec = ContinuousDecoder(gen, queue_cap=512, draft=draft,
                                lookahead=args.gamma)
        srv = ServeServer(dec)
        eff, gaps, toks = [], [], None
        try:
            with ServeClient(srv.host, srv.port) as cli:
                # warm BOTH target shapes before measuring: the
                # (B, 1) step and, in the spec phase, the
                # (B, gamma+1) verify + the draft pair
                cli.generate(short, max_new)
                if speculative:
                    cli.generate(short, max_new, speculative=True)
                s0 = dec.stats()
                for _ in range(reps):
                    marks = []
                    out = cli.generate(
                        short, max_new, speculative=speculative,
                        on_token=lambda t:
                        marks.append(telemetry.now_ms()))
                    if toks is None:
                        toks = [int(t) for t in out]
                    if len(marks) >= 2:
                        eff.append((marks[-1] - marks[0])
                                   / (len(marks) - 1))
                        gaps.extend(b - a for a, b in
                                    zip(marks, marks[1:]))
                s1 = dec.stats()
        finally:
            srv.close()
            dec.close()
        delta = {k: s1[k] - s0[k] for k in s1
                 if isinstance(s1[k], (int, float))
                 and isinstance(s0.get(k), (int, float))}
        return eff, gaps, delta, toks

    plain_eff, plain_gaps, plain_delta, plain_toks = phase(False)
    spec_eff, spec_gaps, spec_delta, spec_toks = phase(True)
    if spec_toks != plain_toks:
        # speculative decoding is exact BY CONSTRUCTION (shared-noise
        # verification, docs/serving.md §speculative) — a mismatch
        # here is a correctness bug, not a benchmark artifact
        raise RuntimeError(
            "speculative output diverged from plain decode: %r vs %r"
            % (spec_toks, plain_toks))
    plain_p99 = telemetry.quantile(sorted(plain_eff), 0.99)
    spec_p99 = telemetry.quantile(sorted(spec_eff), 0.99)
    # during the measured spec window every forward is a verify (the
    # sole client sends only speculative requests), so the target-
    # forward count is the steps delta
    tpf = round((reps * max_new) / spec_delta["steps"], 3) \
        if spec_delta.get("steps") else None
    acc = round(spec_delta["spec_accepted"]
                / spec_delta["spec_proposed"], 4) \
        if spec_delta.get("spec_proposed") else None
    return {
        "gamma": int(args.gamma),
        "draft_layers": int(args.draft_layers),
        "target_layers": int(args.lm_layers),
        "max_new": max_new,
        "requests": reps,
        "plain_inter_token_eff_ms": _q(plain_eff),
        "spec_inter_token_eff_ms": _q(spec_eff),
        # acceptance: < 1.0 (per-session effective latency, p99
        # across sessions)
        "inter_token_eff_p99_ratio": round(spec_p99 / plain_p99, 4),
        # acceptance: > 1.5 at gamma=4
        "tokens_per_target_forward": tpf,
        "accept_rate_mean": acc,
        "plain_inter_token_gap_ms": _q(plain_gaps),
        "spec_inter_token_gap_ms": _q(spec_gaps),
        "plain_stats": plain_delta,
        "spec_stats": spec_delta,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--concurrency", default=None,
                   help="comma-separated closed-loop client counts "
                        "(default 1,2,4,8,16; fleet mode 4,8,16,32 — "
                        "past-saturation levels where replica count, "
                        "not the coalesce window, is the capacity "
                        "knob)")
    p.add_argument("--requests", type=int,
                   default=int(os.environ.get("BENCH_SERVE_REQUESTS",
                                              "100")),
                   help="round trips per client per level")
    p.add_argument("--buckets", default=None,
                   help="engine buckets (default MXNET_SERVE_BUCKETS)")
    p.add_argument("--wait-ms", type=float, default=None,
                   help="coalesce window (default "
                        "MXNET_SERVE_MAX_WAIT_MS)")
    p.add_argument("--features", type=int, default=64)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--classes", type=int, default=16)
    p.add_argument("--replicas", type=int, default=0,
                   help="fleet mode: router + this many subprocess "
                        "replicas (0 = classic in-process engine "
                        "sweep)")
    p.add_argument("--work-ms", type=float, default=None,
                   help="fixed per-forward sleep in each CPU replica "
                        "process (fleet default 5.0; 0 = raw XLA-CPU "
                        "forwards)")
    p.add_argument("--disagg", default=None, metavar="P:D",
                   help="prefill/decode disaggregation A/B: P "
                        "prefill + D decode generator replicas vs "
                        "P+D colocated ones at equal chip count "
                        "(docs/serving.md §disaggregated prefill)")
    p.add_argument("--sessions", type=int,
                   default=int(os.environ.get("BENCH_DISAGG_SESSIONS",
                                              "4")),
                   help="disagg mode: measured short-prompt decode "
                        "session threads")
    p.add_argument("--load-clients", type=int,
                   default=int(os.environ.get("BENCH_DISAGG_LOAD",
                                              "2")),
                   help="disagg mode: concurrent long-prompt "
                        "generate load threads")
    p.add_argument("--controller", action="store_true",
                   help="load-doubling autoscale bench: 2 subprocess "
                        "replicas under a FleetController, baseline "
                        "load then doubled clients (the controller "
                        "must scale out mid-window) then the doubled "
                        "load against the grown fleet (docs/"
                        "serving.md §fleet controller); acceptance "
                        "is >= 1 scale-out, zero errors, recovered "
                        "p99 < pressure p99")
    p.add_argument("--streaming", action="store_true",
                   help="streaming A/B pair: streamed-vs-one-shot "
                        "TTFT and chunked-vs-monolithic prefill "
                        "inter-token p99 (docs/serving.md "
                        "§streaming)")
    p.add_argument("--speculative", action="store_true",
                   help="speculative-decoding A/B: plain vs "
                        "draft/verify continuous batching on the "
                        "same doctored target (docs/serving.md "
                        "§speculative); acceptance is effective "
                        "inter-token p99 ratio < 1.0 and tokens per "
                        "target forward > 1.5 at gamma=4")
    p.add_argument("--gamma", type=int, default=4,
                   help="speculative mode: draft lookahead per round")
    p.add_argument("--draft-layers", type=int, default=1,
                   help="speculative mode: truncated-draft depth")
    p.add_argument("--prefill-chunk", type=int, default=16,
                   help="streaming mode: MXNET_PREFILL_CHUNK for the "
                        "chunked side of the prefill A/B")
    p.add_argument("--short-prompt", type=int, default=4)
    p.add_argument("--long-prompt", type=int, default=None,
                   help="loader prompt tokens (default 96; streaming "
                        "mode 512 — the chunked-prefill A/B needs a "
                        "prefill wall that dwarfs one-core scheduling "
                        "noise)")
    p.add_argument("--max-new", type=int, default=16,
                   help="disagg mode: tokens per measured decode "
                        "request (inter-token = wall / this)")
    p.add_argument("--slots", type=int, default=4,
                   help="decode replica slot-pool width")
    p.add_argument("--lm-vocab", type=int, default=64)
    p.add_argument("--lm-dim", type=int, default=None,
                   help="decode replica width (default 64; "
                        "speculative mode 256 — below that, per-"
                        "forward dispatch overhead hides the "
                        "draft/target compute gap on CPU)")
    p.add_argument("--lm-layers", type=int, default=None,
                   help="decode replica depth (default 2; "
                        "speculative mode 4 — the draft/target depth "
                        "gap is where the speedup lives)")
    p.add_argument("--lm-heads", type=int, default=2)
    p.add_argument("--lm-max-len", type=int, default=None,
                   help="decode cache length (default 160; streaming "
                        "mode 544 to hold the long-prompt A/B)")
    p.add_argument("--role", default=None,
                   help=argparse.SUPPRESS)   # internal: child role
    p.add_argument("--serve-replica", action="store_true",
                   help=argparse.SUPPRESS)   # internal: child mode
    args = p.parse_args(argv)
    if args.lm_layers is None:
        args.lm_layers = 4 if args.speculative else 2
    if args.lm_dim is None:
        args.lm_dim = 256 if args.speculative else 64
    if args.speculative:
        if args.draft_layers >= args.lm_layers:
            p.error("--draft-layers must be < --lm-layers (the draft "
                    "must be cheaper than the target)")
        if args.short_prompt + max(args.max_new, 32) \
                > (args.lm_max_len or 160) - args.gamma:
            p.error("--short-prompt + max_new exceeds the speculative "
                    "headroom (--lm-max-len - gamma)")
    if args.long_prompt is None:
        args.long_prompt = 512 if args.streaming else 96
    if args.lm_max_len is None:
        args.lm_max_len = 544 if args.streaming else 160
    if args.streaming and \
            args.long_prompt + max(args.max_new, 32) > args.lm_max_len:
        p.error("--long-prompt + max_new exceeds --lm-max-len")
    if args.controller and args.buckets is None:
        # the autoscale signal is QUEUE DEPTH: unit buckets keep the
        # replicas from absorbing the doubled load by coalescing
        # (which would flatten the depth signal the bench exists to
        # drive over the policy threshold)
        args.buckets = "1"
    if args.work_ms is None:
        if args.controller:
            args.work_ms = 20.0
        else:
            args.work_ms = 5.0 if (args.replicas or args.serve_replica) \
                else 0.0

    if args.disagg:
        metric, unit = "serve_disagg_p99", "ms/token"
    elif args.speculative:
        metric, unit = "serve_spec_decode", "ms/token"
    elif args.streaming:
        metric, unit = "serve_streaming_ttft", "ms"
    elif args.controller:
        metric, unit = "serve_controller_scale", "ms"
    elif args.replicas:
        metric, unit = "serve_fleet_throughput", "req/s"
    else:
        metric, unit = "serve_throughput", "req/s"
    if args.serve_replica:
        if args.role in ("prefill", "decode"):
            return _gen_replica_child(args)
        return _replica_child(args)
    # killed mid-run -> still exactly one parseable JSON line; any
    # other failure prints the same shape and then raises
    from bench_common import fail_payload, install_death_stub
    install_death_stub(metric, unit)
    try:
        payload = _run_mode(args, metric, unit)
    except Exception as e:
        print(json.dumps(fail_payload(metric, unit, e)))
        raise
    # the device is named last, after any fleet has been refused or
    # spawned: naming it initialises this process's backend
    from bench_common import device_fields
    print(json.dumps({**payload, **device_fields()}))
    return 0


def _run_mode(args, metric, unit):
    """The selected mode's result payload."""
    if args.controller:
        conc = int(args.concurrency.replace(",", " ").split()[0]) \
            if args.concurrency else 8
        row = _run_controller(args, conc)
        return {
            "metric": metric,
            "value": (row["recovered"]["latency_ms"] or {}).get("p99"),
            "unit": unit,
            # acceptance shape: recovered p99 < pressure p99 at the
            # same doubled load (lower is better), zero errors, and
            # at least one controller scale-out mid-run
            "vs_baseline": row["p99_recovery_ratio"],
            **row}
    if args.speculative:
        row = _run_speculative(args)
        return {
            "metric": metric,
            "value": row["spec_inter_token_eff_ms"]["p99"],
            "unit": unit,
            # acceptance shape: spec effective inter-token p99 <
            # 1.0x plain on the same target (lower is better), with
            # tokens_per_target_forward > 1.5 at gamma=4
            "vs_baseline": row["inter_token_eff_p99_ratio"],
            **row}
    if args.streaming:
        row = _run_streaming(args)
        return {
            "metric": metric,
            "value": row["streamed_ttft_ms"]["p50"],
            "unit": unit,
            # acceptance shape: streamed TTFT p50 <= 0.25x the
            # one-shot total at max_new >= 32 (lower is better)
            "vs_baseline": row["ttft_vs_oneshot"],
            **row}
    if args.disagg:
        disagg, coloc, micro = _run_disagg(args)
        d_p99 = (disagg["inter_token_ms"] or {}).get("p99")
        c_p99 = (coloc["inter_token_ms"] or {}).get("p99")
        return {
            "metric": metric,
            "value": d_p99,
            "unit": unit,
            # acceptance shape: disagg p99 <= 0.7x colocated at equal
            # replica count (lower is better)
            "vs_baseline": round(d_p99 / c_p99, 4)
            if d_p99 and c_p99 else None,
            "disagg": disagg,
            "colocated": coloc,
            "handoff": micro}
    if args.concurrency is None:
        args.concurrency = "4,8,16,32" if args.replicas \
            else "1,2,4,8,16"
    levels = sorted({int(c) for c in
                     args.concurrency.replace(",", " ").split()})
    buckets = tuple(int(b) for b in
                    args.buckets.replace(",", " ").split()) \
        if args.buckets else None

    fleet_stats = None
    if args.replicas:
        sweep, fleet_stats = _run_fleet(args, levels)
    else:
        pred = _build_predictor(args.features, args.hidden,
                                args.classes)
        sweep = [_run_level(pred, args.features, buckets,
                            args.wait_ms, c, args.requests)
                 for c in levels]

    best = max(sweep, key=lambda r: r["throughput_rps"] or 0.0)
    base = next((r for r in sweep if r["concurrency"] == levels[0]),
                None) if args.replicas else \
        next((r for r in sweep if r["concurrency"] == 1), None)
    gain = (round(best["throughput_rps"] / base["throughput_rps"], 3)
            if base and base["throughput_rps"] else None)
    payload = {
        "metric": metric,
        "value": best["throughput_rps"],
        "unit": "req/s",
        "vs_baseline": gain,          # gain over the sweep's base level
        "best_concurrency": best["concurrency"],
        "best_latency_ms": best["latency_ms"],
        "sweep": sweep}
    if args.replicas:
        payload["replicas"] = args.replicas
        payload["work_ms"] = args.work_ms
        payload["per_replica_fill"] = best["per_replica_fill"]
        payload["rerouted"] = (fleet_stats or {}).get("rerouted")
    else:
        payload["best_mean_batch_fill"] = best["mean_batch_fill"]
    return payload


if __name__ == "__main__":
    sys.exit(main())
