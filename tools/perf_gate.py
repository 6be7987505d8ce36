"""Journal-backed perf-regression gate: CPU-deterministic scenarios
checked against committed baselines (docs/perf_gates.md, ROADMAP 5).

The counts earlier PRs established — PR 2's ≤1 blocking host sync per
step, PR 11's one-executable donated-buffer steps, PR 8/10's journal +
trace vocabulary — hold on any backend, and were protected only by
scattered per-PR tests. This tool turns the telemetry journal and
trace spill those PRs built into ONE enforcement surface:

* each **scenario** (TrainStep fit, Module fit, GSPMD layout step,
  PS push/pull under fault injection, ServeEngine request path,
  ContinuousDecoder) runs a short deterministic workload in a fresh
  subprocess on the CPU backend with ``MXNET_TELEMETRY`` +
  ``MXNET_TRACE`` on;
* a **gate fingerprint** is extracted from the journal + spill:
  per-step blocking-host-sync counts, compile-event counts and which
  step carries them, the jit-cache size across donated steps, the
  trace-span vocabulary/nesting shape, the journal schema version,
  key counter values (ps.retries, guardrail.masked_steps, serve.shed).
  No times: speed is measured on the chip, by ``cellbench``;
* the fingerprint is compared against the committed baseline in
  ``perf_baselines/<scenario>.json`` — EXACT match for every field;
* a failure prints which field diverged AND which PR-won property that
  field protects, so a gate failure reads as "you reintroduced a
  per-step host sync", not as a JSON diff.

    python tools/perf_gate.py                    # all scenarios
    python tools/perf_gate.py --scenario trainstep,gspmd
    python tools/perf_gate.py --bless            # regenerate baselines
    python tools/perf_gate.py --keep /tmp/gate   # keep run artifacts

``tools/perf_gate.sh`` runs this gate plus every smoke-lint and marker
test subset — the one builder entrypoint. Count/shape fields are
deterministic run-to-run (asserted in tests/test_perf_gate.py, marker
``gate``); after an INTENDED behavior change, re-bless and commit the
new baselines with the change that caused them.
"""
import argparse
import json
import os
import subprocess
import sys

_SELF = os.path.abspath(__file__)
_REPO = os.path.dirname(os.path.dirname(_SELF))
sys.path.insert(0, _REPO)

GATE_SCHEMA = 2
BASELINE_DIR = os.path.join(_REPO, "perf_baselines")


# ---------------------------------------------------------------------------
# scenario workloads (run in a fresh child process; see _child_main)
# ---------------------------------------------------------------------------
# Every workload must be CPU-deterministic: fixed seeds, fixed fault
# specs, sequential request submission where concurrency would make
# event counts racy. The fit scenarios emit a `gate.probe` journal event
# carrying the in-process measurements a journal record can't (host-sync
# deltas); everything else is read back from the journal + trace spill.

def _mlp(classes=2, hidden=32):
    import mxnet_tpu as mx
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=hidden)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=classes)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy(n=96, d=16, classes=2, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, classes, n).astype(np.float32)
    return X, y


def _sync_marks_probe(marks, steps_per_epoch, warm_epochs=1):
    """Per-step host-sync figures from cumulative counter marks taken
    at each batch end. Steady state = epochs after `warm_epochs`;
    deltas are only taken WITHIN an epoch (the epoch boundary pays the
    metric read + window drain by design)."""
    steady_deltas = []
    for e in range(warm_epochs, len(marks) // steps_per_epoch):
        base = e * steps_per_epoch
        for i in range(1, steps_per_epoch):
            steady_deltas.append(marks[base + i] - marks[base + i - 1])
    return {
        "max_step_syncs_steady": max(steady_deltas) if steady_deltas
        else None,
        "fit_total_syncs": marks[-1] - marks[0] if marks else None,
    }


def _scn_trainstep():
    """PR 2/3 surface: pipelined TrainStep.fit with the guardrail on
    and one deterministically injected NaN step (nan@6 of 12)."""
    from mxnet_tpu import io, profiler, telemetry
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.parallel.resilience import (FaultInjector,
                                               install_fault_injector)
    X, y = _toy()
    step = make_train_step(_mlp(), optimizer="sgd",
                           optimizer_params={"rescale_grad": 1.0 / 24})
    train = io.NDArrayIter(X, y, batch_size=24)     # 4 steps/epoch
    marks = []
    install_fault_injector(FaultInjector("nan@6"))  # epoch 2, step 2
    try:
        step.fit(train, num_epoch=3, initializer=Xavier(), lr=0.1,
                 seed=0, batch_end_callback=lambda _p: marks.append(
                     profiler.host_sync_count()))
    finally:
        install_fault_injector(None)
    telemetry.journal_event("gate.probe",
                            **_sync_marks_probe(marks, 4))


def _scn_module():
    """The Module fit path (executor group + device metrics)."""
    import mxnet_tpu as mx
    from mxnet_tpu import io, profiler, telemetry
    X, y = _toy()
    train = io.NDArrayIter(X, y, batch_size=24)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    marks = []
    mod.fit(train, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1,
                              "rescale_grad": 1.0 / 24},
            batch_end_callback=lambda _p: marks.append(
                profiler.host_sync_count()))
    telemetry.journal_event("gate.probe",
                            **_sync_marks_probe(marks, 4))


def _scn_gspmd():
    """PR 11 surface: one-jit GSPMD fit over the forced-8-device
    data×fsdp mesh with zero1 optimizer sharding — the jit-cache gauge
    must stay at ONE executable across donated steps."""
    from mxnet_tpu import io, profiler, telemetry
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.parallel import SpecLayout, make_mesh, make_train_step
    X, y = _toy(classes=8)
    mesh = make_mesh({"data": 2, "fsdp": 4})
    layout = SpecLayout(mesh, min_shard_size=0)
    step = make_train_step(_mlp(classes=8), layout=layout,
                           optimizer="adam", optimizer_sharding="zero1",
                           optimizer_params={"rescale_grad": 1.0 / 24})
    train = io.NDArrayIter(X, y, batch_size=24)     # 24 % 8 == 0
    marks = []
    step.fit(train, num_epoch=3, initializer=Xavier(), lr=0.05,
             seed=0, batch_end_callback=lambda _p: marks.append(
                 profiler.host_sync_count()))
    telemetry.journal_event("gate.probe",
                            **_sync_marks_probe(marks, 4))


def _scn_ps_faults():
    """PR 1 surface: async PS push/pull under a deterministic
    mid-push disconnect + dropped pull reply — exactly-once replay
    means the retry counters are exact, not flaky."""
    import threading

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.ps_async import AsyncPSClient, AsyncPSServer
    from mxnet_tpu.parallel.resilience import (FaultInjector,
                                               install_fault_injector)
    srv = AsyncPSServer(host="127.0.0.1", port=0, num_workers=1)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    c = AsyncPSClient(host="127.0.0.1", port=srv.port)
    c.set_optimizer(mx.optimizer.SGD(learning_rate=0.1,
                                     rescale_grad=1.0))
    c.init("w", np.ones((4,), np.float32))
    inj = install_fault_injector(
        FaultInjector("send:disconnect@3;recv:drop@6"))
    try:
        for i in range(8):
            c.push("w", np.full((4,), float(i % 3), np.float32))
        c.pull("w")
    finally:
        install_fault_injector(None)
    c.close()
    srv.stop()
    assert inj.fired == [("send", 3, "disconnect"),
                         ("recv", 6, "drop")], inj.fired


def _serve_predictor(feat=8, classes=4):
    import mxnet_tpu as mx
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.predictor import Predictor
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=classes)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(2, feat))
    mx.random.seed(7)
    init = Xavier()
    args = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        arr = mx.nd.zeros(shp)
        init(name, arr)
        args[name] = arr
    return Predictor(net, args, data_names=("data",))


def _scn_serve():
    """PR 9 surface: warmed buckets + sequential requests (each its
    own deterministic batch), then a zero-capacity engine so the shed
    count is exact."""
    import numpy as np

    from mxnet_tpu.serve import Overloaded, ServeEngine
    pred = _serve_predictor()
    x = np.zeros((1, 8), np.float32)
    with ServeEngine(pred, buckets=(1, 2, 4), max_wait_ms=0.0,
                     feature_shapes=[(8,)],
                     install_sigterm=False) as eng:
        eng.warmup()
        for _ in range(4):                  # sequential: fill=1 each
            eng.infer(x, timeout=60.0)
    with ServeEngine(pred, buckets=(1,), max_wait_ms=0.0, queue_cap=0,
                     feature_shapes=[(8,)],
                     install_sigterm=False) as eng:
        for _ in range(2):                  # cap 0: every submit sheds
            try:
                eng.submit(x)
            except Overloaded:
                pass


def _scn_router():
    """PR 14 surface: fleet router over two in-process replicas —
    replica 1 sheds every request (queue cap 0) so each of the 4
    sequential requests reroutes to replica 2 (exact reroute count),
    then replica 2 is recycled (drain -> in-process restart ->
    re-warm over the wire -> readmit) and serves one more. Counters,
    reroutes, recycle events and the router->replica span edges are
    all deterministic."""
    import numpy as np

    from mxnet_tpu.serve import ServeEngine, ServeRouter, ServeServer
    pred = _serve_predictor()
    x = np.zeros((1, 8), np.float32)

    def make_replica(cap):
        kw = {} if cap is None else {"queue_cap": cap}
        eng = ServeEngine(pred, buckets=(1, 2), max_wait_ms=0.0,
                          feature_shapes=[(8,)],
                          install_sigterm=False, **kw)
        return eng, ServeServer(eng)
    e1, s1 = make_replica(0)              # sheds everything
    e2, s2 = make_replica(None)
    live = {"e": e2, "s": s2}
    router = ServeRouter(poll_ms=0)       # no background poller: every
    #                                       stats RPC is scripted
    router.add_replica(s1.host, s1.port, name="r1")
    router.add_replica(s2.host, s2.port, name="r2")
    router.poll_now()
    for _ in range(4):                    # r1 sheds -> reroute to r2
        router.infer(x, timeout=60.0)

    def restart():
        live["s"].close()
        live["e"].close()
        live["e"], live["s"] = make_replica(None)
        return (live["s"].host, live["s"].port)
    router.recycle("r2", restart=restart)
    router.infer(x, timeout=60.0)         # the readmitted replica serves
    router.close()
    for closer in (s1, live["s"], e1, live["e"]):
        closer.close()


def _decode_workload(quantize_kv, block_type="attention"):
    """Shared body of the decode scenarios: sequential ragged
    requests through a 3-slot pool so admissions/steps/finishes are
    exact and every admission is a slot turnover (the jit-cache gauge
    must stay at ONE compiled (B, 1) step across them)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    V, L, H, DIM, T = 50, 2, 2, 32, 24
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 pos_encoding="learned",
                                 block_type=block_type)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    state = step.init_state(Xavier(), {"data": (2, 12),
                                       "softmax_label": (2, 12)})
    gen = Generator(state[0], V, T, num_layers=L, num_heads=H,
                    dim=DIM, batch_size=3, quantize_kv=quantize_kv,
                    block_type=block_type)
    with gen.serving_decoder() as dec:
        for length, max_new in ((4, 5), (6, 3), (3, 4)):
            dec.submit(np.arange(length), max_new,
                       eos_id=None).result(300.0)


def _scn_disagg():
    """PR 15 surface: prefill/decode disaggregation — one prefill +
    one decode in-process replica behind the role-aware router,
    sequential ragged generates with ONE injected transport fault torn
    into the 2nd prefill frame. The pure prefill replays to the
    identical blob, every admission is a scatter-only import (zero
    decode-side prefill graph calls), and the decode (B, 1) step stays
    ONE compiled executable across imported-slot turnover."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.parallel.resilience import (FaultInjector,
                                               install_fault_injector)
    from mxnet_tpu.serve import (ContinuousDecoder, PrefillEngine,
                                 ServeRouter, ServeServer)
    V, L, H, DIM, T = 50, 2, 2, 32, 24
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 pos_encoding="learned")
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    params = step.init_state(Xavier(), {"data": (2, 12),
                                        "softmax_label": (2, 12)})[0]

    def gen(bs):
        return Generator(params, V, T, num_layers=L, num_heads=H,
                         dim=DIM, batch_size=bs)
    pre = PrefillEngine(gen(1))
    dec = ContinuousDecoder(gen(3))
    s1, s2 = ServeServer(pre), ServeServer(dec)
    router = ServeRouter(poll_ms=0)       # scripted polling only
    router.add_replica(s1.host, s1.port, name="prefill0")
    router.add_replica(s2.host, s2.port, name="decode0")
    router.poll_now()
    inj = install_fault_injector(
        FaultInjector("prefill_send:disconnect@2"))
    try:
        for length, max_new in ((4, 5), (6, 3), (3, 4)):
            router.generate(np.arange(1, length + 1), max_new,
                            session="s")
    finally:
        install_fault_injector(None)
    assert inj.fired == [("prefill_send", 2, "disconnect")], inj.fired
    st = dec.stats()
    assert st["prefills"] == 0 and st["imported"] == 3, st
    router.close()
    for closer in (s1, s2, dec, pre):
        closer.close()


def _scn_failover():
    """PR 16 surface: fleet survives replica death — two in-process
    decode replicas behind the router. One pinned replica "dies"
    (every data send AND the liveness probe dropped) mid-generate:
    the router fails the pin over and REPLAYS the request on the
    survivor token-for-token (same prompt + seed => byte-equal row).
    Then a recycle of the replica holding a live session migrates it
    mid-decode (evacuate -> resume on a survivor) instead of
    draining, and the migrated row is byte-equal to an undisturbed
    run. Failover/replay/migration/evacuation counters, the
    suspect->revive cycle and the decode resume/dedup counters are
    all deterministic; the (B, 1) decode step stays ONE compiled
    executable across the evacuated-slot turnover."""
    import threading
    import time

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.parallel.resilience import (FaultInjector,
                                               install_fault_injector)
    from mxnet_tpu.serve import ContinuousDecoder, ServeRouter, ServeServer
    V, L, H, DIM, T = 50, 2, 2, 32, 24
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 pos_encoding="learned")
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    params = step.init_state(Xavier(), {"data": (2, 12),
                                        "softmax_label": (2, 12)})[0]

    def gen():
        return Generator(params, V, T, num_layers=L, num_heads=H,
                         dim=DIM, batch_size=3)

    def cval(name):
        rec = telemetry.snapshot().get(name) or {}
        return rec.get("value", 0)
    d0 = ContinuousDecoder(gen())
    d1 = ContinuousDecoder(gen())
    s0, s1 = ServeServer(d0), ServeServer(d1)
    router = ServeRouter(poll_ms=0)       # scripted polling only
    router.add_replica(s0.host, s0.port, name="d0")
    router.add_replica(s1.host, s1.port, name="d1")
    router.poll_now()
    p = np.arange(1, 5)
    kw = {"temperature": 0.8, "top_k": 8, "seed": 7}
    r1 = router.generate(p, 5, session="s", timeout=120.0, **kw)
    pin = router.sessions()["s"]
    idx = int(pin[-1])                    # add_replica order == family
    # the pinned replica "dies": every data send and the control-path
    # liveness probe fail from here on
    inj = install_fault_injector(FaultInjector(
        "router%d_send:drop@1x*;router%d_ctl_send:drop@1x*"
        % (idx, idx)))
    try:
        r2 = router.generate(p, 5, session="s", timeout=120.0, **kw)
    finally:
        install_fault_injector(None)
    assert inj.fired and {f[0] for f in inj.fired} <= {
        "router%d_send" % idx, "router%d_ctl_send" % idx}, inj.fired
    # token-exact replay: same prompt + seed on the survivor
    assert np.array_equal(r1, r2), (r1, r2)
    assert router.sessions()["s"] != pin
    router.poll_now()                     # fault gone -> revive
    # -- live migration: recycle the replica holding session "m" ----
    steps0 = cval("serve.decode.steps")
    box = {}

    def bg():
        box["row"] = router.generate(np.arange(1, 4), 18, session="m",
                                     timeout=120.0, temperature=0.8,
                                     top_k=8, seed=11)
    th = threading.Thread(target=bg)
    th.start()
    while cval("serve.decode.steps") < steps0 + 2:   # mid-decode
        time.sleep(0.005)
    router.recycle(router.sessions()["m"], timeout=60.0)
    th.join(120.0)
    assert not th.is_alive(), "migrated generate never completed"
    # byte-equal to an undisturbed run of the same request
    ver = router.generate(np.arange(1, 4), 18, session="v",
                          timeout=120.0, temperature=0.8, top_k=8,
                          seed=11)
    assert np.array_equal(box["row"], ver), (box["row"], ver)
    st0, st1 = d0.stats(), d1.stats()
    assert st0["evacuated"] + st1["evacuated"] == 1, (st0, st1)
    assert st0["resumed"] + st1["resumed"] == 1, (st0, st1)
    router.close()
    for closer in (s0, s1, d0, d1):
        closer.close()


def _scn_decode():
    """PR 9 surface: continuous-batching decode, sequential ragged
    requests so admissions/steps/finishes are exact."""
    _decode_workload(quantize_kv=False)


def _scn_decode_q8():
    """PR 13 surface: the SAME ragged workload with int8 KV caches —
    the per-row q8 op must keep jit cache size 1 across slot
    turnover and publish the (halved) kv_bytes_per_slot gauge."""
    _decode_workload(quantize_kv=True)


def _scn_decode_ssm():
    """ISSUE 19 surface: the SAME ragged workload on an O(1)-state
    SSM generator — slot turnover over constant (H, hd, hd) state
    blobs must keep jit cache size 1 (the recurrence needs no per-row
    twin at all) and publish a kv_bytes_per_slot gauge that never
    mentions max_len."""
    _decode_workload(quantize_kv=False, block_type="ssm")


def _scn_streaming():
    """PR 17 surface: streamed generate frames + chunked prefill.
    One decode replica behind the wire: a streamed generate's
    on_token tail byte-equals the one-shot row (greedy AND seeded —
    the terminal reply cross-checks every stream bitwise), a long
    prompt under MXNET_PREFILL_CHUNK admits in a deterministic chunk
    count with the same bits, and the (B, 1) decode step stays ONE
    compiled executable across streamed + chunked turnover. Stream/
    chunk counters are exact and no frame is late; frame and hand-off
    counts are noisy (a frame is a loop turn's tokens of one stream
    and a hand-off a turn's frames, but a token emitted before its
    stream subscribed is the handler's replayed prefix, not the
    relay's, and that is a race between two threads)."""
    import os as _os

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.serve import ContinuousDecoder, ServeServer
    from mxnet_tpu.serve.net import ServeClient
    V, L, H, DIM, T = 50, 2, 2, 32, 24
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    params = step.init_state(Xavier(), {"data": (2, 12),
                                        "softmax_label": (2, 12)})[0]

    def gen(bs):
        return Generator(params, V, T, num_layers=L, num_heads=H,
                         dim=DIM, batch_size=bs)
    p, long_p = np.arange(1, 5), np.arange(1, 11)
    kw = {"temperature": 0.8, "top_k": 8, "seed": 7}
    single = gen(1)
    want = single.generate(p[None], 8, eos_id=0)[0]
    want_s = single.generate(p[None], 8, eos_id=0, **kw)[0]
    want_l = single.generate(long_p[None], 6, eos_id=0)[0]
    dec = ContinuousDecoder(gen(2))
    srv = ServeServer(dec)
    with ServeClient(srv.host, srv.port) as cli:
        toks = []
        out = cli.generate(p, 8, eos_id=0, on_token=toks.append)
        assert np.array_equal(out, want), (out, want)
        assert np.array_equal(toks, want[p.size:]), (toks, want)
        toks = []
        out = cli.generate(p, 8, eos_id=0, on_token=toks.append,
                           **kw)
        assert np.array_equal(out, want_s), (out, want_s)
        assert np.array_equal(toks, want_s[p.size:]), (toks, want_s)
        # chunked prefill: 10-token prompt in 3-token slices -> 4
        # chunks, bit-identical row
        _os.environ["MXNET_PREFILL_CHUNK"] = "3"
        try:
            out = cli.generate(long_p, 6, eos_id=0)
        finally:
            _os.environ.pop("MXNET_PREFILL_CHUNK", None)
        assert np.array_equal(out, want_l), (out, want_l)

    def cval(name):
        rec = telemetry.snapshot().get(name) or {}
        return rec.get("value", 0)
    assert cval("serve.decode.streams") == 2
    assert cval("serve.decode.prefill_chunks") == 4
    srv.close()
    dec.close()


def _scn_spec_decode():
    """PR 18 surface: speculative decoding in the serving fleet —
    one decode replica with a 1-layer truncated draft attached. A
    plain (non-speculative) request runs FIRST and alone, tracing
    the (B, 1) target step, then greedy + sampled speculative
    requests run draft/verify rounds: every row byte-equals the
    single-row generate (shared-noise verification — speculation is
    a schedule, not a sampler), the target owns exactly TWO compiled
    programs ((B, 1) step + (B, gamma+1) verify), the draft exactly
    ONE, and the round/draft-step/acceptance counters are exact for
    the deterministic workload."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.generation import Generator
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.serve import ContinuousDecoder, ServeServer
    from mxnet_tpu.serve.net import ServeClient
    V, L, H, DIM, T = 50, 2, 2, 32, 24
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    params = step.init_state(Xavier(), {"data": (2, 12),
                                        "softmax_label": (2, 12)})[0]

    def gen(bs):
        return Generator(params, V, T, num_layers=L, num_heads=H,
                         dim=DIM, batch_size=bs)
    p = np.arange(1, 5)
    kw = {"temperature": 0.8, "top_k": 8, "seed": 7}
    single = gen(1)
    want = single.generate(p[None], 8, eos_id=0)[0]
    want_s = single.generate(p[None], 8, eos_id=0, **kw)[0]
    target = gen(2)
    dec = ContinuousDecoder(target,
                            draft=target.truncated_draft(num_layers=1),
                            lookahead=3)
    srv = ServeServer(dec)
    with ServeClient(srv.host, srv.port) as cli:
        # the plain request runs FIRST and alone so the (B, 1) step
        # traces before any verify — the jit gauge then pins the
        # full two-program target contract
        out = cli.generate(p, 8, eos_id=0)
        assert np.array_equal(out, want), (out, want)
        out = cli.generate(p, 8, eos_id=0, speculative=True)
        assert np.array_equal(out, want), (out, want)
        out = cli.generate(p, 8, eos_id=0, speculative=True, **kw)
        assert np.array_equal(out, want_s), (out, want_s)
    st = dec.stats()
    assert st["spec_rounds"] > 0 and st["draft_steps"] > 0, st
    assert st["spec_accepted"] <= st["spec_proposed"], st
    assert st["draft_prefills"] == 2, st   # one per speculative admit

    def gval(name):
        rec = telemetry.snapshot().get(name) or {}
        return rec.get("value", 0)
    assert gval("serve.decode.jit_cache_size") == 2
    assert gval("serve.spec.draft_jit_cache_size") == 1
    srv.close()
    dec.close()


def _scn_controller():
    """PR 20 surface: the fleet controller over an in-process
    2-replica fleet — one forced scale-out on a scripted sustained
    queue-depth signal, one self-heal of a cold-killed replica, one
    scale-in back to the floor, and one rollout gated down by a
    deliberately broken canary artifact (rolled back, zero traffic
    ever routed to it). Decisions are explicit ``tick()`` calls
    against scripted stats frames, so every serve.ctrl counter is
    exact."""
    import numpy as np

    from mxnet_tpu.serve import (FleetController, ServeEngine,
                                 ServeRouter, ServeServer)
    pred = _serve_predictor()
    x = np.zeros((1, 8), np.float32)

    class Scripted(ServeEngine):
        fake_depth = 0

        def introspect(self):
            out = super().introspect()
            out["queue_depth"] += self.fake_depth
            return out

    class Broken:
        def forward(self, *arrays):
            raise RuntimeError("deliberately broken artifact")

    cells = {}                  # "host:port" -> (engine, server)

    def spawn(manifest=None):
        model = Broken() if manifest == "bad" else pred
        eng = Scripted(model, buckets=(1, 2), max_wait_ms=0.0,
                       feature_shapes=[(8,)], install_sigterm=False)
        srv = ServeServer(eng)
        cells["%s:%d" % (srv.host, srv.port)] = (eng, srv)
        return (srv.host, srv.port)

    def retire(name, addr):
        cell = cells.pop(addr, None)
        if cell is not None:
            cell[1].close()
            cell[0].close()

    def script_depth(depth):
        for eng, _ in cells.values():
            eng.fake_depth = depth

    router = ServeRouter(poll_ms=0)       # every stats RPC scripted
    for i in range(2):
        host, port = spawn(None)
        router.add_replica(host, port, name="r%d" % i)
    router.poll_now()
    ctrl = FleetController(router, spawn, retire=retire, poll_ms=0,
                           min_replicas=2, max_replicas=3,
                           sustain=1, cooldown=0, canary_inputs=[x])
    # 1. scale-out: a sustained (sustain=1) scripted depth signal
    script_depth(50)
    assert len(ctrl.tick()["scaled_out"]) == 1
    script_depth(2)                       # neutral band: no action
    router.infer(x, timeout=60.0)         # the grown fleet serves
    # 2. heal: kill r1 cold (no drain); the next tick suspects,
    # probe-confirms, and respawns it under the same name
    desc = router.replicas()["r1"]
    retire("r1", "%s:%d" % (desc["host"], desc["port"]))
    assert ctrl.tick()["healed"] == ["r1"]
    # 3. scale-in: an idle window drains the newest replica away
    script_depth(0)
    assert len(ctrl.tick()["scaled_in"]) == 1
    # 4. gated rollback: the broken artifact fails its canary on the
    # first replica and rolls back — the fleet stays on the prior
    res = ctrl.rollout("bad", model_id="vBad")
    assert res.rolled_back, res
    router.infer(x, timeout=60.0)         # still serving, uniform
    ctrl.close()
    router.close()
    for eng, srv in list(cells.values()):
        srv.close()
        eng.close()


# which PR-won property each gauge protects is resolved through
# _PROPERTY_NOTES below; `gauges` lists the gauge names a scenario
# REQUIRES in the final snapshot (absence is itself a gate failure),
# `noisy_counters`/`noisy_events` name snapshot fields excluded from
# the exact compare because their values are timing-dependent.
SCENARIOS = {
    "trainstep": {
        "fn": _scn_trainstep,
        "desc": "pipelined TrainStep.fit + guardrail NaN masking",
        "gauges": ("trainstep.jit_cache_size",),
        "noisy_counters": (), "noisy_events": (),
    },
    "module": {
        "fn": _scn_module,
        "desc": "Module.fit executor-group path",
        "gauges": ("step.model_flops",),
        "noisy_counters": (), "noisy_events": (),
    },
    "gspmd": {
        "fn": _scn_gspmd,
        "desc": "one-jit GSPMD fit (data×fsdp, zero1)",
        "gauges": ("trainstep.jit_cache_size", "gspmd.sharded_params"),
        "noisy_counters": (), "noisy_events": (),
    },
    "ps_faults": {
        "fn": _scn_ps_faults,
        "desc": "PS push/pull under injected disconnect+drop",
        "gauges": (),
        "noisy_counters": (), "noisy_events": (),
    },
    "serve": {
        "fn": _scn_serve,
        "desc": "ServeEngine request path + exact shed",
        "gauges": (),
        "noisy_counters": (), "noisy_events": (),
    },
    "router": {
        "fn": _scn_router,
        "desc": "fleet router: shed-and-retry + zero-drop recycle "
                "over two in-process replicas",
        "gauges": ("serve.router.replicas_live",
                   "serve.router.sessions"),
        "noisy_counters": (), "noisy_events": (),
    },
    "decode": {
        "fn": _scn_decode,
        "desc": "ContinuousDecoder sequential ragged requests",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.decode.kv_bytes_per_slot"),
        "noisy_counters": (), "noisy_events": (),
    },
    "decode_q8": {
        "fn": _scn_decode_q8,
        "desc": "ContinuousDecoder ragged requests, int8 KV caches "
                "(quantize_kv)",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.decode.kv_bytes_per_slot"),
        "noisy_counters": (), "noisy_events": (),
    },
    "decode_ssm": {
        "fn": _scn_decode_ssm,
        "desc": "ContinuousDecoder ragged requests, O(1) SSM state "
                "blobs (block_type='ssm')",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.decode.kv_bytes_per_slot"),
        "noisy_counters": (), "noisy_events": (),
    },
    "disagg": {
        "fn": _scn_disagg,
        "desc": "prefill/decode disaggregation: role-aware router, "
                "KV handoff with one injected mid-handoff fault",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.router.replicas_live"),
        "noisy_counters": (), "noisy_events": (),
    },
    "failover": {
        "fn": _scn_failover,
        "desc": "fleet replica death: token-exact generate failover "
                "+ one live mid-decode session migration",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.router.replicas_live"),
        "noisy_counters": (), "noisy_events": (),
    },
    "streaming": {
        "fn": _scn_streaming,
        "desc": "streamed generate frames (token-exact vs one-shot) "
                "+ chunked prefill, one decode replica on the wire",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.decode.kv_bytes_per_slot"),
        # a first token emitted before its stream subscribed rides
        # the replayed prefix — frame and hand-off counts are
        # scheduling-dependent, the token sequence is not
        "noisy_counters": ("serve.net.stream_frames",
                           "serve.decode.stream_handoffs"),
        "noisy_events": (),
    },
    "spec_decode": {
        "fn": _scn_spec_decode,
        "desc": "speculative decoding: draft/verify rounds on one "
                "decode replica, token-exact vs plain decode",
        "gauges": ("serve.decode.jit_cache_size",
                   "serve.spec.draft_jit_cache_size",
                   "serve.decode.kv_bytes_per_slot"),
        "noisy_counters": (), "noisy_events": (),
    },
    "controller": {
        "fn": _scn_controller,
        "desc": "fleet controller: scripted scale-out, self-heal, "
                "scale-in, and one canary-gated rollback",
        "gauges": ("serve.router.replicas_live",
                   "serve.router.replicas"),
        "noisy_counters": (), "noisy_events": (),
    },
}

# field-path prefix -> the protected property a regression names.
# Ordered most-specific first; the first match wins.
_PROPERTY_NOTES = (
    ("counts.probe.max_step_syncs_steady",
     "PR 2 pipelined hot loop: at most ONE blocking host sync per "
     "steady-state step (a stray .asnumpy()/wait in the step loop "
     "re-serializes host and device)"),
    ("counts.probe.fit_total_syncs",
     "PR 2 pipelined hot loop: total blocking host syncs across the "
     "fit are budgeted (window drains + epoch metric reads only)"),
    ("counts.gauges.trainstep.jit_cache_size",
     "PR 11 donated-buffer sharding: ONE cached executable across "
     "donated steps (a growing jit cache is the step-2-recompile "
     "regression — outgoing state lost its pinned sharding)"),
    ("counts.gauges.gspmd.sharded_params",
     "PR 11 SpecLayout placement: the expected parameter count is "
     "sharded over the data×fsdp mesh"),
    ("counts.gauges.serve.decode.jit_cache_size",
     "PR 13 int8 continuous decode: ONE compiled (B, 1) step across "
     "slot turnover (a growing jit cache means admissions recompile "
     "— the per-admission-recompile regression continuous batching "
     "exists to avoid); with a speculative draft attached the target "
     "owns exactly TWO programs — the step plus the (B, gamma+1) "
     "verify (PR 18)"),
    ("counts.gauges.serve.spec.draft_jit_cache_size",
     "PR 18 speculative compile discipline: the draft owns exactly "
     "ONE compiled (B, 1) program across propose steps, catch-ups "
     "and slot turnover"),
    ("counts.counters.serve.spec.rounds",
     "PR 18 speculative serving: one verify forward per draft/"
     "verify round, exactly — a drifting round count means the "
     "acceptance walk or the round scheduler changed"),
    ("counts.counters.serve.spec.accepted",
     "PR 18 shared-noise verification: the accepted-token count is "
     "exact for a deterministic workload (a drift means draft "
     "proposal or target verification changed numerically — and "
     "token-exactness vs plain decode is probably gone with it)"),
    ("counts.counters.serve.spec.",
     "PR 18 speculative serving: draft-step/proposal/draft-prefill "
     "counters are exact for a deterministic request sequence"),
    ("counts.gauges.serve.decode.kv_bytes_per_slot",
     "PR 13/19 decode HBM diet: state bytes per slot follow from the "
     "cache pytree's shapes/dtypes alone — a drift means the int8 "
     "rows, per-token scale caches, or O(1) SSM state blobs changed "
     "layout"),
    ("counts.compile",
     "compile discipline: XLA compiles happen exactly where the "
     "baseline says (first step / per jit variant); extra compile "
     "events or a later compile-flagged step mean steady-state "
     "recompilation"),
    ("counts.counters.ps.retries",
     "PR 1 resilience: deterministic fault injection produces the "
     "exact retry count (exactly-once replay, no hidden extra "
     "round trips)"),
    ("counts.counters.ps.reconnects",
     "PR 1 resilience: reconnect-and-replay count under injected "
     "disconnects is exact"),
    ("counts.counters.guardrail.masked_steps",
     "PR 3 guardrails: the injected non-finite step is masked on "
     "device and counted exactly once"),
    ("counts.counters.serve.router.rerouted",
     "PR 14 shed-and-retry: a replica-local Overloaded retries on "
     "the next-least-loaded replica, counted exactly (a drifting "
     "reroute count means dispatch order or the on_fatal hook "
     "changed)"),
    ("counts.counters.serve.router.recycles",
     "PR 14 zero-drop rolling restarts: drain -> restart -> re-warm "
     "-> readmit ran to completion exactly as scripted"),
    ("counts.counters.serve.decode.streams",
     "PR 17 streaming: one stream per streamed generate, exactly — "
     "a drift means the frame subscription path double-registers or "
     "silently degrades to one-shot"),
    ("counts.counters.serve.decode.prefill_chunks",
     "PR 17 chunked prefill: ceil(prompt/MXNET_PREFILL_CHUNK) chunk "
     "forwards per long admission, exactly — a drift means the "
     "chunk loop re-runs slices or stopped interleaving"),
    ("counts.counters.serve.net.stream",
     "PR 17 streaming wire: streamed requests counted once at the "
     "server (frame counts are scheduling-dependent and excluded "
     "where streams run)"),
    ("counts.counters.serve.router.streams",
     "PR 17 streaming relay: the router relays frames without "
     "buffering, one stream per streamed generate"),
    ("counts.counters.serve.prefill.batched",
     "PR 17 batched prefill: coalesced prefill groups — nonzero "
     "only where concurrent prompts rode one padded forward"),
    ("counts.counters.serve.prefill.",
     "PR 15 disaggregation: prefill fan-out is exact — requests "
     "prefilled on prefill-role replicas and handoffs shipped, "
     "counted one per generate even across the injected mid-handoff "
     "replay (a drift means role-aware dispatch or the pure-replay "
     "path changed)"),
    ("counts.counters.serve.decode.imported",
     "PR 15 disaggregation: every admission of a remote-prefilled "
     "sequence is a scatter-only import — exactly one admit per "
     "request, zero prefill graph calls on the decode replica"),
    ("counts.counters.serve.router.generates",
     "PR 15 disaggregation: completed generate dispatches are exact "
     "for a deterministic request sequence"),
    ("counts.counters.serve.router.failovers",
     "PR 16 replica-death failover: a pinned replica whose probe "
     "fails is failed over exactly once per dead pin (a drift means "
     "the probe discriminator or pin handoff changed)"),
    ("counts.counters.serve.router.replays",
     "PR 16 token-exact replay: the recovery record replays a "
     "mid-flight generate exactly once — on the survivor after a "
     "dead pin, on the same replica after a transient fault"),
    ("counts.counters.serve.router.migrations",
     "PR 16 live session migration: each mid-decode session a "
     "recycle evacuates resumes on a survivor exactly once "
     "(bit-exact continuation, never a from-scratch replay)"),
    ("counts.counters.serve.router.evacuations",
     "PR 16 evacuating recycle: a decode-role recycle exports its "
     "active sessions instead of draining them — the evacuate count "
     "is exact for a scripted recycle"),
    ("counts.counters.serve.decode.resumed",
     "PR 16 migration landing: every evacuated session is admitted "
     "exactly once via the scatter-only resume path (no re-prefill, "
     "no divergence)"),
    ("counts.counters.serve.decode.evacuated",
     "PR 16 session export: the engine exports exactly the sessions "
     "the recycle evacuated mid-decode"),
    ("counts.counters.serve.decode.deduped",
     "PR 16 exactly-once admission: the decode dedup table swallows "
     "replayed admits — a drift means the admit-id lineage or the "
     "dedup window changed"),
    ("counts.counters.serve.router.",
     "PR 14 fleet router: dispatch/suspect/session counters are "
     "exact for a deterministic request sequence"),
    ("counts.gauges.serve.router.replicas_live",
     "PR 14 fleet health: every replica is live again after the "
     "recycle (a stuck draining/suspect replica shrinks the fleet)"),
    ("counts.counters.serve.ctrl.",
     "PR 20 fleet controller: scale-out/scale-in/heal/promote/"
     "rollback decisions are exact for a scripted signal sequence — "
     "a drift means hysteresis, cooldown, the liveness probe, or the "
     "rollout gate changed semantics"),
    ("counts.counters.serve.shed",
     "PR 9 backpressure: a full queue sheds with the typed "
     "Overloaded, counted exactly"),
    ("counts.counters.serve.",
     "PR 9 serving engine: admission/forward/decode counters are "
     "exact for a deterministic request sequence"),
    ("counts.counters.host_syncs",
     "PR 2 sync budget: the process-wide blocking-host-sync total "
     "for this deterministic workload is exact"),
    ("counts.journal_schema",
     "PR 8 journal schema version: readers refuse unknown schemas — "
     "bump SCHEMA_VERSION and re-bless deliberately, never drift"),
    ("counts.events",
     "PR 8/10 event vocabulary: every journal event the scenario "
     "used to emit must still be emitted, exactly as often"),
    ("counts.steps",
     "journal step records: the fit loops journal one record per "
     "step"),
    ("trace.",
     "PR 10 tracing: the span vocabulary / nesting shape of this "
     "path (a span that disappears or re-parents breaks trace "
     "consumers and usually marks deleted instrumentation)"),
)


def property_note(path):
    for prefix, note in _PROPERTY_NOTES:
        if path.startswith(prefix):
            return note
    return "gate fingerprint field (see docs/perf_gates.md)"


# ---------------------------------------------------------------------------
# fingerprint extraction
# ---------------------------------------------------------------------------

# the one torn-final-line-tolerant JSONL loader (the journal/spill
# write contract's read side) is shared across the tools — schema
# checked per file kind at the call sites in run_scenario
try:
    from tools.telemetry_report import load_jsonl
except ImportError:
    from telemetry_report import load_jsonl


def _intish(v):
    if isinstance(v, float) and v.is_integer():
        return int(v)
    return v


def extract_fingerprint(scenario, journal_records, trace_records):
    """The gate fingerprint: counts and trace shape (exact-compared)
    from one scenario run's journal and trace spill."""
    from mxnet_tpu.trace import span_shape

    cfg = SCENARIOS[scenario]
    counts = {}
    run_start = next((r for r in journal_records
                      if r.get("kind") == "run_start"), None)
    counts["journal_schema"] = (run_start or {}).get("schema")
    steps = [r for r in journal_records if r.get("kind") == "step"]
    counts["steps"] = len(steps)
    counts["compile_steps"] = sorted(
        int(s.get("step", -1)) for s in steps if s.get("compile"))

    events, probe = {}, {}
    for r in journal_records:
        if r.get("kind") != "event":
            continue
        ev = r.get("event", "?")
        events[ev] = events.get(ev, 0) + 1
        if ev == "gate.probe":
            probe.update(r.get("fields") or {})
    counts["compile_events"] = events.get("compile", 0)
    counts["events"] = {k: v for k, v in sorted(events.items())
                        if k not in cfg["noisy_events"]}
    counts["probe"] = dict(sorted(probe.items()))

    snap = next((r.get("metrics") for r in reversed(journal_records)
                 if r.get("kind") == "snapshot"), None) or {}
    counts["counters"] = {
        k: _intish(v.get("value")) for k, v in sorted(snap.items())
        if v.get("type") == "counter" and k not in cfg["noisy_counters"]}
    counts["gauges"] = {}
    for g in cfg["gauges"]:
        val = snap.get(g, {}).get("value")
        # model_flops is workload-determined but large; presence +
        # exact value are both deterministic, so keep it exact
        counts["gauges"][g] = _intish(val) if val is not None else None

    return {"gate_schema": GATE_SCHEMA, "scenario": scenario,
            "counts": counts, "trace": span_shape(trace_records)}


# ---------------------------------------------------------------------------
# compare
# ---------------------------------------------------------------------------

class Failure:
    def __init__(self, path, baseline, live, why=None):
        self.path, self.baseline, self.live = path, baseline, live
        self.why = why

    def format(self):
        head = "%s: baseline %r -> live %r" % (
            self.path, self.baseline, self.live)
        if self.why:
            head += "  (%s)" % self.why
        return head + "\n      regressed property: %s" \
            % property_note(self.path)


def _cmp_tree(path, base, live, fails):
    if isinstance(base, dict) or isinstance(live, dict):
        bkeys = set(base or {}) if isinstance(base, dict) else set()
        lkeys = set(live or {}) if isinstance(live, dict) else set()
        for k in sorted(bkeys | lkeys):
            sub = "%s.%s" % (path, k)
            if k not in lkeys:
                fails.append(Failure(sub, (base or {}).get(k), None,
                                     "missing from live run"))
            elif k not in bkeys:
                fails.append(Failure(sub, None, (live or {}).get(k),
                                     "not in baseline — re-bless if "
                                     "intended"))
            else:
                _cmp_tree(sub, base[k], live[k], fails)
        return
    if base != live:
        fails.append(Failure(path, base, live))


def compare(baseline, live):
    """Baseline record (the perf_baselines/*.json dict) vs a live
    fingerprint -> list of Failure. Every field is exact."""
    fails = []
    bfp = baseline["fingerprint"]
    if bfp.get("gate_schema") != live.get("gate_schema"):
        fails.append(Failure("gate_schema", bfp.get("gate_schema"),
                             live.get("gate_schema")))
        return fails
    _cmp_tree("counts", bfp.get("counts"), live.get("counts"), fails)
    _cmp_tree("trace", bfp.get("trace"), live.get("trace"), fails)
    return fails


# ---------------------------------------------------------------------------
# the runner (parent side)
# ---------------------------------------------------------------------------

def scenario_env(out_dir):
    """The child's env: deterministic by construction. EVERY MXNET_*
    and BENCH_* knob from the operator's shell is dropped (a stray
    MXNET_DISPATCH_AHEAD=1 would shift the sync-count fingerprint and
    read as a false PR 2 regression) and XLA_FLAGS is pinned to
    exactly the forced-8-device mesh; then the six knobs the gate
    itself needs are set."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MXNET_", "BENCH_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["MXNET_TELEMETRY"] = os.path.join(out_dir, "journal.jsonl")
    env["MXNET_TRACE"] = os.path.join(out_dir, "trace.jsonl")
    env["PYTHONHASHSEED"] = "0"
    env["MXNET_PS_RETRY_BASE"] = "0.01"
    # no heartbeat may fire inside a scenario window (its ping count
    # would be timing-dependent)
    env["MXNET_PS_HEARTBEAT_INTERVAL"] = "600"
    return env


def run_scenario(name, out_dir, timeout=600):
    """Run one scenario subprocess; returns (fingerprint, None) or
    (None, failure_text). A scenario that dies before producing any
    journal is a GATE FAILURE with the child's stderr attached, never
    an unhandled traceback."""
    os.makedirs(out_dir, exist_ok=True)
    env = scenario_env(out_dir)
    # journal + spill open in APPEND mode; a reused --keep dir must
    # not accumulate the previous run's records into this fingerprint
    for stale in (env["MXNET_TELEMETRY"], env["MXNET_TRACE"]):
        if os.path.exists(stale):
            os.unlink(stale)
    try:
        proc = subprocess.run(
            [sys.executable, _SELF, "--run-scenario", name],
            env=env, cwd=_REPO, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, "scenario %r timed out after %ds" % (name, timeout)
    tail = "\n".join(proc.stderr.strip().splitlines()[-8:])
    if proc.returncode != 0:
        return None, "scenario %r exited rc=%d before completing:\n%s" \
            % (name, proc.returncode, tail)
    jpath = env["MXNET_TELEMETRY"]
    tpath = env["MXNET_TRACE"]
    if not os.path.exists(jpath):
        return None, "scenario %r produced no journal at %s:\n%s" \
            % (name, jpath, tail)
    try:
        fp = extract_fingerprint(name, load_jsonl(jpath),
                                 load_jsonl(tpath)
                                 if os.path.exists(tpath) else [])
    except ValueError as e:
        return None, "scenario %r journal/trace unreadable: %s" \
            % (name, e)
    return fp, None


def baseline_path(name, baselines=None):
    return os.path.join(baselines or BASELINE_DIR, name + ".json")


def load_baseline(name, baselines=None):
    with open(baseline_path(name, baselines)) as f:
        return json.load(f)


def bless(name, fingerprint, baselines=None):
    path = baseline_path(name, baselines)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rec = {"scenario": name,
           "description": SCENARIOS[name]["desc"],
           "bless_cmd": "python tools/perf_gate.py --bless "
                        "--scenario " + name,
           "fingerprint": fingerprint}
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def _child_main(name):
    """Scenario body, run in the fresh subprocess the parent spawned
    (journal/trace destinations arrive via env). The name resolves
    BEFORE the journal opens, so a bad scenario dies with no journal —
    the exact before-any-journal failure the parent must report as a
    gate failure, not a traceback."""
    fn = SCENARIOS[name]["fn"]
    from mxnet_tpu import telemetry, trace
    telemetry.start_journal()
    trace.start_tracing()
    fn()
    trace.stop_tracing()
    telemetry.close_journal()


def main(argv=None):
    p = argparse.ArgumentParser(
        description="journal-backed perf-regression gate "
                    "(docs/perf_gates.md)")
    p.add_argument("--scenario", default=None,
                   help="comma-separated subset (default: all)")
    p.add_argument("--bless", action="store_true",
                   help="regenerate the baselines instead of comparing")
    p.add_argument("--baselines", default=None,
                   help="baseline dir (default perf_baselines/)")
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep per-scenario journals/traces under DIR")
    p.add_argument("--json", action="store_true",
                   help="emit a machine-readable result")
    p.add_argument("--run-scenario", default=None,
                   help=argparse.SUPPRESS)   # internal: child mode
    args = p.parse_args(argv)

    if args.run_scenario:
        _child_main(args.run_scenario)
        return 0

    names = list(SCENARIOS) if not args.scenario else [
        s.strip() for s in args.scenario.split(",") if s.strip()]
    for n in names:
        if n not in SCENARIOS:
            p.error("unknown scenario %r (have: %s)"
                    % (n, ", ".join(SCENARIOS)))

    import tempfile
    work = args.keep or tempfile.mkdtemp(prefix="perf_gate_")
    results = {}
    failed = False
    mode = "bless" if args.bless else "check"
    print("== perf gate (%s): %d scenario(s), baselines in %s =="
          % (mode, len(names), args.baselines or BASELINE_DIR))
    for name in names:
        fp, err = run_scenario(name, os.path.join(work, name))
        if err is not None:
            failed = True
            results[name] = {"status": "error", "error": err}
            print("  %-10s ERROR\n    %s" % (name,
                                             err.replace("\n", "\n    ")))
            continue
        if args.bless:
            path = bless(name, fp, args.baselines)
            results[name] = {"status": "blessed", "baseline": path}
            print("  %-10s blessed -> %s"
                  % (name, os.path.relpath(path, _REPO)))
            continue
        try:
            base = load_baseline(name, args.baselines)
        except (OSError, ValueError) as e:
            failed = True
            results[name] = {"status": "error",
                             "error": "no readable baseline: %s" % e}
            print("  %-10s ERROR no readable baseline (%s) — run "
                  "--bless and commit it" % (name, e))
            continue
        fails = compare(base, fp)
        if fails:
            failed = True
            results[name] = {"status": "fail",
                             "failures": [f.format() for f in fails]}
            print("  %-10s FAIL (%d divergence(s))" % (name, len(fails)))
            for f in fails:
                print("    - " + f.format())
        else:
            results[name] = {"status": "ok"}
            c = fp["counts"]
            print("  %-10s OK (steps=%d, %d compile event(s), %d "
                  "span name(s))"
                  % (name, c["steps"], c["compile_events"],
                     len(fp["trace"]["spans"])))
    if not args.keep and not failed:
        import shutil
        shutil.rmtree(work, ignore_errors=True)
    elif failed and not args.keep:
        print("artifacts kept for inspection under %s" % work)
    if args.json:
        print(json.dumps(results, indent=2))
    if failed:
        print("PERF GATE: FAIL — a committed-baseline property "
              "regressed (or changed intentionally: re-bless with "
              "tools/perf_gate.py --bless and commit the new "
              "baselines)")
        return 1
    print("PERF GATE: OK" if not args.bless else
          "PERF GATE: baselines regenerated — review + commit "
          "perf_baselines/")
    return 0


if __name__ == "__main__":
    sys.exit(main())
