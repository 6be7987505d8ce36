"""Distributed tracing (ISSUE 10): span layer, wire propagation,
instrumented loops, and the Perfetto export.

The load-bearing assertions (acceptance):
- a PS client op and the server-side handler it caused share ONE
  trace_id with correct parent/child nesting, across threads
  (in-process) and across PROCESSES (subprocess variant), and the
  merged Chrome JSON contains the flow arrows;
- a concurrent serve request's client span, server handler span and
  the batcher's queue/pad/forward/respond lifecycle all share one
  trace_id;
- tracing enabled adds ZERO blocking host syncs vs disabled
  (profiler.host_sync_count identical);
- disabled mode is a bounded no-op (no spill file, cheap span calls);
- a torn final spill line is tolerated, earlier corruption is not;
- trace_report produces the golden Chrome-JSON shape.
"""
import json
import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, io, profiler, telemetry, trace
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.parallel.ps_async import AsyncPSClient, AsyncPSServer
from mxnet_tpu.parallel.resilience import (FaultInjector,
                                           install_fault_injector)
from mxnet_tpu.serve import ServeClient, ServeEngine, ServeServer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools import trace_report  # noqa: E402

pytestmark = pytest.mark.trace


@pytest.fixture
def trace_dir(tmp_path):
    """Tracing scoped to this test: fresh spill dir via override,
    tracing stopped + override cleared on exit."""
    trace.stop_tracing()
    d = str(tmp_path / "tr")
    config.set_override("MXNET_TRACE", d)
    yield d
    trace.stop_tracing()
    config.clear_override("MXNET_TRACE")


@pytest.fixture
def no_injector():
    yield
    install_fault_injector(None)


def _spans(path, name=None):
    recs = trace_report.load(path)
    spans = [r for r in recs if r.get("kind") == "span"]
    if name is None:
        return spans
    return [s for s in spans if s["name"] == name]


def _mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=2)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy(n=96, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float32)
    return X, y


class _Echo:
    """Trivial forward-capable serve model (no compile, no jax)."""

    def forward(self, *arrays):
        return [np.asarray(arrays[0]) * 2.0]


# ---------------------------------------------------------------------------
# span layer
# ---------------------------------------------------------------------------

def test_disabled_mode_is_a_bounded_noop(tmp_path):
    """MXNET_TRACE unset: no tracer, no file, no context — and 100k
    span enters/exits stay cheap enough for hot-path call sites."""
    if os.environ.get("MXNET_TRACE"):
        pytest.skip("MXNET_TRACE set in the environment")
    trace.stop_tracing()
    config.clear_override("MXNET_TRACE")
    assert trace.tracer() is None
    assert not trace.enabled()
    assert trace.current_context() is None
    assert trace.wire_context() is None
    assert trace.start_span("x") is None
    trace.end_span(None)                       # tolerated
    trace.instant("x")
    assert trace.add_span("x", 0.0, 1.0) is None
    t0 = time.perf_counter()
    for _ in range(100_000):
        with trace.span("hot"):
            pass
    assert time.perf_counter() - t0 < 2.0      # ~µs/call, huge slack
    assert trace.stop_tracing() is None


def test_span_nesting_ids_and_attrs(trace_dir):
    with trace.span("root", a=1) as root:
        assert trace.current_context().span_id == root.span_id
        with trace.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        trace.instant("mark", k=2)
        grand = trace.add_span("retro", telemetry.now_ms() - 5.0,
                               telemetry.now_ms(), parent=root, r=3)
        assert grand.trace_id == root.trace_id
    path = trace.stop_tracing()
    recs = trace_report.load(path)
    assert recs[0]["kind"] == "trace_start"
    assert recs[0]["schema"] == trace.TRACE_SCHEMA_VERSION
    by_name = {r["name"]: r for r in recs[1:]}
    assert by_name["root"]["parent"] is None
    assert by_name["root"]["attrs"] == {"a": 1}
    assert by_name["child"]["parent"] == by_name["root"]["span"]
    assert by_name["retro"]["parent"] == by_name["root"]["span"]
    assert by_name["retro"]["dur_us"] >= 4000
    assert by_name["mark"]["kind"] == "instant"
    # deterministic ids: pid-prefixed counter, no uuid/random
    pid = os.getpid()
    for r in recs[1:]:
        assert r["trace"].startswith("%d." % pid)


def test_thread_isolation(trace_dir):
    """Concurrent root spans on different threads land in DIFFERENT
    traces; nesting never crosses threads."""
    ready = threading.Barrier(2)
    results = {}

    def work(tag):
        with trace.span("root-" + tag) as root:
            ready.wait(5)
            with trace.span("child-" + tag) as child:
                results[tag] = (root.trace_id, child.trace_id,
                                child.parent_id, root.span_id)

    threads = [threading.Thread(target=work, args=(t,))
               for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    (ta, ca, pa, ra), (tb, cb, pb, rb) = results["a"], results["b"]
    assert ta == ca and pa == ra
    assert tb == cb and pb == rb
    assert ta != tb


def test_unwind_drops_open_spans(trace_dir):
    sp = trace.start_span("abandoned")
    assert trace.current_context() is not None
    trace.unwind()
    assert trace.current_context() is None
    with trace.span("after"):
        pass
    path = trace.stop_tracing()
    spans = _spans(path)
    assert [s["name"] for s in spans] == ["after"]
    assert spans[0]["parent"] is None
    trace.end_span(sp)                         # tolerated post-unwind


def test_spill_write_failure_disables_with_one_warning(trace_dir,
                                                       caplog):
    class Boom:
        def write(self, *_a):
            raise OSError(28, "No space left on device")

        def flush(self):
            pass

        def close(self):
            pass

    with trace.span("before"):
        pass
    sp = trace.tracer()
    sp._f = Boom()
    with caplog.at_level(logging.WARNING):
        for _ in range(5):
            with trace.span("lost"):
                pass
    warned = [r for r in caplog.records
              if "tracing output disabled" in r.message]
    assert len(warned) == 1
    assert sp._broken


def test_unwritable_destination_disables_with_one_warning(tmp_path,
                                                          caplog):
    """A destination unwritable at STARTUP (lazy auto-start) latches
    tracing off with one warning — never an OSError into the traced
    hot path. An explicit start_tracing() still raises."""
    trace.stop_tracing()
    blocker = tmp_path / "blocked"
    blocker.write_text("a file, not a dir")
    dest = str(blocker / "sub")
    config.set_override("MXNET_TRACE", dest)
    try:
        with caplog.at_level(logging.WARNING):
            for _ in range(3):
                with trace.span("x"):
                    pass
        assert not trace.enabled()
        assert trace.tracer() is None
        warned = [r for r in caplog.records
                  if "tracing disabled" in r.message]
        assert len(warned) == 1
        with pytest.raises(OSError):
            trace.start_tracing(dest)
    finally:
        trace.stop_tracing()
        config.clear_override("MXNET_TRACE")


# ---------------------------------------------------------------------------
# wire propagation: PS (acceptance)
# ---------------------------------------------------------------------------

def test_ps_trace_join_with_retry(trace_dir, no_injector):
    """The fault-injected PS acceptance path, in-process: a dropped
    push replays under retry, and client op span, retry instant,
    backoff span and server handler span all share one trace_id with
    correct parent/child nesting; the export carries flow arrows."""
    srv = AsyncPSServer(host="127.0.0.1", port=0, num_workers=1)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    client = AsyncPSClient("127.0.0.1", srv.port)
    try:
        client.init("w", np.ones(4, np.float32))
        install_fault_injector(FaultInjector("send:drop@1"))
        client.push("w", np.ones(4, np.float32))
        install_fault_injector(None)
        assert np.allclose(client.pull("w"), 1.0)
    finally:
        client.close()
        srv.stop()
    path = trace.stop_tracing()
    recs = trace_report.load(path)
    spans = [r for r in recs if r.get("kind") == "span"]
    push = next(s for s in spans if s["name"] == "ps.op.push")
    handle = next(s for s in spans if s["name"] == "ps.handle.push")
    # one trace across both ends, handler nested under the client op
    assert handle["trace"] == push["trace"]
    assert handle["parent"] == push["span"]
    assert handle["tid"] != push["tid"]
    # the retry is visible in the same trace: instant + backoff span
    retry = next(r for r in recs if r.get("kind") == "instant"
                 and r["name"] == "ps.retry")
    assert retry["trace"] == push["trace"]
    backoff = next(s for s in spans if s["name"] == "retry.backoff")
    assert backoff["trace"] == push["trace"]
    # flow arrows across the thread hop in the merged export
    chrome = trace_report.to_chrome(recs)
    flows = [e for e in chrome["traceEvents"]
             if e.get("ph") in ("s", "f")]
    assert any(e["ph"] == "s" for e in flows)
    assert any(e["ph"] == "f" for e in flows)


@pytest.mark.slow
def test_ps_trace_join_across_processes(tmp_path, trace_dir):
    """Acceptance: a real two-process run — the server writes its own
    spill file, and after merging, ONE trace_id spans both pids with
    the handler span parented under the client op span."""
    srv_dir = str(tmp_path / "srv_trace")
    port_file = str(tmp_path / "port")
    script = (
        "import os\n"
        "os.environ['MXNET_TRACE'] = %r\n"
        "os.environ['MXNET_PS_LINGER'] = '0.1'\n"
        "from mxnet_tpu.parallel.ps_async import AsyncPSServer\n"
        "srv = AsyncPSServer(host='127.0.0.1', port=0, num_workers=1)\n"
        "open(%r, 'w').write(str(srv.port))\n"
        "srv.serve_forever()\n" % (srv_dir, port_file))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", script], env=env)
    try:
        deadline = time.time() + 60
        while not os.path.exists(port_file):
            assert proc.poll() is None, "server process died"
            assert time.time() < deadline, "server never bound"
            time.sleep(0.05)
        time.sleep(0.1)
        port = int(open(port_file).read())
        client = AsyncPSClient("127.0.0.1", port)
        client.init("w", np.ones(4, np.float32))
        client.push("w", np.ones(4, np.float32))
        client.close()
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    client_spill = trace.stop_tracing()
    srv_spills = [os.path.join(srv_dir, f) for f in os.listdir(srv_dir)]
    assert len(srv_spills) == 1
    merged = trace_report.merge([client_spill] + srv_spills)
    spans = [r for r in merged if r.get("kind") == "span"]
    push = next(s for s in spans if s["name"] == "ps.op.push")
    handle = next(s for s in spans if s["name"] == "ps.handle.push")
    assert handle["trace"] == push["trace"]
    assert handle["parent"] == push["span"]
    assert handle["pid"] != push["pid"]        # two real processes
    chrome = trace_report.to_chrome(merged)
    pids = {e["pid"] for e in chrome["traceEvents"] if "pid" in e}
    assert len(pids) >= 2
    assert any(e.get("ph") == "f" for e in chrome["traceEvents"])


# ---------------------------------------------------------------------------
# wire propagation: serve (acceptance)
# ---------------------------------------------------------------------------

def test_serve_trace_join_and_lifecycle(trace_dir):
    """A concurrent serve run: client request span, server handler
    span and the batcher's queue -> pad -> forward -> respond
    lifecycle all share one trace_id (the batcher emits across a
    thread hop — flow arrows in the export)."""
    eng = ServeEngine(_Echo(), buckets=(1, 2, 4), max_wait_ms=2.0,
                      feature_shapes=[(4,)], install_sigterm=False)
    srv = ServeServer(eng)
    clients = [ServeClient(srv.host, srv.port) for _ in range(3)]
    try:
        outs = []
        threads = [threading.Thread(
            target=lambda c=c, i=i: outs.append(
                c.request([np.full((1, 4), i, np.float32)])))
            for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(outs) == 3
    finally:
        for c in clients:
            c.close()
        srv.close()
        eng.close()
    path = trace.stop_tracing()
    recs = trace_report.load(path)
    spans = [r for r in recs if r.get("kind") == "span"]
    reqs = [s for s in spans if s["name"] == "serve.request"]
    assert len(reqs) == 3
    for req in reqs:
        mine = [s for s in spans if s["trace"] == req["trace"]]
        names = {s["name"] for s in mine}
        assert {"serve.request", "serve.handle", "serve.queue",
                "serve.pad", "serve.forward",
                "serve.respond"} <= names
        handle = next(s for s in mine if s["name"] == "serve.handle")
        assert handle["parent"] == req["span"]
    chrome = trace_report.to_chrome(recs)
    assert any(e.get("ph") == "f" for e in chrome["traceEvents"])


# ---------------------------------------------------------------------------
# instrumented fit loops (acceptance)
# ---------------------------------------------------------------------------

def test_trainstep_fit_spans_cross_reference_journal(trace_dir,
                                                     tmp_path):
    """train.step spans carry the journal's step seq, so a trace and a
    telemetry report of the same run cross-reference; wait children
    reconstruct the step's data/window breakdown."""
    telemetry.close_journal()
    config.set_override("MXNET_TELEMETRY", str(tmp_path / "tele"))
    try:
        X, y = _toy()
        step = make_train_step(_mlp())
        train = io.NDArrayIter(X, y, batch_size=32)
        step.fit(train, num_epoch=1, initializer=Xavier(), lr=0.1)
        jpath = telemetry.close_journal()
    finally:
        config.clear_override("MXNET_TELEMETRY")
    path = trace.stop_tracing()
    steps = _spans(path, "train.step")
    assert len(steps) == 3
    journal_steps = {r["step"] for r in
                     (json.loads(ln) for ln in open(jpath))
                     if r.get("kind") == "step"}
    for s in steps:
        assert s["attrs"]["loop"] == "trainstep"
        assert s["attrs"]["step"] in journal_steps
        kids = [k for k in _spans(path)
                if k.get("parent") == s["span"]]
        assert {"step.data_wait", "step.window_wait"} <= \
            {k["name"] for k in kids}


def test_module_fit_spans(trace_dir):
    X, y = _toy()
    train = io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(train, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    path = trace.stop_tracing()
    steps = _spans(path, "train.step")
    assert len(steps) == 3
    assert all(s["attrs"]["loop"] == "module" for s in steps)
    # prepare()'s staging rides the step too: under the live
    # step.data_wait phase, itself a child of the step span
    stages = _spans(path, "module.stage")
    assert stages
    step_ids = {s["span"] for s in steps}
    waits = {s["span"] for s in _spans(path, "step.data_wait")
             if s["parent"] in step_ids}
    assert any(s["parent"] in waits for s in stages)


def test_trace_adds_zero_host_syncs(trace_dir):
    """Acceptance: tracing on vs off — the instrumented epoch performs
    the IDENTICAL number of blocking host syncs (tracing is host wall
    clock + file appends only)."""
    X, y = _toy()
    step = make_train_step(_mlp())
    train = io.NDArrayIter(X, y, batch_size=32)
    # warm while tracing is ON (fixture): compiles included
    state, _ = step.fit(train, num_epoch=1, initializer=Xavier(),
                        lr=0.1)
    base = profiler.host_sync_count()
    state, _ = step.fit(train, num_epoch=1, state=state, lr=0.1)
    syncs_on = profiler.host_sync_count() - base

    trace.stop_tracing()
    config.clear_override("MXNET_TRACE")
    base = profiler.host_sync_count()
    state, _ = step.fit(train, num_epoch=1, state=state, lr=0.1)
    syncs_off = profiler.host_sync_count() - base
    assert syncs_on == syncs_off, (syncs_on, syncs_off)


def test_guardrail_masked_step_instant(trace_dir, no_injector):
    """A nan@N-injected masked step annotates the trace with an
    instant event inside the run's spans."""
    X, y = _toy()
    install_fault_injector(FaultInjector("nan@2"))
    step = make_train_step(_mlp())
    train = io.NDArrayIter(X, y, batch_size=32)
    step.fit(train, num_epoch=1, initializer=Xavier(), lr=0.5)
    install_fault_injector(None)
    path = trace.stop_tracing()
    recs = trace_report.load(path)
    marks = [r for r in recs if r.get("kind") == "instant"
             and r["name"] == "guardrail.masked_step"]
    assert marks
    assert marks[0]["attrs"]["total"] >= 1
    # a mark whose flag drained inside a step's window wait parents to
    # that step's trace; one drained at the epoch-end flush to that
    # flush's own phase — both are valid placements
    step_traces = {s["trace"] for s in recs
                   if s.get("kind") == "span"
                   and s["name"] in ("train.step", "train.epoch_drain")}
    for m in marks:
        assert m["trace"] in step_traces


# ---------------------------------------------------------------------------
# spill format + report (golden shape)
# ---------------------------------------------------------------------------

def test_torn_spill_line_tolerated(trace_dir):
    with trace.span("a"):
        pass
    path = trace.stop_tracing()
    n = len(trace_report.load(path))
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "sp')       # crash signature
    assert len(trace_report.load(path)) == n
    # corruption anywhere earlier is NOT tolerated
    bad = path + ".bad"
    lines = open(path).read().splitlines()
    lines[0] = "not json"
    with open(bad, "w") as f:
        f.write("\n".join(lines))
    with pytest.raises(ValueError, match="corrupt"):
        trace_report.load(bad)
    # unknown schema refused
    v2 = path + ".v2"
    with open(v2, "w") as f:
        f.write('{"v": 99, "kind": "trace_start"}\n')
    with pytest.raises(ValueError, match="schema"):
        trace_report.load(v2)


def test_trace_report_golden_shape(trace_dir):
    with trace.span("root", a=1):
        with trace.span("inner"):
            pass
        trace.instant("blip")
    path = trace.stop_tracing()
    recs = trace_report.load(path)
    chrome = trace_report.to_chrome(recs)
    assert set(chrome) == {"traceEvents", "displayTimeUnit"}
    evs = chrome["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert {"M", "X", "i"} <= phs
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"root", "inner"}
    for e in xs.values():
        assert {"ts", "dur", "pid", "tid", "args"} <= set(e)
        assert "trace" in e["args"] and "span" in e["args"]
    assert xs["root"]["args"]["a"] == 1
    # same-thread nesting draws NO flow arrow
    assert not [e for e in evs if e["ph"] in ("s", "f")]
    names = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in names}
    summary = trace_report.critical_path(recs)
    assert "root" in summary and "inner" in summary
    assert "% of root" in summary
