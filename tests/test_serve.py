"""The serving engine (mxnet_tpu/serve/): dynamic batching,
backpressure, drain, the AOT deploy chain, and the TCP front end.

Load-bearing acceptance gates:
- N concurrent clients produce < N engine forwards with mean batch
  fill > 1 (batching is real), and every row matches the in-process
  Predictor bitwise (batching is lossless).
- Every request gets exactly one response — correct payload or typed
  error — under MXNET_FAULT_SPEC drop/delay/disconnect injection on
  the serving wire.
- SIGTERM drains: admitted requests finish, new ones are rejected.
- Predictor.export -> CompiledPredictor served by ServeEngine is
  bitwise-identical to the in-process Predictor at EVERY bucket shape.
"""
import json
import signal
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, telemetry
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.parallel.resilience import (FaultInjector, RetryPolicy,
                                           install_fault_injector)
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serve import (EngineClosed, Overloaded, RequestTimeout,
                             ServeClient, ServeEngine, ServeServer)

pytestmark = pytest.mark.serve

FEAT, CLASSES = 8, 4


def _predictor(seed=7):
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=CLASSES)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(2, FEAT))
    mx.random.seed(seed)
    init = Xavier()
    args = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        arr = mx.nd.zeros(shp)
        init(name, arr)
        args[name] = arr
    return Predictor(net, args, data_names=("data",))


@pytest.fixture(scope="module")
def pred():
    return _predictor()


@pytest.fixture
def no_injector():
    yield
    install_fault_injector(None)


class _Recorder:
    """Forward wrapper recording batch shapes (and optionally
    sleeping, to make queues observable)."""

    def __init__(self, pred, delay=0.0):
        self._pred = pred
        self.delay = delay
        self.shapes = []

    def forward(self, *arrays):
        self.shapes.append(tuple(a.shape[0] for a in arrays))
        if self.delay:
            time.sleep(self.delay)
        return self._pred.forward(*arrays)


class TestBatching:
    def test_concurrent_requests_batch_and_match(self, pred):
        """ACCEPTANCE: 8 concurrent single-row clients -> fewer than 8
        forwards, mean batch fill > 1 (via the serve.batch_fill
        histogram the stats mirror), and every row bitwise-equal to
        the in-process Predictor."""
        rng = np.random.RandomState(0)
        X = rng.standard_normal((8, FEAT)).astype(np.float32)
        want = pred.forward(X)[0].asnumpy()
        fill_before = telemetry.histogram(
            "serve.batch_fill", buckets=telemetry.COUNT_BUCKETS)
        n0, s0 = fill_before.count, fill_before.sum
        with ServeEngine(pred, buckets=(1, 2, 4, 8),
                         max_wait_ms=250.0, install_sigterm=False,
                         feature_shapes=[(FEAT,)]) as eng:
            eng.warmup()
            res = [None] * 8

            def go(i):
                res[i] = eng.infer(X[i:i + 1], timeout=30.0)

            ts = [threading.Thread(target=go, args=(i,))
                  for i in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            st = eng.stats()
        for i in range(8):
            np.testing.assert_array_equal(res[i][0][0], want[i])
        assert st["forwards"] < 8
        assert st["mean_fill"] > 1
        # and the process-global histogram carries the same evidence
        assert fill_before.count - n0 == st["forwards"]
        assert (fill_before.sum - s0) / (fill_before.count - n0) > 1

    def test_bucket_rounding_and_padding(self, pred):
        """A 3-row request pads to the 4-bucket; outputs slice back to
        exactly the request's rows."""
        rec = _Recorder(pred)
        rng = np.random.RandomState(1)
        X = rng.standard_normal((3, FEAT)).astype(np.float32)
        want = pred.forward(X)[0].asnumpy()
        with ServeEngine(rec, buckets=(1, 2, 4, 8), max_wait_ms=0.0,
                         install_sigterm=False) as eng:
            out = eng.infer(X, timeout=30.0)
        assert rec.shapes == [(4,)]
        assert out[0].shape[0] == 3
        np.testing.assert_array_equal(out[0], want)

    def test_oversized_request_rejected(self, pred):
        with ServeEngine(pred, buckets=(1, 2), max_wait_ms=0.0,
                         install_sigterm=False) as eng:
            with pytest.raises(ValueError, match="largest bucket"):
                eng.submit(np.zeros((3, FEAT), np.float32))

    def test_mismatched_rows_rejected_even_first(self, pred):
        """Row-count agreement is validated BEFORE feature shapes are
        learned — a malformed first request must not poison a group."""
        with ServeEngine(pred, buckets=(1, 2, 4), max_wait_ms=0.0,
                         install_sigterm=False) as eng:
            with pytest.raises(ValueError, match="rows must agree"):
                eng.submit(np.zeros((2, FEAT), np.float32),
                           np.zeros((3, FEAT), np.float32))

    def test_warmup_compiles_every_bucket(self, pred):
        rec = _Recorder(pred)
        with ServeEngine(rec, buckets=(1, 2, 4), max_wait_ms=0.0,
                         feature_shapes=[(FEAT,)],
                         install_sigterm=False) as eng:
            eng.warmup()
        assert rec.shapes == [(1,), (2,), (4,)]


class TestBackpressure:
    def test_overload_sheds_typed_and_admitted_complete(self, pred):
        """Queue cap 2 + slow model: floods shed with the typed
        Overloaded; every ADMITTED request still gets its payload
        (exactly one response each, nothing silently dropped)."""
        rec = _Recorder(pred, delay=0.1)
        x = np.zeros((1, FEAT), np.float32)
        with ServeEngine(rec, buckets=(1, 2, 4), max_wait_ms=0.0,
                         queue_cap=2, install_sigterm=False) as eng:
            futs, shed = [], 0
            for _ in range(12):
                try:
                    futs.append(eng.submit(x))
                except Overloaded:
                    shed += 1
            assert shed > 0
            assert eng.stats()["shed"] == shed
            for f in futs:
                assert f.result(30.0)[0].shape == (1, CLASSES)

    def test_deadline_timeout_typed(self, pred):
        """A request whose deadline lapses in the queue gets the typed
        RequestTimeout and never occupies a batch slot."""
        rec = _Recorder(pred, delay=0.25)
        x = np.zeros((1, FEAT), np.float32)
        with ServeEngine(rec, buckets=(1,), max_wait_ms=0.0,
                         install_sigterm=False) as eng:
            first = eng.submit(x)              # occupies the model
            doomed = eng.submit(x, deadline_ms=1.0)
            assert first.result(30.0)
            with pytest.raises(RequestTimeout):
                doomed.result(30.0)
            assert eng.stats()["timeouts"] == 1

    def test_default_deadline_from_env(self, pred):
        config.set_override("MXNET_SERVE_DEADLINE_MS", 1.0)
        try:
            rec = _Recorder(pred, delay=0.25)
            x = np.zeros((1, FEAT), np.float32)
            with ServeEngine(rec, buckets=(1,), max_wait_ms=0.0,
                             install_sigterm=False) as eng:
                first = eng.submit(x, deadline_ms=0)   # explicit: none
                doomed = eng.submit(x)                 # env default
                assert first.result(30.0)
                with pytest.raises(RequestTimeout):
                    doomed.result(30.0)
        finally:
            config.clear_override("MXNET_SERVE_DEADLINE_MS")


class TestDrain:
    def test_close_drains_queued(self, pred):
        rec = _Recorder(pred, delay=0.05)
        x = np.zeros((1, FEAT), np.float32)
        eng = ServeEngine(rec, buckets=(1, 2, 4), max_wait_ms=0.0,
                          install_sigterm=False)
        futs = [eng.submit(x) for _ in range(6)]
        eng.close()
        for f in futs:
            assert f.result(1.0)[0].shape == (1, CLASSES)
        with pytest.raises(EngineClosed):
            eng.submit(x)

    def test_sigterm_drains_and_rejects(self, pred):
        """ACCEPTANCE: SIGTERM through the chaining guardrail handler —
        in-flight requests finish, new submissions are rejected, and
        the previously-installed handler still runs (chained)."""
        rec = _Recorder(pred, delay=0.05)
        x = np.zeros((1, FEAT), np.float32)
        chained = []
        prev = signal.signal(signal.SIGTERM,
                             lambda *_: chained.append(1))
        try:
            eng = ServeEngine(rec, buckets=(1, 2, 4), max_wait_ms=0.0,
                              install_sigterm=True)
            futs = [eng.submit(x) for _ in range(5)]
            signal.raise_signal(signal.SIGTERM)
            for f in futs:
                assert f.result(30.0)[0].shape == (1, CLASSES)
            with pytest.raises(EngineClosed):
                eng.submit(x)
            assert chained == [1]
            eng.close()
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_engine_error_is_the_response(self, pred):
        """A model-side exception becomes each live request's one
        typed response — not a hang, not a silent drop."""
        class Broken:
            def forward(self, *a):
                raise RuntimeError("kaboom")

        with ServeEngine(Broken(), buckets=(1, 2), max_wait_ms=0.0,
                         install_sigterm=False) as eng:
            f = eng.submit(np.zeros((1, FEAT), np.float32))
            with pytest.raises(RuntimeError, match="kaboom"):
                f.result(30.0)


class TestDeployChain:
    def test_compiled_buckets_bitwise_match(self, pred, tmp_path):
        """ACCEPTANCE (satellite): Predictor.export_buckets ->
        ServeEngine.from_export returns BITWISE-identical outputs to
        the in-process Predictor at every configured bucket shape."""
        prefix = str(tmp_path / "m")
        buckets = (1, 2, 4)
        pred.export_buckets(prefix, [(FEAT,)], buckets=buckets)
        rng = np.random.RandomState(5)
        with ServeEngine.from_export(prefix, max_wait_ms=0.0,
                                     install_sigterm=False) as eng:
            eng.warmup()
            for b in buckets:
                X = rng.standard_normal((b, FEAT)).astype(np.float32)
                want = pred.forward(X)[0].asnumpy()
                got = eng.infer(X, timeout=30.0)[0]
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(got, want)

    def test_manifest_contents(self, pred, tmp_path):
        prefix = str(tmp_path / "m")
        path = pred.export_buckets(prefix, [(FEAT,)], buckets=(1, 2))
        with open(path) as f:
            man = json.load(f)
        assert man["buckets"] == [1, 2]
        assert man["feature_shapes"] == [[FEAT]]
        assert man["data_names"] == ["data"]


class TestNet:
    def test_roundtrip_and_typed_errors(self, pred):
        with ServeEngine(pred, buckets=(1, 2, 4), max_wait_ms=0.0,
                         install_sigterm=False) as eng, \
                ServeServer(eng) as srv:
            c = ServeClient(srv.host, srv.port,
                            retry=RetryPolicy(base_delay=0.01))
            assert c.ping()
            x = np.random.RandomState(2).standard_normal(
                (1, FEAT)).astype(np.float32)
            out = c.request([x])
            np.testing.assert_array_equal(
                out[0], pred.forward(x)[0].asnumpy())
            c.close()

    def test_overload_raises_typed_across_wire(self, pred):
        with ServeEngine(pred, buckets=(1,), max_wait_ms=0.0,
                         queue_cap=0, install_sigterm=False) as eng, \
                ServeServer(eng) as srv:
            c = ServeClient(srv.host, srv.port,
                            retry=RetryPolicy(base_delay=0.01))
            with pytest.raises(Overloaded):
                c.request([np.zeros((1, FEAT), np.float32)])
            c.close()

    def test_closed_engine_raises_typed_across_wire(self, pred):
        eng = ServeEngine(pred, buckets=(1,), max_wait_ms=0.0,
                          install_sigterm=False)
        eng.close()
        with ServeServer(eng) as srv:
            c = ServeClient(srv.host, srv.port,
                            retry=RetryPolicy(base_delay=0.01))
            with pytest.raises(EngineClosed):
                c.request([np.zeros((1, FEAT), np.float32)])
            c.close()

    def test_stats_introspection_rpc(self, pred):
        """Satellite: the `stats` frame answers with the telemetry
        registry snapshot + live engine state, via ServeClient.stats()
        AND tools/telemetry_report.py's --stats fetch path."""
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        ".."))
        from tools.telemetry_report import fetch_stats, format_stats

        with ServeEngine(pred, buckets=(1, 2, 4), max_wait_ms=0.0,
                         feature_shapes=[(FEAT,)],
                         install_sigterm=False) as eng, \
                ServeServer(eng) as srv:
            eng.warmup()
            c = ServeClient(srv.host, srv.port,
                            retry=RetryPolicy(base_delay=0.01))
            c.request([np.zeros((1, FEAT), np.float32)])
            stats = c.stats()
            c.close()
            # the standalone tool speaks the wire without the framework
            tool_stats = fetch_stats("%s:%d" % (srv.host, srv.port))
        for got in (stats, tool_stats):
            assert set(got) == {"telemetry", "engine"}
            eng_state = got["engine"]
            assert eng_state["buckets"] == [1, 2, 4]
            assert eng_state["warmed"] == [1, 2, 4]
            assert eng_state["queue_depth"] == 0
            assert eng_state["admitted"] >= 1
            assert "serve.admitted" in got["telemetry"]
        text = format_stats(tool_stats)
        assert "warmed" in text and "serve.admitted" in text

    @pytest.mark.faults
    def test_exactly_one_response_under_faults(self, pred,
                                               no_injector):
        """ACCEPTANCE: drop/delay/disconnect injection on BOTH sides
        of the serving wire — every request still yields exactly one
        correct payload (the client replays on fresh connections;
        inference is pure, so replay is safe)."""
        install_fault_injector(FaultInjector(
            "serve_send:disconnect@3;serve_send:delay@5:0.02;"
            "serve_recv:drop@7;serve_srv_send:disconnect@11;"
            "serve_srv_recv:drop@14"))
        rng = np.random.RandomState(3)
        X = rng.standard_normal((6, FEAT)).astype(np.float32)
        want = pred.forward(X)[0].asnumpy()
        results = {}
        with ServeEngine(pred, buckets=(1, 2, 4), max_wait_ms=1.0,
                         install_sigterm=False) as eng, \
                ServeServer(eng) as srv:
            def client(i):
                c = ServeClient(srv.host, srv.port,
                                retry=RetryPolicy(base_delay=0.01,
                                                  seed=i))
                for j in range(3):
                    out = c.request([X[i:i + 1]])
                    results[(i, j)] = out[0][0]
                c.close()

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(6)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        assert len(results) == 18        # one response per request
        # responses arrive from whatever bucket shape the batcher
        # chose, so allclose (bucket shapes differ at the last ulp);
        # the bitwise gate lives in TestDeployChain at fixed shapes
        for (i, _j), row in results.items():
            np.testing.assert_allclose(row, want[i], rtol=1e-5,
                                       atol=1e-7)


class TestTelemetryReport:
    def test_serving_section_in_report(self, pred, tmp_path):
        """Engine traffic journals serve.* events; the report tool
        renders them as the serving section."""
        import os
        import sys
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            import telemetry_report
        finally:
            sys.path.pop(0)
        telemetry.close_journal()
        d = str(tmp_path / "tele")
        config.set_override("MXNET_TELEMETRY", d)
        try:
            with ServeEngine(pred, buckets=(1, 2, 4),
                             max_wait_ms=0.0, queue_cap=1,
                             install_sigterm=False) as eng:
                x = np.zeros((1, FEAT), np.float32)
                for _ in range(4):
                    eng.infer(x, timeout=30.0)
            path = telemetry.close_journal()
        finally:
            telemetry.close_journal()
            config.clear_override("MXNET_TELEMETRY")
        summary = telemetry_report.summarize(
            telemetry_report.load(path))
        assert summary["serving"]["forwards"] == 4
        assert summary["serving"]["mean_fill"] >= 1.0
        text = telemetry_report.format_report(summary)
        assert "serving:" in text and "mean batch fill" in text
