"""The fleet router (mxnet_tpu/serve/router.py): least-loaded
dispatch, decode session affinity, shed-and-retry, suspect/reroute,
and zero-drop rolling restarts.

Load-bearing acceptance gates:
- Shed-and-retry: an Overloaded from one replica lands the request on
  the next replica, with ONE trace_id spanning router AND both
  replicas; Overloaded reaches the caller only when every live
  replica shed.
- Dead-replica reroute: an injected always-drop transport to one
  replica marks it suspect and reroutes — every request still
  succeeds, and a healthy poll revives the replica.
- Rolling-restart zero-drop: a closed-loop client sweep running while
  EVERY replica is recycled once (drain -> restart -> re-warm ->
  readmit) observes exactly one successful response per request — no
  drops, no client-visible errors, no sleeps-as-sync (the drain waits
  on the router's in-flight condition + the stats frame).
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, telemetry, trace
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.parallel.resilience import (FaultInjector, RetryPolicy,
                                           install_fault_injector)
from mxnet_tpu.predictor import Predictor
from mxnet_tpu.serve import (EngineClosed, Overloaded, ReplicaState,
                             ServeClient, ServeEngine, ServeRouter,
                             ServeServer)

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


class _Slow:
    """Forward wrapper with a fixed per-forward delay — makes load
    observable without depending on model speed."""

    def __init__(self, pred, delay):
        self._pred = pred
        self.delay = delay

    def forward(self, *arrays):
        if self.delay:
            time.sleep(self.delay)
        return self._pred.forward(*arrays)


class _DecodeCapable(ServeEngine):
    """An engine whose introspection reports decode slot headroom —
    the signal a decode-capable replica publishes and the router's
    session placement consumes."""

    def __init__(self, *args, free_slots=0, **kwargs):
        super().__init__(*args, **kwargs)
        self.free_slots = free_slots

    def introspect(self):
        out = super().introspect()
        out["decode_free_slots"] = self.free_slots
        return out


class _Fleet:
    """N in-process replicas (engine + ServeServer) behind one router
    — the whole fleet in one test process, every wire real."""

    def __init__(self, pred, n, engine_cls=ServeEngine, delays=None,
                 caps=None, buckets=(1, 2, 4), router_kw=None,
                 engine_kw=None):
        self.pred = pred
        self.buckets = buckets
        self.engine_cls = engine_cls
        self.engine_kw = engine_kw or {}
        self.engines, self.servers = [], []
        for i in range(n):
            self._build(i, (delays or {}).get(i, 0.0),
                        (caps or {}).get(i))
        self.router = ServeRouter(poll_ms=0, **(router_kw or {}))
        self.names = [
            self.router.add_replica(s.host, s.port, name="r%d" % i)
            for i, s in enumerate(self.servers)]
        self.router.poll_now()

    def _build(self, i, delay, cap):
        kw = dict(self.engine_kw)
        if cap is not None:
            kw["queue_cap"] = cap
        model = _Slow(self.pred, delay) if delay else self.pred
        eng = self.engine_cls(model, buckets=self.buckets,
                              max_wait_ms=0.0,
                              feature_shapes=[(FEAT,)],
                              install_sigterm=False, **kw)
        srv = ServeServer(eng)
        if i < len(self.engines):
            self.engines[i], self.servers[i] = eng, srv
        else:
            self.engines.append(eng)
            self.servers.append(srv)
        return srv

    def restarter(self, i, delay=0.0, cap=None):
        """An in-process restart hook: drain+close the old replica,
        build a fresh one, hand its address back to the router."""
        def restart():
            self.servers[i].close()
            self.engines[i].close()
            srv = self._build(i, delay, cap)
            return (srv.host, srv.port)
        return restart

    def close(self):
        self.router.close()
        for s in self.servers:
            s.close()
        for e in self.engines:
            e.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class TestRetryPolicyHook:
    def test_on_fatal_reroutes_without_weakening_fast_fail(self):
        """Satellite: RetryPolicy.run(on_fatal=) — a fatal error
        retries only when the hook approves; without the hook the
        fast-fail contract is byte-identical."""
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise Overloaded("shed")
            return "ok"

        pol = RetryPolicy(max_retries=5, base_delay=0.001)
        # no hook: fatal raises on the FIRST call (fast fail)
        with pytest.raises(Overloaded):
            pol.run(flaky)
        assert len(calls) == 1
        # hook approves: retried until success, same budget
        calls.clear()
        assert pol.run(flaky, on_fatal=lambda e: True) == "ok"
        assert len(calls) == 3
        # hook declines: fast fail preserved
        calls.clear()
        with pytest.raises(Overloaded):
            pol.run(flaky, on_fatal=lambda e: False)
        assert len(calls) == 1
        # the hook is never consulted for TRANSIENT errors
        seen = []

        def transient_once():
            seen.append(1)
            if len(seen) < 2:
                raise ConnectionError("blip")
            return "ok"

        assert pol.run(transient_once,
                       on_fatal=lambda e: pytest.fail(
                           "on_fatal consulted for a transient "
                           "error")) == "ok"


class TestLeastLoaded:
    def test_skew_away_from_slow_replica(self, pred):
        """A slowed replica accumulates in-flight and the router
        routes around it: the fast replica serves the bulk."""
        with _Fleet(pred, 2, delays={0: 0.05}) as f:
            x = np.zeros((1, FEAT), np.float32)
            f.router.infer(x)            # both candidates warm paths

            def client():
                for _ in range(5):
                    f.router.infer(x)

            ts = [threading.Thread(target=client) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            reps = f.router.replicas()
            slow = reps["r0"]["dispatched"]
            fast = reps["r1"]["dispatched"]
        assert fast > slow, (slow, fast)
        assert slow >= 1                 # the slow one still serves

    def test_warm_bucket_preference(self, pred):
        """With equal load, a request prefers the replica whose
        bucket for its size is WARMED — a cold replica never costs a
        live request an XLA compile while a warm one is free."""
        with _Fleet(pred, 2) as f:
            # warm only replica 1 (index order would otherwise send
            # the request to r0)
            f.engines[1].warmup()
            f.router.poll_now()
            x = np.zeros((1, FEAT), np.float32)
            f.router.infer(x)
            reps = f.router.replicas()
            assert reps["r1"]["dispatched"] == 1
            assert reps["r0"]["dispatched"] == 0

    def test_stats_aggregation(self, pred):
        """router.stats() sums the fleet; introspect() adds the
        per-replica detail the stats frame ships."""
        with _Fleet(pred, 3) as f:
            x = np.zeros((1, FEAT), np.float32)
            for _ in range(6):
                f.router.infer(x)
            st = f.router.stats()
            assert st["replicas"] == 3 and st["live"] == 3
            assert st["dispatched"] == 6 and st["in_flight"] == 0
            intro = f.router.introspect()
            assert intro["role"] == "router"
            assert set(intro["per_replica"]) == {"r0", "r1", "r2"}
            assert sum(r["dispatched"]
                       for r in intro["per_replica"].values()) == 6
            # the fleet front end answers the same stats frame any
            # replica does — clients cannot tell a router apart
            with ServeServer(f.router) as front:
                c = ServeClient(front.host, front.port,
                                retry=RetryPolicy(base_delay=0.01))
                got = c.stats()
                c.close()
            assert got["engine"]["role"] == "router"
            assert set(got["engine"]["per_replica"]) == \
                {"r0", "r1", "r2"}


class TestSessionAffinity:
    def test_pin_and_turnover(self, pred):
        """New sessions land on the replica with the most free decode
        slots; every subsequent request of the session sticks to the
        pin; releasing the session (slot freed) lets it re-place on
        the new most-free replica."""
        with _Fleet(pred, 2, engine_cls=_DecodeCapable) as f:
            f.engines[0].free_slots = 1
            f.engines[1].free_slots = 4
            f.router.poll_now()
            x = np.zeros((1, FEAT), np.float32)
            f.router.infer(x, session="a")
            assert f.router.sessions()["a"] == "r1"
            # load the pin's replica: the session STAYS (affinity
            # beats least-loaded)
            for _ in range(4):
                f.router.infer(x, session="a")
            assert f.router.sessions()["a"] == "r1"
            assert f.router.replicas()["r1"]["dispatched"] == 5
            # slot turnover: r1 fills up, r0 frees — a NEW session
            # goes to r0
            f.engines[0].free_slots = 4
            f.engines[1].free_slots = 0
            f.router.poll_now()
            f.router.infer(x, session="b")
            assert f.router.sessions()["b"] == "r0"
            # release -> the id re-places like a new session
            assert f.router.release_session("a")
            f.router.infer(x, session="a")
            assert f.router.sessions()["a"] == "r0"

    def test_session_rides_the_wire(self, pred):
        """The session id crosses the front-end wire (an extra payload
        key old servers ignore) and drives the router's pin — remote
        clients get affinity without a new protocol."""
        with _Fleet(pred, 2, engine_cls=_DecodeCapable) as f:
            f.engines[1].free_slots = 4
            f.router.poll_now()
            x = np.zeros((1, FEAT), np.float32)
            with ServeServer(f.router) as front:
                c = ServeClient(front.host, front.port,
                                retry=RetryPolicy(base_delay=0.01))
                c.request([x], session="w")
                c.request([x], session="w")
                c.close()
            assert f.router.sessions()["w"] == "r1"
            assert f.router.replicas()["r1"]["dispatched"] == 2
            # and a session id against a BARE replica is harmlessly
            # ignored (single engine: nothing to route)
            c2 = ServeClient(f.servers[0].host, f.servers[0].port,
                             retry=RetryPolicy(base_delay=0.01))
            assert c2.request([x], session="w")[0].shape == \
                (1, CLASSES)
            c2.close()

    @pytest.mark.faults
    def test_fresh_pin_reroutes_on_transport_fault(self, pred,
                                                   no_injector):
        """A SPECULATIVE pin (placed by the failing dispatch itself)
        must not chain retries back to the dead replica through the
        pinned-branch fast path — the pin drops and the session
        re-places on a live replica."""
        with _Fleet(pred, 2, engine_cls=_DecodeCapable) as f:
            f.engines[0].free_slots = 4   # placement favors r0
            f.router.poll_now()
            install_fault_injector(FaultInjector(
                "router0_send:drop@1x*"))
            x = np.zeros((1, FEAT), np.float32)
            out = f.router.infer(x, session="s")   # r0 dead -> r1
            assert out[0].shape == (1, CLASSES)
            assert f.router.sessions()["s"] == "r1"
            assert f.router.replicas()["r0"]["state"] == \
                ReplicaState.SUSPECT
            # and while r0 is suspect, its (stale, attractive) slot
            # stats must not win NEW sessions either
            f.router.infer(x, session="s2")
            assert f.router.sessions()["s2"] == "r1"

    def test_session_cap_evicts_lru(self, pred):
        with _Fleet(pred, 2, router_kw={"session_cap": 2}) as f:
            x = np.zeros((1, FEAT), np.float32)
            for sid in ("a", "b", "c"):
                f.router.infer(x, session=sid)
            assert set(f.router.sessions()) == {"b", "c"}

    def test_established_pin_does_not_reroute_on_shed(self, pred):
        """An ESTABLISHED session sheds to the caller rather than
        silently abandoning its KV slot; a sessionless request (and a
        FRESH speculative pin) in the same state reroutes fine."""
        with _Fleet(pred, 2, engine_cls=_DecodeCapable) as f:
            f.engines[0].free_slots = 4   # sessions place on r0
            f.router.poll_now()
            x = np.zeros((1, FEAT), np.float32)
            f.router.infer(x, session="s")
            assert f.router.sessions()["s"] == "r0"
            f.engines[0]._cap = 0         # r0 now sheds everything
            # established pin: the shed is the caller's backpressure
            # signal, never a silent KV-state abandonment
            with pytest.raises(Overloaded):
                f.router.infer(x, session="s")
            assert f.router.sessions()["s"] == "r0"   # pin intact
            # sessionless traffic reroutes around the full replica
            assert f.router.infer(x)[0].shape == (1, CLASSES)
            # a FRESH pin is speculative (no KV state yet): it may
            # move — the new session lands on r1 despite r0's slots
            f.router.infer(x, session="fresh")
            assert f.router.sessions()["fresh"] == "r1"


class TestShedAndRetry:
    def test_reroute_lands_on_next_replica(self, pred):
        """ACCEPTANCE (shed-and-retry): replica 1 sheds (cap 0),
        the request lands on replica 2; Overloaded reaches the caller
        only when EVERY live replica shed."""
        with _Fleet(pred, 2, caps={0: 0}) as f:
            x = np.zeros((1, FEAT), np.float32)
            out = f.router.infer(x)
            assert out[0].shape == (1, CLASSES)
            reps = f.router.replicas()
            assert reps["r0"]["rerouted_from"] == 1
            assert reps["r1"]["dispatched"] == 1
            assert f.router.stats()["rerouted"] == 1
            # both shed -> typed Overloaded to the caller
            f.engines[1]._cap = 0
            with pytest.raises(Overloaded, match="every live replica"):
                f.router.infer(x)

    def test_one_trace_spans_router_and_both_replicas(self, pred,
                                                      tmp_path):
        """ACCEPTANCE: the shed-and-retry request produces ONE
        trace_id covering the client request, the router dispatch
        (with its reroute instant), and BOTH replicas' handlers."""
        trace.stop_tracing()
        dest = str(tmp_path / "spill.jsonl")
        trace.start_tracing(dest)
        try:
            with _Fleet(pred, 2, caps={0: 0}) as f, \
                    ServeServer(f.router) as front:
                c = ServeClient(front.host, front.port,
                                retry=RetryPolicy(base_delay=0.01))
                c.request([np.zeros((1, FEAT), np.float32)])
                c.close()
        finally:
            path = trace.stop_tracing()
        import json
        records = [json.loads(ln) for ln in open(path)
                   if ln.strip()]
        spans = [r for r in records if r.get("kind") == "span"]
        by_name = {}
        for r in spans:
            by_name.setdefault(r["name"], []).append(r)
        # the remote client's request span roots the trace
        tid = by_name["serve.request"][0]["trace"]
        # router front handler + two replica handlers, same trace
        handles = by_name["serve.handle"]
        assert len(handles) == 3
        assert all(h["trace"] == tid for h in handles)
        dispatch = by_name["serve.router.dispatch"]
        assert len(dispatch) == 1 and dispatch[0]["trace"] == tid
        assert dispatch[0]["attrs"]["reroutes"] == 1
        assert dispatch[0]["attrs"]["replica"] == "r1"
        # three serve.request spans: client->router, router->r0,
        # router->r1 — one trace end to end
        assert len(by_name["serve.request"]) == 3
        assert all(s["trace"] == tid
                   for s in by_name["serve.request"])
        reroutes = [r for r in records
                    if r.get("kind") == "instant"
                    and r["name"] == "serve.router.reroute"]
        assert len(reroutes) == 1 and reroutes[0]["trace"] == tid

    @pytest.mark.faults
    def test_dead_replica_reroute_and_revive(self, pred, no_injector):
        """ACCEPTANCE: an always-drop transport to replica 0 (its
        own injection point family — router0_send) marks it suspect
        and reroutes every request to replica 1; clearing the fault
        and polling revives it."""
        with _Fleet(pred, 2) as f:
            install_fault_injector(FaultInjector(
                "router0_send:drop@1x*"))
            x = np.zeros((1, FEAT), np.float32)
            for _ in range(3):
                assert f.router.infer(x)[0].shape == (1, CLASSES)
            reps = f.router.replicas()
            assert reps["r0"]["state"] == ReplicaState.SUSPECT
            assert reps["r1"]["dispatched"] == 3
            assert telemetry.counter(
                "serve.router.suspected").value >= 1
            # heal the wire: the next poll revives the replica (its
            # control points are a separate family — polls never died)
            install_fault_injector(None)
            f.router.poll_now()
            assert f.router.replicas()["r0"]["state"] == \
                ReplicaState.LIVE


class TestRollingRestart:
    def test_zero_drop_recycle_under_load(self, pred):
        """ACCEPTANCE: a closed-loop sweep runs while EVERY replica
        is recycled once; each request gets exactly one successful
        response — zero drops, zero client-visible errors. No
        sleeps-as-sync: recycle() blocks on the router's in-flight
        condition + the stats frame, the sweep is a fixed request
        count."""
        N_CLIENTS, N_REQ = 6, 18
        with _Fleet(pred, 3, delays={0: 0.002, 1: 0.002, 2: 0.002},
                    engine_kw={"queue_cap": 512}) as f:
            x = np.zeros((1, FEAT), np.float32)
            ok = [0] * N_CLIENTS
            errs = []
            started = threading.Barrier(N_CLIENTS + 1)

            def client(ci):
                started.wait()
                for _ in range(N_REQ):
                    try:
                        out = f.router.infer(x)
                        assert out[0].shape == (1, CLASSES)
                        ok[ci] += 1
                    except Exception as exc:  # noqa: BLE001 — the
                        errs.append(exc)      # test asserts none
                        return

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(N_CLIENTS)]
            for t in ts:
                t.start()
            started.wait()               # sweep provably in flight
            for i, name in enumerate(f.names):
                f.router.recycle(name, restart=f.restarter(i, 0.002))
            for t in ts:
                t.join()
            assert not errs, errs[:3]
            assert sum(ok) == N_CLIENTS * N_REQ
            st = f.router.stats()
            assert st["recycles"] == 3
            reps = f.router.replicas()
            assert all(r["state"] == ReplicaState.LIVE
                       for r in reps.values())
            # re-warm happened: every replica's buckets are warm again
            assert all(sorted(r["stats"]["warmed"]) == [1, 2, 4]
                       for r in reps.values())
            # the sweep's volume all arrived somewhere
            assert sum(r["dispatched"] for r in reps.values()) >= \
                N_CLIENTS * N_REQ

    def test_recycle_refuses_last_live_replica(self, pred):
        with _Fleet(pred, 1) as f:
            with pytest.raises(ValueError, match="no live replica"):
                f.router.recycle("r0")

    def test_recycle_without_restart_rewarns_and_readmits(self, pred):
        """restart=None: drain + re-warm + readmit (config-reload
        shape) — and dispatch EXCLUDES the replica while draining."""
        with _Fleet(pred, 2) as f:
            x = np.zeros((1, FEAT), np.float32)
            f.router.recycle("r0")
            reps = f.router.replicas()
            assert reps["r0"]["state"] == ReplicaState.LIVE
            assert sorted(reps["r0"]["stats"]["warmed"]) == [1, 2, 4]
            assert f.router.stats()["recycles"] == 1
            f.router.infer(x)

    def test_draining_replica_rejects_via_router(self, pred):
        """A replica draining OUTSIDE the router's control (its own
        SIGTERM/close) is observed at dispatch (EngineClosed answer)
        and routed around — via the self-healing polled-stats channel,
        NOT a sticky state flip (a restarted replica readmits on the
        next poll, no recycle() needed)."""
        with _Fleet(pred, 2) as f:
            x = np.zeros((1, FEAT), np.float32)
            f.engines[0].close()          # drains: submits now reject
            out = f.router.infer(x)       # observed + rerouted
            assert out[0].shape == (1, CLASSES)
            reps = f.router.replicas()
            assert reps["r0"]["stats"]["draining"]
            assert reps["r0"]["state"] == ReplicaState.LIVE
            # further requests skip r0 WITHOUT paying a round trip
            f.router.infer(x)
            assert f.router.replicas()["r1"]["dispatched"] == 2
            # the replica restarts itself on the SAME address (its
            # supervisor's job): the next poll readmits it — no
            # operator action, no recycle()
            host, port = f.servers[0].host, f.servers[0].port
            f.servers[0].close()
            f.engines[0] = ServeEngine(
                pred, buckets=f.buckets, max_wait_ms=0.0,
                feature_shapes=[(FEAT,)], install_sigterm=False)
            f.servers[0] = ServeServer(f.engines[0], host=host,
                                       port=port)
            f.router.poll_now()
            assert not f.router.replicas()["r0"]["stats"]["draining"]
            f.engines[1].close()          # r1 drains; r0 must serve
            assert f.router.infer(x)[0].shape == (1, CLASSES)


class TestRouterTelemetry:
    def test_gauges_and_fleet_report(self, pred):
        """The serve.router.* gauges track the fleet, and the
        multi-target --stats fleet table renders one row per
        replica."""
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        "..", "tools"))
        try:
            from telemetry_report import fetch_stats, format_fleet
        finally:
            sys.path.pop(0)
        with _Fleet(pred, 2) as f:
            x = np.zeros((1, FEAT), np.float32)
            for _ in range(4):
                f.router.infer(x)
            assert telemetry.gauge(
                "serve.router.replicas").value == 2
            assert telemetry.gauge(
                "serve.router.replicas_live").value == 2
            rows = [("%s:%d" % (s.host, s.port),
                     fetch_stats("%s:%d" % (s.host, s.port)))
                    for s in f.servers]
            text = format_fleet(rows)
        for s in f.servers:
            assert "%s:%d" % (s.host, s.port) in text
        assert "queue" in text and "warmed" in text
        # a dead target renders as unreachable, not a crash
        text2 = format_fleet(rows + [("127.0.0.1:1", None)])
        assert "unreachable" in text2
