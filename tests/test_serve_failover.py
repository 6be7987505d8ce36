"""Fleet survives replica death (docs/robustness.md §fleet failure
semantics).

Load-bearing acceptance gates:

* a pinned replica dying mid-generate (transport fault + failed
  probe) replays the request on a survivor token-for-token — greedy
  and seeded alike — and the admit-id dedup table makes a replay onto
  a replica that already admitted it exactly-once;
* a migrating ``recycle()`` / SIGTERM evacuation exports every active
  decode session (KV rows + emitted tokens + PRNG progress) and the
  resumed stream emits the remaining tokens bit-identically — f32,
  int8 (quantize_kv) and GQA caches included;
* the router never wedges on its own plumbing: the poller survives a
  ``poll_now`` exception, and a decode-role drain timeout fails OPEN
  to SUSPECT (revived by the next successful poll), never stranding
  the replica DRAINING.
"""
import signal
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator, replay_key
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.parallel.resilience import (FaultInjector,
                                           install_fault_injector)
from mxnet_tpu.serve import (ContinuousDecoder, ServeRouter,
                             ServeServer, SessionEvacuated)

pytestmark = pytest.mark.serve

V, L, H, DIM, T = 50, 2, 2, 32, 24


def _params(seed=0, num_kv_heads=None):
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 num_kv_heads=num_kv_heads)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(seed)
    return step.init_state(Xavier(), {"data": (2, 12),
                                      "softmax_label": (2, 12)})[0]


@pytest.fixture(scope="module")
def params():
    return _params()


def _gen(params, batch_size, **kw):
    return Generator(params, V, T, num_layers=L, num_heads=H, dim=DIM,
                     batch_size=batch_size, **kw)


def _cval(name):
    e = telemetry.snapshot().get(name)
    return int(e["value"]) if e else 0


def _wait(pred, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError("timed out waiting for %s" % what)


class _Fleet:
    """Two real decode replicas behind a poll-less router —
    deterministic: tests drive poll_now() themselves."""

    def __init__(self, params, **genkw):
        self.decoders = [ContinuousDecoder(_gen(params, 2, **genkw))
                         for _ in range(2)]
        self.servers = [ServeServer(d) for d in self.decoders]
        self.router = ServeRouter(poll_ms=0)
        for i, s in enumerate(self.servers):
            self.router.add_replica(s.host, s.port,
                                    name="replica%d" % i)
        self.router.poll_now()

    def decoder_of(self, name):
        return self.decoders[int(name[-1])]

    def close(self):
        self.router.close()
        for s in self.servers:
            s.close()
        for d in self.decoders:
            d.close()


# -- (a) token-exact generate failover -----------------------------------
class TestFailover:
    @pytest.mark.parametrize("sampling", [
        {"temperature": 0.0},
        pytest.param({"temperature": 0.8, "top_k": 8, "seed": 3},
                     marks=pytest.mark.slow)], ids=["greedy",
                                                    "seeded"])
    def test_dead_pin_replays_on_survivor_token_exact(self, params,
                                                      sampling):
        """Transport fault + failed probe on the pinned replica =
        dead: the retained recovery record replays on the survivor,
        byte-equal to the unfaulted run; the pin moves."""
        p = np.arange(1, 5)
        want = _gen(params, 1).generate(p[None], 6, eos_id=0,
                                        **sampling)[0]
        f = _Fleet(params)
        f0, r0 = (_cval("serve.router.failovers"),
                  _cval("serve.router.replays"))
        try:
            out = f.router.generate(p, 6, eos_id=0, session="s",
                                    **sampling)
            np.testing.assert_array_equal(out, want)
            pin = f.router.sessions()["s"]
            idx = int(pin[-1])
            # data AND control transport dead = the process is gone
            install_fault_injector(FaultInjector(
                "router%d_send:drop@1x*;router%d_ctl_send:drop@1x*"
                % (idx, idx)))
            try:
                out2 = f.router.generate(p, 6, eos_id=0, session="s",
                                         **sampling)
            finally:
                install_fault_injector(None)
            np.testing.assert_array_equal(out2, want)
            assert f.router.sessions()["s"] != pin
            assert _cval("serve.router.failovers") == f0 + 1
            assert _cval("serve.router.replays") == r0 + 1
        finally:
            f.close()

    def test_transient_fault_replays_exactly_once(self, params):
        """A reply lost AFTER the replica admitted (recv drop, probe
        fine): the replay carries the same admit id, the dedup table
        rides the original admission — admitted moves by ONE."""
        p = np.arange(2, 7)
        want = _gen(params, 1).generate(p[None], 5, eos_id=0,
                                        temperature=0.8, top_k=8,
                                        seed=11)[0]
        f = _Fleet(params)
        r0 = _cval("serve.router.replays")
        try:
            out = f.router.generate(p, 5, eos_id=0, temperature=0.8,
                                    top_k=8, seed=11, session="s")
            np.testing.assert_array_equal(out, want)
            pin = f.router.sessions()["s"]
            dec = f.decoder_of(pin)
            before = dec.stats()
            install_fault_injector(FaultInjector(
                "router%d_recv:drop@1" % int(pin[-1])))
            try:
                out2 = f.router.generate(p, 5, eos_id=0,
                                         temperature=0.8, top_k=8,
                                         seed=11, session="s")
            finally:
                install_fault_injector(None)
            np.testing.assert_array_equal(out2, want)
            after = dec.stats()
            assert after["admitted"] - before["admitted"] == 1
            assert after["deduped"] - before["deduped"] == 1
            assert f.router.sessions()["s"] == pin   # same replica
            assert _cval("serve.router.replays") == r0 + 1
        finally:
            f.close()

    def test_dedup_returns_same_future(self, params):
        """Decoder-level exactly-once contract: the same admit id
        resubmitted returns the ORIGINAL future, no second slot."""
        with _gen(params, 2).serving_decoder() as dec:
            f1 = dec.submit(np.arange(1, 5), 4, eos_id=0,
                            admit_id="cid:1")
            f2 = dec.submit(np.arange(1, 5), 4, eos_id=0,
                            admit_id="cid:1")
            assert f1 is f2
            f1.result(120.0)
            st = dec.stats()
            assert st["deduped"] == 1
            assert st["admitted"] == 1


# -- (b) live session migration ------------------------------------------
class TestMigration:
    def _evacuate_resume_parity(self, params, **genkw):
        """Core migration invariant, no router: evacuate mid-decode,
        resume the exported state on a SECOND pool, remaining tokens
        bit-identical; the PRNG re-derives by advancing the same
        splits."""
        single = _gen(params, 1, **genkw)
        p = np.arange(1, 6)
        want = single.generate(p[None], 8, temperature=0.8, top_k=8,
                               seed=7)[0]
        d1 = _gen(params, 2, **genkw).serving_decoder()
        d2 = _gen(params, 2, **genkw).serving_decoder()
        try:
            fut = d1.submit(p, 8, temperature=0.8, top_k=8, seed=7)
            _wait(lambda: len(fut.emitted) >= 3, what="3 emitted")
            assert d1.evacuate() == 1
            with pytest.raises(SessionEvacuated) as ei:
                fut.result(10.0)
            state = ei.value.state
            k = len(state["emitted"])
            assert k >= 3
            # export position = prompt + emitted - 1 (the last emitted
            # token is still pending, not yet fed)
            assert state["kv_blob"]["pos"] == len(p) + k - 1
            got = d2.submit(p, 8, temperature=0.8, top_k=8, seed=7,
                            resume=state).result(120.0)
            np.testing.assert_array_equal(got, want)
            st = d2.stats()
            assert st["resumed"] == 1
            assert st["prefills"] == 0    # scatter-only admission
            assert d1.stats()["evacuated"] == 1
            assert d1.stats()["finished"] == 0
        finally:
            d1.close()
            d2.close()

    def test_evacuate_resume_parity_f32(self, params):
        self._evacuate_resume_parity(params)

    @pytest.mark.slow
    def test_evacuate_resume_parity_bf16(self, params):
        self._evacuate_resume_parity(params, dtype="bfloat16")

    @pytest.mark.slow
    def test_evacuate_resume_parity_int8_kv_gqa(self):
        params = _params(seed=5, num_kv_heads=1)
        self._evacuate_resume_parity(params, quantize_kv=True,
                                     num_kv_heads=1)

    def test_replay_key_advances_splits(self):
        """replay_key(seed, k) == the key generate() holds after k
        picks — the invariant the resume path rests on."""
        import jax
        key = jax.random.PRNGKey(7)
        for k in range(4):
            np.testing.assert_array_equal(
                np.asarray(replay_key(7, k)), np.asarray(key))
            key, _ = jax.random.split(key)

    def test_migrating_recycle_completes_without_drain(self, params):
        """recycle() of a decode replica with an active session
        migrates it to the survivor mid-sequence (bounded by
        export+import, not by the sequence finishing) and the
        completed row is bit-identical."""
        p = np.arange(1, 4)
        want = _gen(params, 1).generate(p[None], 12, temperature=0.8,
                                        top_k=8, seed=9)[0]
        f = _Fleet(params)
        m0, e0 = (_cval("serve.router.migrations"),
                  _cval("serve.router.evacuations"))
        out = {}
        try:
            t = threading.Thread(target=lambda: out.update(
                row=f.router.generate(p, 12, temperature=0.8,
                                      top_k=8, seed=9, session="m")))
            t.start()
            _wait(lambda: "m" in f.router.sessions()
                  and any(d.stats()["active"]
                          for d in f.decoders), what="admission")
            victim = f.router.sessions()["m"]
            f.router.recycle(victim)
            t.join(60.0)
            assert not t.is_alive()
            np.testing.assert_array_equal(out["row"], want)
            # the victim exported the session MID-FLIGHT (only active
            # sessions export — recycle did not wait for the sequence
            # to finish), and exactly one resume completed it. Where
            # the resume lands is a race the contract doesn't pin:
            # usually the survivor, but a fast readmission makes the
            # recycled victim itself a legal target.
            assert f.decoder_of(victim).stats()["evacuated"] == 1
            assert sum(d.stats()["resumed"] for d in f.decoders) == 1
            assert sum(d.stats()["finished"] for d in f.decoders) == 1
            assert _cval("serve.router.migrations") == m0 + 1
            assert _cval("serve.router.evacuations") == e0 + 1
        finally:
            f.close()

    @pytest.mark.slow
    def test_sigterm_evacuates_instead_of_killing(self, params):
        """A polite SIGTERM on a decode replica exports its active
        sessions (the caller gets SessionEvacuated, resumable
        elsewhere) instead of killing them, and drains the pool."""
        # pin a benign base handler first: GracefulShutdown CHAINS
        # whatever is installed, and an earlier test in a full-suite
        # run may leave a process-exiting handler behind that a real
        # SIGTERM would otherwise reach
        prev = signal.signal(signal.SIGTERM, lambda *_a: None)
        d1 = ContinuousDecoder(_gen(params, 2), install_sigterm=True)
        d2 = _gen(params, 2).serving_decoder()
        try:
            p = np.arange(1, 6)
            want = _gen(params, 1).generate(p[None], 8,
                                            temperature=0.8, top_k=8,
                                            seed=4)[0]
            fut = d1.submit(p, 8, temperature=0.8, top_k=8, seed=4)
            _wait(lambda: len(fut.emitted) >= 2, what="2 emitted")
            import os
            os.kill(os.getpid(), signal.SIGTERM)
            with pytest.raises(SessionEvacuated) as ei:
                fut.result(10.0)
            got = d2.submit(p, 8, temperature=0.8, top_k=8, seed=4,
                            resume=ei.value.state).result(120.0)
            np.testing.assert_array_equal(got, want)
            # SIGTERM = the process is going away: pool drains
            _wait(lambda: d1.stats()["evacuated"] == 1,
                  what="evacuation stat")
            from mxnet_tpu.serve.engine import EngineClosed
            with pytest.raises(EngineClosed):
                d1.submit(p, 4)
        finally:
            d1.close()
            d2.close()
            signal.signal(signal.SIGTERM, prev)

    def test_resume_rejects_wrong_prompt_and_handoff_mix(self, params):
        with _gen(params, 2).serving_decoder() as d1, \
                _gen(params, 2).serving_decoder() as d2:
            fut = d1.submit(np.arange(1, 6), 8, temperature=0.8,
                            top_k=8, seed=7)
            _wait(lambda: len(fut.emitted) >= 2, what="2 emitted")
            d1.evacuate()
            with pytest.raises(SessionEvacuated) as ei:
                fut.result(10.0)
            state = ei.value.state
            with pytest.raises(ValueError, match="prompt"):
                d2.submit(np.arange(2, 7), 8, resume=state)
            # args must RESTATE the migrated request — a silently
            # diverging resume is a loud error instead
            with pytest.raises(ValueError, match="restate"):
                d2.submit(np.arange(1, 6), 8, temperature=0.8,
                          top_k=4, seed=7, resume=state)
            with pytest.raises(ValueError, match="restate"):
                d2.submit(np.arange(1, 6), 8, resume=state)
            with pytest.raises(ValueError, match="mutually"):
                d2.submit(np.arange(1, 6), 8, resume=state,
                          handoff={"first_token": 1, "kv_blob": None,
                                   "pos": 5})


# -- router plumbing robustness (satellites) -----------------------------
class _StuckEngine:
    """Engine-shaped stub: a decode-role replica whose engine forever
    reports one in-flight sequence (a wedged drain, distilled)."""

    role = "decode"

    def __init__(self, in_flight=1):
        self.in_flight = in_flight

    def introspect(self):
        return {"in_flight": self.in_flight, "queue_depth": 0,
                "draining": False, "warmed": [], "buckets": []}

    def evacuate(self):
        return 0                          # nothing active to export


class TestRouterPlumbing:
    def test_poller_survives_poll_now_exception(self):
        router = ServeRouter(poll_ms=5)
        try:
            calls = {"n": 0, "after_failure": 0}
            orig = router.poll_now

            def flaky():
                calls["n"] += 1
                if calls["n"] <= 3:
                    raise RuntimeError("injected poll failure")
                calls["after_failure"] += 1
                return orig()

            router.poll_now = flaky
            _wait(lambda: calls["after_failure"] >= 2,
                  what="poller recovery")
            assert router._poll_thread.is_alive()
        finally:
            router.close()

    def test_decode_drain_timeout_fails_open_to_suspect(self):
        """A decode-role replica that cannot drain parks SUSPECT
        (never stranded DRAINING), replicas_live drops, and the next
        successful poll revives it."""
        stuck, idle = _StuckEngine(1), _StuckEngine(0)
        s1, s2 = ServeServer(stuck), ServeServer(idle)
        router = ServeRouter(poll_ms=0)
        try:
            router.add_replica(s1.host, s1.port, name="stuck")
            router.add_replica(s2.host, s2.port, name="idle")
            router.poll_now()
            assert _cval("serve.router.replicas_live") == 2
            with pytest.raises(TimeoutError, match="drain budget"):
                router.recycle("stuck", timeout=0.3, warm=False)
            reps = router.replicas()
            assert reps["stuck"]["state"] == "suspect"
            assert _cval("serve.router.replicas_live") == 1
            stuck.in_flight = 0           # the wedge clears
            router.poll_now()             # ...and the poll revives it
            assert router.replicas()["stuck"]["state"] == "live"
            assert _cval("serve.router.replicas_live") == 2
        finally:
            router.close()
            s1.close()
            s2.close()


# -- MXNET_FAULT_SPEC validation + the kill family (satellites) ----------
class TestFaultSpecValidation:
    def test_unknown_wire_point_raises(self):
        with pytest.raises(ValueError, match="unknown injection point"):
            FaultInjector("serve_snd:drop@1")

    def test_router_family_points_accepted(self):
        FaultInjector("router3_ctl_recv:drop@1;router0_send:delay@2:0.1")

    def test_kill_as_wire_point_rejected(self):
        # `kill1:drop@2` parses as a WIRE rule naming point "kill1" —
        # the validation catches it (the kill family is step-indexed)
        with pytest.raises(ValueError, match="unknown injection point"):
            FaultInjector("kill1:drop@2")

    def test_bad_rule_still_actionable(self):
        with pytest.raises(ValueError,
                           match="bad MXNET_FAULT_SPEC rule"):
            FaultInjector("kill@")

    def test_kill_family_parses_and_ticks(self):
        inj = FaultInjector("kill1@3")
        assert [inj.on_chaos_tick("kill1") for _ in range(4)] == \
            [False, False, True, False]
        # distinct points count independently
        inj = FaultInjector("kill0@1;kill2@2x2")
        assert inj.on_chaos_tick("kill0") is True
        assert [inj.on_chaos_tick("kill2") for _ in range(4)] == \
            [False, True, True, False]
