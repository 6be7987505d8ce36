"""Streaming serving (docs/serving.md §streaming).

Load-bearing acceptance gates:

* a streamed generate delivers EXACTLY the one-shot row's generated
  tail, token-for-token, one ``on_token`` call per token — greedy and
  seeded, f32 and the quantized/reduced-precision caches alike (the
  terminal reply still carries the full row, so every streamed call
  cross-checks itself bitwise);
* the router relays frames as they arrive, never buffering a stream:
  the first token reaches the caller while the decoder is still
  decoding, and mid-stream replica death resumes on a survivor with
  no duplicated and no missing tokens (delivered-prefix replay);
* chunked prefill (MXNET_PREFILL_CHUNK) and batched prefill
  (PrefillEngine coalescing) are bitwise invisible: same tokens, same
  exported KV rows as the monolithic/sequential paths;
* a stalled stream is detected by the per-frame idle timeout
  (MXNET_STREAM_IDLE_TIMEOUT) — never by the old whole-request
  deadline — and recovery delivers every token exactly once.
"""
import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.parallel.ps_async import _recv_msg, _send_msg
from mxnet_tpu.parallel.resilience import (FaultInjector, RetryPolicy,
                                           install_fault_injector)
from mxnet_tpu.serve import (ContinuousDecoder, PrefillEngine,
                             ServeRouter, ServeServer)
from mxnet_tpu.serve.decode import prefill_chunk
from mxnet_tpu.serve.net import ServeClient, stream_idle_timeout
from test_block_diffusion import _in_one_round
from test_serve_failover import _wait

pytestmark = pytest.mark.serve

V, L, H, DIM, T = 50, 2, 2, 32, 24


def _params(seed=0):
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T)
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


GREEDY = {"temperature": 0.0}
SEEDED = {"temperature": 0.8, "top_k": 8, "seed": 3}


# -- (a) streamed == one-shot --------------------------------------------
class TestStreamedEqualsOneShot:
    # the seeded twin re-runs the same wire path for ~4 s — slow tier
    # (sampled streamed==one-shot exactness stays pinned there and in
    # the failover/chaos suites)
    @pytest.mark.parametrize("sampling",
                             [GREEDY,
                              pytest.param(SEEDED,
                                           marks=pytest.mark.slow)],
                             ids=["greedy", "seeded"])
    def test_client_stream_matches_oneshot(self, params, sampling):
        p = np.arange(1, 5)
        want = _gen(params, 1).generate(p[None], 8, eos_id=0,
                                        **sampling)[0]
        dec = ContinuousDecoder(_gen(params, 2))
        srv = ServeServer(dec)
        f0 = _cval("serve.net.stream_frames")
        try:
            with ServeClient(srv.host, srv.port) as cli:
                toks = []
                out = cli.generate(p, 8, eos_id=0,
                                   on_token=toks.append, **sampling)
                np.testing.assert_array_equal(out, want)
                np.testing.assert_array_equal(np.asarray(toks),
                                              want[p.size:])
                assert _cval("serve.net.stream_frames") > f0
        finally:
            srv.close()
            dec.close()

    @pytest.mark.slow
    @pytest.mark.parametrize("genkw", [{"dtype": "bfloat16"},
                                       {"quantize_kv": True}],
                             ids=["bf16", "int8kv"])
    def test_stream_matches_oneshot_reduced_precision(self, params,
                                                      genkw):
        """The frame path carries whatever the cache dtype decodes —
        bf16 and int8-KV streams byte-equal their one-shot twins."""
        p = np.arange(1, 5)
        want = _gen(params, 1, **genkw).generate(p[None], 6, eos_id=0,
                                                 **SEEDED)[0]
        dec = ContinuousDecoder(_gen(params, 2, **genkw))
        srv = ServeServer(dec)
        try:
            with ServeClient(srv.host, srv.port) as cli:
                toks = []
                out = cli.generate(p, 6, eos_id=0,
                                   on_token=toks.append, **SEEDED)
                np.testing.assert_array_equal(out, want)
                np.testing.assert_array_equal(np.asarray(toks),
                                              want[p.size:])
        finally:
            srv.close()
            dec.close()

    def test_generate_stream_iterator(self, params):
        """The pull-style twin: the iterator yields the same tail and
        returns the full row as its StopIteration value."""
        p = np.arange(2, 6)
        want = _gen(params, 1).generate(p[None], 6, eos_id=0)[0]
        dec = ContinuousDecoder(_gen(params, 2))
        srv = ServeServer(dec)
        try:
            with ServeClient(srv.host, srv.port) as cli:
                it = cli.generate_stream(p, 6, eos_id=0)
                got = []
                row = None
                while True:
                    try:
                        got.append(next(it))
                    except StopIteration as stop:
                        row = stop.value
                        break
                np.testing.assert_array_equal(np.asarray(got),
                                              want[p.size:])
                np.testing.assert_array_equal(row, want)
        finally:
            srv.close()
            dec.close()


# -- (b) router relay: unbuffered, failover-exact ------------------------
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


class TestRouterRelay:
    def test_relays_without_buffering(self, params, tmp_path):
        """The first token reaches the caller while the decoder is
        still mid-sequence (finished stays 0 at first frame), and the
        relay/first-token trace events mark the path — a buffering
        relay would deliver everything after the terminal reply."""
        from mxnet_tpu import trace
        from tools.trace_report import load

        p = np.arange(1, 5)
        want = _gen(params, 1).generate(p[None], 16, eos_id=0)[0]
        if want.size - p.size < 4:
            pytest.skip("model finished too fast to observe")
        f = _Fleet(params)
        dest = tmp_path / "trace.jsonl"
        trace.start_tracing(str(dest))
        seen_finished = []
        toks = []

        def on_token(t):
            if not toks:
                seen_finished.append(
                    sum(d.stats()["finished"] for d in f.decoders))
            toks.append(t)

        try:
            out = f.router.generate(p, 16, eos_id=0, session="s",
                                    on_token=on_token)
        finally:
            trace.stop_tracing()
            f.close()
        np.testing.assert_array_equal(out, want)
        np.testing.assert_array_equal(np.asarray(toks),
                                      want[p.size:])
        # at the FIRST frame no sequence had finished anywhere — the
        # frame outran the terminal reply by construction
        assert seen_finished == [0]
        names = {r.get("name") for r in load(str(dest))}
        assert "serve.router.stream_relay" in names
        assert "serve.stream.first_token" in names

    def test_midstream_death_resumes_token_exact(self, params):
        """Replica killed after the second delivered token: the
        delivered-prefix replay resumes on the survivor and the
        caller sees every remaining token exactly once — the
        concatenation byte-equals the fault-free tail."""
        p = np.arange(1, 5)
        sampling = {"temperature": 0.8, "top_k": 8, "seed": 11}
        want = _gen(params, 1).generate(p[None], 12, eos_id=0,
                                        **sampling)[0]
        if want.size - p.size < 5:
            pytest.skip("model finished too fast to kill mid-stream")
        f = _Fleet(params)
        f0 = _cval("serve.router.failovers")
        try:
            # pin the session with a plain generate first
            np.testing.assert_array_equal(
                f.router.generate(p, 12, eos_id=0, session="s",
                                  **sampling), want)
            pin = f.router.sessions()["s"]
            idx = int(pin[-1])
            toks = []

            def on_token(t):
                toks.append(t)
                if len(toks) == 2:
                    # the pinned replica "dies" now: every further
                    # frame read AND the control probe drop — the
                    # mid-stream read is where a dead replica shows
                    install_fault_injector(FaultInjector(
                        "router%d_recv:drop@1x*;"
                        "router%d_ctl_send:drop@1x*" % (idx, idx)))

            try:
                out = f.router.generate(p, 12, eos_id=0, session="s",
                                        on_token=on_token, **sampling)
            finally:
                install_fault_injector(None)
            np.testing.assert_array_equal(out, want)
            np.testing.assert_array_equal(np.asarray(toks),
                                          want[p.size:])
            assert f.router.sessions()["s"] != pin
            assert _cval("serve.router.failovers") == f0 + 1
        finally:
            f.close()


# -- (c) chunked prefill -------------------------------------------------
class TestChunkedPrefill:
    # the seeded twin costs another ~4 s for the same chunked path —
    # slow tier (the sampling stream's chunk-invariance is also pinned
    # by the perf-gate streaming scenario's seeded row)
    @pytest.mark.parametrize("sampling",
                             [GREEDY,
                              pytest.param(SEEDED,
                                           marks=pytest.mark.slow)],
                             ids=["greedy", "seeded"])
    def test_chunked_parity(self, params, monkeypatch, sampling):
        """A chunked prefill admits the same sequence the monolithic
        one does — bitwise — and the chunk counter/stat move."""
        p = np.arange(1, 11)                       # 10 > chunk 3
        want = _gen(params, 1).generate(p[None], 6, eos_id=0,
                                        **sampling)[0]
        monkeypatch.setenv("MXNET_PREFILL_CHUNK", "3")
        c0 = _cval("serve.decode.prefill_chunks")
        with _gen(params, 2).serving_decoder() as dec:
            out = dec.submit(p, 6, eos_id=0, **sampling).result(120.0)
            np.testing.assert_array_equal(out, want)
            assert dec.stats()["prefills"] == 1
        assert _cval("serve.decode.prefill_chunks") == c0 + 4

    def test_short_prompts_not_held_behind_chunked(self, params,
                                                   monkeypatch,
                                                   tmp_path):
        """A short prompt admitted behind a long chunked prefill
        still decodes concurrently (the chunking slot is reserved,
        not the loop), and the chunk spans land in the trace."""
        from mxnet_tpu import trace
        from tools.trace_report import load

        monkeypatch.setenv("MXNET_PREFILL_CHUNK", "4")
        long_p, short_p = np.arange(1, 13), np.arange(1, 4)
        want_l = _gen(params, 1).generate(long_p[None], 4,
                                          eos_id=0)[0]
        want_s = _gen(params, 1).generate(short_p[None], 4,
                                          eos_id=0)[0]
        dest = tmp_path / "trace.jsonl"
        trace.start_tracing(str(dest))
        try:
            with _gen(params, 2).serving_decoder() as dec:
                f_long = dec.submit(long_p, 4, eos_id=0)
                f_short = dec.submit(short_p, 4, eos_id=0)
                np.testing.assert_array_equal(f_long.result(120.0),
                                              want_l)
                np.testing.assert_array_equal(f_short.result(120.0),
                                              want_s)
        finally:
            trace.stop_tracing()
        spans = [r for r in load(str(dest))
                 if r.get("name") == "serve.decode.prefill_chunk"]
        assert len(spans) == 3             # ceil(12 / 4); the short
        # prompt prefilled monolithically — never behind the chunks

    def test_chunk_knob_validated_loudly(self, params, monkeypatch):
        monkeypatch.setenv("MXNET_PREFILL_CHUNK", "-1")
        with pytest.raises(ValueError, match="MXNET_PREFILL_CHUNK"):
            prefill_chunk()
        with _gen(params, 2).serving_decoder() as dec:
            with pytest.raises(ValueError,
                               match="MXNET_PREFILL_CHUNK"):
                dec.submit(np.arange(1, 5), 4, eos_id=0)


# -- (d) batched prefill -------------------------------------------------
class TestBatchedPrefill:
    def test_batched_parity_vs_sequential(self, params, monkeypatch):
        """Concurrent prefills coalesced into one padded forward give
        each request the SAME first token and KV rows a sequential
        engine gives it — causal masking makes the padding inert."""
        monkeypatch.setenv("MXNET_SERVE_MAX_WAIT_MS", "30")
        batched = PrefillEngine(_gen(params, 4))
        monkeypatch.setenv("MXNET_SERVE_MAX_WAIT_MS", "0")
        solo = PrefillEngine(_gen(params, 4))
        b0 = _cval("serve.prefill.batched")
        prompts = [np.arange(1, 5), np.arange(2, 9),
                   np.arange(3, 6)]
        res = [None] * len(prompts)

        def go(i):
            res[i] = batched.prefill(prompts[i], temperature=0.8,
                                     top_k=8, seed=100 + i)

        try:
            threads = [threading.Thread(target=go, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, p in enumerate(prompts):
                ref = solo.prefill(p, temperature=0.8, top_k=8,
                                   seed=100 + i)
                assert res[i]["first_token"] == ref["first_token"]
                assert res[i]["pos"] == ref["pos"]
                got_b, ref_b = res[i]["kv_blob"], ref["kv_blob"]
                assert got_b["pos"] == ref_b["pos"]
                assert set(got_b["rows"]) == set(ref_b["rows"])
                for name, arr in ref_b["rows"].items():
                    assert got_b["rows"][name].dtype == arr.dtype
                    np.testing.assert_array_equal(
                        got_b["rows"][name], arr)
            assert _cval("serve.prefill.batched") > b0
        finally:
            batched.close()
            solo.close()

    def test_close_fails_stranded_waiters(self, params, monkeypatch):
        """close() never strands a queued prefill: the batcher drains
        what it can and anything left fails typed, fast."""
        from mxnet_tpu.serve import EngineClosed
        monkeypatch.setenv("MXNET_SERVE_MAX_WAIT_MS", "30")
        eng = PrefillEngine(_gen(params, 4))
        eng.close()
        with pytest.raises(EngineClosed):
            eng.prefill(np.arange(1, 5))


# -- (d2) the rows a prefill runs ----------------------------------------
SLOTS = 8                                      # the rungs 1 and 8

def _spans(monkeypatch, *names):
    """The attributes of every span of `names` opened from here on."""
    from mxnet_tpu import trace
    seen, real = [], trace.phase

    def phase(name, **kw):
        if name in names:
            seen.append((name, kw))
        return real(name, **kw)

    monkeypatch.setattr(trace, "phase", phase)
    return seen


class TestPrefillRungs:
    """An autoregressive pool's prefill forwards — a whole-prompt
    group, a chunk, the draft's twins of both — run the smallest rung
    of `_row_rungs` that holds the rows that are real, on a state of
    that many rows, as a diffusion pool's do
    (tests/test_block_diffusion.py): one mechanism for both."""

    @pytest.fixture(scope="class")
    def groups(self, params):
        """Groups of 1, 2 and all 8 prompts of one length, each
        submitted in one breath to an idle pool of 8 slots (the
        harness's `_warm_groups`), then a second length alone: what
        the counters rose by, the programs that existed after each,
        the `admit.*` spans and the rows served."""
        mp = pytest.MonkeyPatch()
        seen = _spans(mp, "admit.prefill", "admit.build", "admit.merge")
        gen = _gen(params, SLOTS)
        out = {}
        try:
            with gen.serving_decoder() as dec:
                gate = _in_one_round(dec)
                rng = np.random.RandomState(17)
                for length, k in [(5, 1), (5, 2), (5, SLOTS), (7, 1)]:
                    prompts = [rng.randint(1, V, (length,))
                               for _ in range(k)]
                    before, n_seen = dec.stats(), len(seen)
                    gate.clear()
                    futs = [dec.submit(p, 3) for p in prompts]
                    gate.set()
                    rows = [f.result(120.0) for f in futs]
                    after = dec.stats()
                    out[length, k] = dict(
                        {key: after[key] - before[key] for key in (
                            "prefills", "prefill_rows", "admit_rounds",
                            "merges")},
                        prefill_programs=gen._step_fn._cache_size(),
                        merge_programs=after["merge_programs"],
                        spans=seen[n_seen:], prompts=prompts, rows=rows)
        finally:
            mp.undo()
        return out

    @pytest.mark.parametrize("k", [1, 2, SLOTS])
    def test_a_group_is_one_prefill_at_the_rung_that_holds_it(
            self, params, groups, k):
        """k prompts of one length make ONE prefill whatever k is; it
        runs 1 or 8 rows, its span says which, and every row served is
        the one-shot row."""
        got = groups[5, k]
        run = 1 if k == 1 else SLOTS
        assert (got["admit_rounds"], got["prefills"], got["merges"]) == \
            (1, 1, 1)
        assert got["prefill_rows"] == run
        assert [kw for name, kw in got["spans"]
                if name == "admit.prefill"] == \
            [{"P": 5, "rows": k, "run": run}]
        assert [kw for name, kw in got["spans"]
                if name == "admit.merge"] == [{"rows": k}]
        one = _gen(params, 1)
        for p, row in zip(got["prompts"], got["rows"]):
            np.testing.assert_array_equal(row, one.generate(p[None], 3)[0])

    def test_a_lengths_first_admission_builds_every_rung(self, groups):
        """Two prefill programs and two merge programs after the very
        first admission (one row of one length), `admit.build` there
        once a length with the rungs it built, and no more however
        the groups of that length grow; two more prefill programs at
        the second length's first sight, the merge programs as they
        were."""
        assert [groups[5, k]["prefill_programs"]
                for k in (1, 2, SLOTS)] == [2, 2, 2]
        assert groups[7, 1]["prefill_programs"] == 4
        assert {g["merge_programs"] for g in groups.values()} == {2}
        assert [[kw for name, kw in groups[key]["spans"]
                 if name == "admit.build"] for key in groups] == \
            [[{"P": 5, "rungs": [1, SLOTS]}], [], [],
             [{"P": 7, "rungs": [1, SLOTS]}]]

    @pytest.mark.parametrize("spec", [False, True],
                             ids=["target", "with-draft"])
    def test_a_group_size_first_met_later_compiles_nothing(
            self, params, spec, monkeypatch):
        """After a length's first admission (one row), groups of two,
        of three and of the pool's width at that length are served
        without one backend compile, the draft's prefills with them;
        the draft's prefill runs the rows the pool's ran."""
        import jax.monitoring
        seen = _spans(monkeypatch, "admit.prefill")
        pool = _gen(params, SLOTS)
        kw = dict(draft=pool.truncated_draft(num_layers=1),
                  lookahead=3) if spec else {}
        compiles = []

        def on_event(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(name)

        rng = np.random.RandomState(5)
        with pool.serving_decoder(**kw) as dec:
            gate = _in_one_round(dec)
            dec.submit(rng.randint(1, V, (6,)), 6,
                       speculative=spec).result(120.0)
            jax.monitoring.register_event_duration_secs_listener(
                on_event)
            try:
                for k in (2, 3, SLOTS):
                    gate.clear()
                    futs = [dec.submit(rng.randint(1, V, (6,)), 6,
                                       speculative=spec)
                            for _ in range(k)]
                    gate.set()
                    for f in futs:
                        f.result(120.0)
            finally:
                jax.monitoring.unregister_event_duration_listener(
                    on_event)
            st = dec.stats()
        assert compiles == []
        assert (st["prefills"], st["prefill_rows"]) == \
            (4, 1 + 3 * SLOTS)
        assert st["draft_prefills"] == (4 if spec else 0)
        mine = [(a["rows"], a["run"]) for _n, a in seen
                if "draft" not in a]
        assert mine == [(1, 1), (2, SLOTS), (3, SLOTS), (SLOTS, SLOTS)]
        assert [(a["rows"], a["run"]) for _n, a in seen
                if "draft" in a] == (mine if spec else [])

    @pytest.mark.parametrize("slots,spec", [(3, False), (4, False),
                                            (8, False), (4, True)])
    def test_a_chunk_runs_the_bottom_rung(self, params, monkeypatch,
                                          slots, spec):
        """A chunked prompt's forwards (and the draft's beside them)
        run the pool's bottom rung on a state of that many rows: the
        (slots, chunk) program is never built, and the row served is
        the monolithic one."""
        seen = _spans(monkeypatch, "serve.decode.prefill_chunk")
        p = np.arange(1, 11)                       # 10 > chunk 3
        want = _gen(params, 1).generate(p[None], 6, eos_id=0)[0]
        monkeypatch.setenv("MXNET_PREFILL_CHUNK", "3")
        pool = _gen(params, slots)
        kw = dict(draft=pool.truncated_draft(num_layers=1),
                  lookahead=3) if spec else {}
        with pool.serving_decoder(**kw) as dec:
            assert dec._rungs == [1, slots]
            out = dec.submit(p, 6, eos_id=0,
                             speculative=spec).result(120.0)
            st = dec.stats()
            chunk_programs = [g._step_fn._cache_size() for g in
                              (dec._gen, dec._draft) if g is not None]
        np.testing.assert_array_equal(out, want)
        assert (st["chunks"], st["chunk_rows"], st["prefill_rows"]) == \
            (4, 4, 4)
        assert (st["prefills"], st["merges"]) == (1, 2 if spec else 1)
        assert st["draft_prefills"] == int(spec)
        assert [a["run"] for _n, a in seen] == [1] * 4
        # widths 3 and 1 at one row each, in the draft as in the pool
        assert chunk_programs == [2] * (1 + spec)


# -- (e) idle timeout ----------------------------------------------------
class _StallingEngine:
    """Wire-level stall double: streams the real decoder's frames on
    every call EXCEPT the stalled one (every call, with
    ``stall_on=None``), where it emits one frame and then goes silent
    (socket open, no frames — the failure mode only a per-frame idle
    timeout can see)."""

    def __init__(self, dec, stall_on=2):
        self._dec = dec
        self._calls = 0
        self._stall_on = stall_on
        self.released = threading.Event()

    def handle_generate(self, payload):
        return self._dec.handle_generate(payload)

    def handle_generate_stream(self, payload, emit):
        self._calls += 1
        if self._stall_on not in (None, self._calls):
            return self._dec.handle_generate_stream(payload, emit)
        row = self._dec.handle_generate(payload)
        tail = [int(t) for t in
                np.asarray(row).reshape(-1)[
                    np.asarray(payload["prompt"]).size:]]
        emit(tail[:1], 0)                 # one frame, then silence
        self.released.wait(30.0)
        return row

    def stats(self):
        return self._dec.stats()


class TestIdleTimeout:
    def test_knob_validated_loudly(self, monkeypatch):
        for bad in ("0", "-3", "inf", "nan"):
            monkeypatch.setenv("MXNET_STREAM_IDLE_TIMEOUT", bad)
            with pytest.raises(ValueError,
                               match="MXNET_STREAM_IDLE_TIMEOUT"):
                stream_idle_timeout()
        monkeypatch.setenv("MXNET_STREAM_IDLE_TIMEOUT", "2.5")
        assert stream_idle_timeout() == 2.5

    def test_stalled_stream_detected_and_replayed_exact(
            self, params, monkeypatch):
        """A replica that stalls mid-stream (alive, silent) trips the
        per-frame idle timeout — NOT the old 120s+1s/token request
        deadline — and the replay delivers every token exactly once:
        the frame delivered before the stall is never re-delivered."""
        monkeypatch.setenv("MXNET_STREAM_IDLE_TIMEOUT", "0.4")
        p = np.arange(1, 5)
        want = _gen(params, 1).generate(p[None], 8, eos_id=0)[0]
        dec = ContinuousDecoder(_gen(params, 2))
        stall = _StallingEngine(dec, stall_on=2)
        srv = ServeServer(stall)
        try:
            with ServeClient(srv.host, srv.port) as cli:
                toks = []
                cli.generate(p, 8, eos_id=0,
                             on_token=lambda t: None)  # call 1: clean
                t0 = time.monotonic()
                out = cli.generate(p, 8, eos_id=0,   # call 2: stalls
                                   on_token=toks.append)
                wall = time.monotonic() - t0
            np.testing.assert_array_equal(out, want)
            np.testing.assert_array_equal(np.asarray(toks),
                                          want[p.size:])
            # detected by the idle timeout, nowhere near the old
            # whole-request deadline
            assert wall < 30.0
        finally:
            stall.released.set()
            srv.close()
            dec.close()

    def test_hung_replica_fails_fast_when_alone(self, params,
                                                monkeypatch):
        """No survivor, no recovery: a permanently silent stream
        exhausts the retry budget in idle-timeout time, not the
        blanket generate deadline."""
        monkeypatch.setenv("MXNET_STREAM_IDLE_TIMEOUT", "0.2")
        dec = ContinuousDecoder(_gen(params, 2))
        # the clock below is the idle timeouts' alone: the decoder's
        # programs are compiled before it starts (on a machine shared
        # by six test workers the prefill and the step compile for
        # longer than the 10 s this test allows two missed gaps), and
        # the replica is silent on EVERY call (with only the first one
        # stalled the replay met a warm decoder and succeeded: what
        # made the test raise was the compile, not the stall)
        dec.submit(np.arange(1, 5), 8, eos_id=0).result(120.0)
        stall = _StallingEngine(dec, stall_on=None)
        srv = ServeServer(stall)
        try:
            cli = ServeClient(srv.host, srv.port,
                              retry=RetryPolicy(max_retries=1,
                                                base_delay=0.01,
                                                deadline=10.0))
            t0 = time.monotonic()
            with pytest.raises(Exception):
                cli.generate(np.arange(1, 5), 8, eos_id=0,
                             on_token=lambda t: None)
            assert time.monotonic() - t0 < 10.0
            cli.close()
        finally:
            stall.released.set()
            srv.close()
            dec.close()


# -- (f) one relay a decoder ---------------------------------------------
def _raw_stream(srv, payload, rcvbuf=None):
    """A connection with one streamed generate sent on it and nothing
    read yet: the client these tests have to be able to stall."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if rcvbuf:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(60.0)
    sock.connect((srv.host, srv.port))
    _send_msg(sock, ("generate", dict(payload, stream=True)))
    return sock


def _read_stream(sock):
    """Every message of the stream in arrival order, up to and
    including the terminal reply, and whether anything came after
    it."""
    msgs = []
    while not msgs or msgs[-1][0] == "frame":
        msgs.append(_recv_msg(sock))
    sock.settimeout(0.2)
    try:
        after = _recv_msg(sock)
    except socket.timeout:
        after = None
    return msgs, after


def _tail(msgs):
    """A stream's tokens as its frames carried them, the frames' seq
    and offset held to the contract on the way."""
    toks = []
    for seq, (kind, fr) in enumerate(msgs[:-1]):
        assert kind == "frame"
        assert (fr["seq"], fr["offset"]) == (seq, len(toks))
        toks.extend(fr["tokens"])
    return toks


class TestOneRelay:
    """The streamed path's threads (docs/serving.md §streaming): the
    loop notes, one relay a decoder writes, a stream's handler sleeps
    until the settle."""

    def test_a_step_of_streaming_rows_is_one_handoff(self, params):
        """B rows admitted in one round and streamed to B clients for
        N tokens: the admission's first tokens are one hand-off and
        each of the N - 1 steps is one, of B frames each; no thread is
        woken a row."""
        B, N = 4, 6
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, V, (5,)) for _ in range(B)]
        one = _gen(params, 1)
        dec = ContinuousDecoder(_gen(params, B))
        srv = ServeServer(dec)
        got = [None] * B
        toks = [[] for _ in range(B)]

        def call(i):
            with ServeClient(srv.host, srv.port) as cli:
                got[i] = cli.generate(prompts[i], N,
                                      on_token=toks[i].append)

        try:
            dec.submit(prompts[0], 2).result(120.0)   # programs built
            gate = _in_one_round(dec)
            before, f0 = dec.stats(), _cval("serve.net.stream_frames")
            gate.clear()
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(B)]
            for t in threads:
                t.start()
            # every stream subscribed before its row is admitted, so
            # that no first token is a replayed prefix
            _wait(lambda: len(dec._queue) == B and
                  all(f._sinks for f in list(dec._queue)), 60,
                  "the streams to subscribe")
            gate.set()
            for t in threads:
                t.join(120.0)
                assert not t.is_alive()
            after = dec.stats()
        finally:
            srv.close()
            dec.close()
        for i in range(B):
            want = one.generate(prompts[i][None], N)[0]
            np.testing.assert_array_equal(got[i], want)
            assert toks[i] == want[5:].tolist()
        assert after["admit_rounds"] - before["admit_rounds"] == 1
        assert after["steps"] - before["steps"] == N - 1
        assert after["stream_handoffs"] - before["stream_handoffs"] == N
        assert _cval("serve.net.stream_frames") - f0 == B * N
        assert after["stream_frames_late"] == 0

    def test_a_client_that_stops_reading_delays_nobody(self):
        """One client asks for a long stream and reads nothing: its
        socket fills, the relay leaves its frames to its own handler
        (`stream_frames_late`), and meanwhile another client's streams
        run at their own pace, each inside a wall-clock limit. When
        the first client reads at last, its frames are all there, in
        order, before its terminal reply."""
        long_t, n_slow = 400, 380
        sym = transformer.get_symbol(V, 12, num_layers=1, num_heads=H,
                                     dim=DIM, max_len=long_t)
        mx.random.seed(5)
        wide = make_train_step(sym, optimizer="sgd").init_state(
            Xavier(), {"data": (2, 12), "softmax_label": (2, 12)})[0]

        def gen(batch):
            return Generator(wide, V, long_t, num_layers=1, num_heads=H,
                             dim=DIM, batch_size=batch)

        p = np.arange(1, 6)
        want_slow = gen(1).generate(p[None], n_slow)[0]
        want_fast = gen(1).generate(p[None], 12)[0]
        dec = ContinuousDecoder(gen(2))
        srv = ServeServer(dec)
        try:
            dec.submit(p, 2).result(120.0)            # programs built
            # small buffers on both ends (a connection inherits its
            # listener's), so that a few hundred frames fill them
            srv._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                 2048)
            slow = _raw_stream(srv, {"prompt": p,
                                     "max_new_tokens": n_slow},
                               rcvbuf=2048)
            _wait(lambda: dec.stats()["stream_frames_late"] > 0, 60,
                  "the stalled client's socket to fill")
            steps0 = dec.stats()["steps"]
            with ServeClient(srv.host, srv.port) as cli:
                for _ in range(3):
                    toks = []
                    t0 = time.monotonic()
                    out = cli.generate(p, 12, on_token=toks.append)
                    assert time.monotonic() - t0 < 20.0
                    np.testing.assert_array_equal(out, want_fast)
                    assert toks == want_fast[p.size:].tolist()
            # the loop went on stepping the stalled row beside them
            assert dec.stats()["steps"] - steps0 >= 3 * 11
            assert dec.introspect()["streams_in_flight"] == 1
            msgs, after = _read_stream(slow)
            slow.close()
        finally:
            srv.close()
            dec.close()
        assert after is None
        assert msgs[-1][0] == "ok"
        np.testing.assert_array_equal(msgs[-1][1], want_slow)
        assert _tail(msgs) == want_slow[p.size:].tolist()
        late = dec.stats()["stream_frames_late"]
        assert 0 < late < n_slow

    def test_a_send_fault_ends_its_stream_alone(self, params):
        """A sever injected at `serve_srv_send` under one of two
        concurrent streams: that client reconnects and its replay
        (the deduped admission's prefix from offset 0, then the live
        tokens) delivers every token once; the other stream never
        notices."""
        N = 10
        prompts = [np.arange(1, 5), np.arange(3, 9)]
        one = _gen(params, 1)
        dec = ContinuousDecoder(_gen(params, 2))
        srv = ServeServer(dec)
        got, toks = [None, None], [[], []]

        def call(i):
            with ServeClient(srv.host, srv.port) as cli:
                got[i] = cli.generate(prompts[i], N, admit_id="a%d" % i,
                                      on_token=toks[i].append)

        try:
            for p in prompts:
                dec.submit(p, 2).result(120.0)        # programs built
            before, r0 = dec.stats(), _cval("serve.net.retries")
            # the fourth write at the point is a frame of one of the
            # two (a terminal reply follows a stream's N frames)
            install_fault_injector(FaultInjector(
                "serve_srv_send:disconnect@4"))
            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(2)]
            try:
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(120.0)
                    assert not t.is_alive()
            finally:
                install_fault_injector(None)
            after = dec.stats()
        finally:
            srv.close()
            dec.close()
        for i in range(2):
            want = one.generate(prompts[i][None], N)[0]
            np.testing.assert_array_equal(got[i], want)
            assert toks[i] == want[prompts[i].size:].tolist()
        assert _cval("serve.net.retries") - r0 == 1
        assert after["deduped"] - before["deduped"] == 1
        assert after["admitted"] - before["admitted"] == 2
        assert after["streams"] - before["streams"] == 3
        assert dec.introspect()["streams_in_flight"] == 0

    def test_replays_beside_live_tokens_lose_and_repeat_nothing(
            self, params):
        """Sixteen connections, two for each of eight admissions (the
        second attempt subscribes somewhere mid-sequence and is owed
        the prefix by its own thread and the rest by the relay), on a
        pool of four slots under a switch interval of 10 us: every
        connection reads contiguous frames of exactly the one-shot
        tokens before its terminal reply."""
        import sys
        N = 12
        rng = np.random.RandomState(9)
        prompts = [rng.randint(1, V, (4 + i % 3,)) for i in range(8)]
        one = _gen(params, 1)
        want = [one.generate(p[None], N)[0] for p in prompts]
        dec = ContinuousDecoder(_gen(params, 4))
        srv = ServeServer(dec)
        read = [None] * 16

        def call(k):
            i = k // 2
            time.sleep(0.004 * (k % 2) * (1 + i % 4))
            sock = _raw_stream(srv, {"prompt": prompts[i],
                                     "max_new_tokens": N,
                                     "admit_id": "r%d" % i})
            try:
                read[k] = _read_stream(sock)
            finally:
                sock.close()

        interval = sys.getswitchinterval()
        try:
            for p in prompts[:3]:
                dec.submit(p, 2).result(120.0)        # programs built
            before = dec.stats()
            sys.setswitchinterval(1e-5)
            threads = [threading.Thread(target=call, args=(k,))
                       for k in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120.0)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(interval)
            after = dec.stats()
            srv.close()
            dec.close()
        for k, (msgs, tail) in enumerate(read):
            assert tail is None
            assert msgs[-1][0] == "ok"
            np.testing.assert_array_equal(msgs[-1][1], want[k // 2])
            assert _tail(msgs) == \
                want[k // 2][prompts[k // 2].size:].tolist()
        assert after["admitted"] - before["admitted"] == 8
        assert after["deduped"] - before["deduped"] == 8
        assert after["stream_frames_late"] == 0

    @pytest.mark.parametrize("pool", ["autoregressive", "diffusion"])
    def test_every_frame_precedes_the_terminal_reply(self, params,
                                                     pool):
        """What arrives on a stream's connection is its frames, in
        order and whole, then the terminal reply, then nothing; a
        diffusion row's step is one frame however many tokens it
        unmasked."""
        if pool == "diffusion":
            import test_block_diffusion as bd
            gen = bd._gen(bd.ref.make_params(bd.TOY, bd.SEED, "float32"),
                          2)
            prompts, n = bd._prompts([9, 6], seed=2), 11
        else:
            gen = _gen(params, 2)
            prompts, n = [np.arange(1, 5), np.arange(2, 9)], 9
        with gen.serving_decoder() as dec:
            want = [dec.submit(p, n).result(120.0) for p in prompts]
            with ServeServer(dec) as srv:
                socks = [_raw_stream(srv, {"prompt": p,
                                           "max_new_tokens": n})
                         for p in prompts]
                read = [_read_stream(s) for s in socks]
                for s in socks:
                    s.close()
        for p, row, (msgs, after) in zip(prompts, want, read):
            assert after is None
            assert msgs[-1][0] == "ok"
            np.testing.assert_array_equal(msgs[-1][1], row)
            assert _tail(msgs) == np.asarray(row)[len(p):].tolist()
            sizes = [len(fr["tokens"]) for _kind, fr in msgs[:-1]]
            if pool == "diffusion":
                assert max(sizes) > 1 and len(sizes) < n
            else:
                assert sizes == [1] * n
