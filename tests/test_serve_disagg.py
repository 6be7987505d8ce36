"""Prefill/decode disaggregation (docs/serving.md §disaggregated
prefill): KV-cache handoff over the wire.

Load-bearing acceptance gate: (remote prefill → export_kv_rows → wire
→ import_kv_rows → decode) emits token-for-token what a
single-process ``Generator.generate`` emits — for f32, bf16 and int8
(quantize_kv) caches, GQA included — with ZERO prefill graph calls on
the decode side (the ``prefills`` stat), and a mid-handoff injected
disconnect replays the pure prefill to the identical blob with
exactly one admit.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config as mxconfig
from mxnet_tpu.generation import Generator, kv_blob_nbytes
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.parallel.resilience import (FaultInjector,
                                           install_fault_injector)
from mxnet_tpu.serve import (ContinuousDecoder, PrefillEngine,
                             ServeRouter, ServeServer)
from mxnet_tpu.serve.decode import drain_timeout

pytestmark = pytest.mark.serve

V, L, H, DIM, T, B = 50, 2, 2, 32, 24, 3


def _params(seed=0, num_kv_heads=None):
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 num_kv_heads=num_kv_heads)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(seed)
    state = step.init_state(Xavier(), {"data": (2, 12),
                                       "softmax_label": (2, 12)})
    return state[0]


@pytest.fixture(scope="module")
def params():
    return _params()


def _gen(params, batch_size, **kw):
    return Generator(params, V, T, num_layers=L, num_heads=H, dim=DIM,
                     batch_size=batch_size, **kw)


def _wire(rows, heads):
    """One sequence's (pos, Hkv*hd) token rows (or (pos, Hkv) int8
    scales) as a "v": 1 blob ships them: head-major, (Hkv, pos, hd)
    (or (Hkv, pos)) — what the device kept before PR 30, and what the
    wire still carries."""
    pos = rows.shape[0]
    out = rows.reshape(pos, heads, -1).swapaxes(0, 1)
    return out.reshape(heads, pos) if rows.shape[1] == heads else out


def _ragged(rng, n=4):
    # two DISTINCT prompt lengths only: ragged coverage without a
    # fresh XLA prefill specialization per sequence (tier-1 rides the
    # wall-clock budget; every extra length is two compiles)
    prompts = [rng.randint(0, V, (p,)) for p in (4, 6, 4, 6, 4)[:n]]
    maxnew = [8, 3, 6, 5, 4][:n]
    return prompts, maxnew


class TestHandoffRoundTrip:
    def _roundtrip_parity(self, params, **genkw):
        """ACCEPTANCE body: prefill on one engine, export, import into
        a separate pool, decode — token-for-token vs single-process
        generate; admission runs zero prefill graph calls."""
        single = _gen(params, 1, **genkw)
        pre = PrefillEngine(_gen(params, 2, **genkw))
        rng = np.random.RandomState(3)
        prompts, maxnew = _ragged(rng, 5)   # > B: slot turnover too
        with _gen(params, B, **genkw).serving_decoder() as dec:
            futs = [dec.submit(p, n, eos_id=0, handoff=pre.prefill(p))
                    for p, n in zip(prompts, maxnew)]
            got = [f.result(120.0) for f in futs]
            st = dec.stats()
        assert st["prefills"] == 0          # scatter-only admission
        assert st["imported"] == len(prompts)
        assert st["finished"] == len(prompts) > B
        for p, n, g in zip(prompts, maxnew, got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], n, eos_id=0)[0])

    def test_greedy_parity_f32(self, params):
        self._roundtrip_parity(params)

    # ~10 s on the 1-core tier-1 host — slow tier; f32 (fast, above)
    # pins the round-trip contract and the bf16 row dtype is preserved
    # bit-for-bit by the same export path test_prefill_is_pure_and_
    # blob_exact checks
    @pytest.mark.slow
    def test_greedy_parity_bf16(self, params):
        self._roundtrip_parity(params, dtype="bfloat16")

    def test_greedy_parity_int8_kv_gqa(self):
        """int8 caches + GQA in one pool: the handoff ships int8 rows
        AND their per-token f32 scale rows, at kv_heads=1 (covers the
        plain-int8 path too — same scatter, more rows)."""
        params = _params(seed=5, num_kv_heads=1)
        self._roundtrip_parity(params, quantize_kv=True,
                               num_kv_heads=1)

    def test_sampled_parity(self, params):
        """The handoff first token consumes the request PRNG stream's
        first split on the PREFILL side; the decode side continues the
        stream — together exactly generate()'s key discipline."""
        single = _gen(params, 1)
        pre = PrefillEngine(_gen(params, 2))
        prompt = np.random.RandomState(9).randint(0, V, (5,))
        with _gen(params, B).serving_decoder() as dec:
            h = pre.prefill(prompt, temperature=0.8, top_k=5, seed=42)
            got = dec.submit(prompt, 6, temperature=0.8, top_k=5,
                             seed=42, handoff=h).result(120.0)
        want = single.generate(prompt[None], 6, temperature=0.8,
                               top_k=5, seed=42)[0]
        np.testing.assert_array_equal(got, want)

    def test_prefill_is_pure_and_blob_exact(self, params):
        """Replay safety rests on purity: the same prompt + seed lands
        the bit-identical reply, and the exported rows equal the
        prefill aux's own rows (device-roundtrip-exact)."""
        gen = _gen(params, 2)
        pre = PrefillEngine(gen)
        prompt = np.arange(1, 7)
        h1, h2 = pre.prefill(prompt), pre.prefill(prompt)
        assert h1["first_token"] == h2["first_token"]
        assert h1["pos"] == h2["pos"] == 6
        for name in h1["kv_blob"]["rows"]:
            a, b = h1["kv_blob"]["rows"][name], h2["kv_blob"]["rows"][name]
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        # export slices the aux bit-for-bit
        rows = np.stack([prompt, prompt]).astype(np.float32)
        _, aux = gen._forward(gen._fresh_aux(), rows, 0)
        blob = gen.export_kv_rows(aux, 0, 6)
        for name, arr in blob["rows"].items():
            np.testing.assert_array_equal(
                arr, _wire(np.asarray(aux[name][0, :6]), arr.shape[0]))

    @pytest.mark.parametrize("genkw", [
        {}, {"dtype": "bfloat16"},
        {"quantize_kv": True, "num_kv_heads": 1}],
        ids=["f32", "bf16", "int8_gqa"])
    def test_blob_in_the_parents_row_shape_imports_bit_exact(self, genkw):
        """Handoff across trees: a "v": 1 blob built BY HAND in the
        row shape every earlier tree exports — (Hkv, pos, hd) a cache,
        (Hkv, pos) the int8 scales — lands in the token-contiguous
        pool bit for bit, token t of head h at lanes [h*hd, (h+1)*hd)
        of row t, and an export of that slot is the blob again."""
        import jax.numpy as jnp
        kv = genkw.get("num_kv_heads", H)
        gen = _gen(_params(seed=5, num_kv_heads=genkw.get(
            "num_kv_heads")), B, **genkw)
        rng = np.random.RandomState(17)
        pos, hd, rows = 7, DIM // H, {}
        for name in gen._sym.list_auxiliary_states():
            dtype = gen._aux_spec(name)[1]
            if name.endswith(("_k_scale", "_v_scale")):
                rows[name] = (rng.rand(kv, pos) + 0.01).astype(dtype)
            elif dtype == jnp.int8:
                rows[name] = rng.randint(-127, 128, (kv, pos, hd)
                                         ).astype(np.int8)
            else:
                rows[name] = np.asarray(jnp.asarray(
                    rng.randn(kv, pos, hd), dtype))
        blob = {"v": 1, "pos": pos, "rows": rows}
        with gen.serving_decoder() as dec:
            assert dec.import_kv_rows(1, blob) == pos
            pool = {n: np.asarray(a) for n, a in dec._aux.items()}
            back = gen.export_kv_rows(dec._aux, 1, pos)
        for name, arr in rows.items():
            assert pool[name].shape[:2] == (B, T)       # token rows
            for h in range(kv):
                got = pool[name][1, :pos].reshape(pos, kv, -1)[:, h]
                np.testing.assert_array_equal(
                    got.reshape(arr[h].shape), arr[h])
            assert back["rows"][name].dtype == arr.dtype
            np.testing.assert_array_equal(back["rows"][name], arr)
        assert back["v"] == 1 and back["pos"] == pos

    def test_int8_blob_smaller_than_f32(self, params):
        """int8 rows + f32 per-token scales undercut the float blob
        (the per-token scales weigh less against a wider head; at
        this toy hd the ordering still must hold)."""
        p = np.arange(1, 9)
        b_f32 = PrefillEngine(_gen(params, 1)).prefill(p)
        b_q8 = PrefillEngine(
            _gen(params, 1, quantize_kv=True)).prefill(p)
        assert kv_blob_nbytes(b_q8["kv_blob"]) < \
            kv_blob_nbytes(b_f32["kv_blob"])

    def test_blob_validation_is_loud(self, params):
        gen = _gen(params, 2)
        pre = PrefillEngine(gen)
        prompt = np.arange(1, 6)
        h = pre.prefill(prompt)
        with _gen(params, B).serving_decoder() as dec:
            with pytest.raises(ValueError, match="exactly the prompt"):
                dec.submit(np.arange(1, 5), 3, handoff=h)  # wrong P
            with pytest.raises(ValueError, match="first_token"):
                dec.submit(prompt, 3, handoff={"kv_blob": 1})
        # a quantized blob must not scatter into a float pool
        hq = PrefillEngine(
            _gen(params, 1, quantize_kv=True)).prefill(prompt)
        with _gen(params, B).serving_decoder() as dec:
            with pytest.raises(ValueError,
                               match="do not match this pool"):
                dec.submit(prompt, 3, handoff=hq)
        # export-side validation
        rows = np.stack([prompt, prompt]).astype(np.float32)
        _, aux = gen._forward(gen._fresh_aux(), rows, 0)
        with pytest.raises(ValueError, match="row 7 out of range"):
            gen.export_kv_rows(aux, 7, 5)
        with pytest.raises(ValueError, match="pos 99 out of range"):
            gen.export_kv_rows(aux, 0, 99)


class TestWire:
    def _fleet(self, params, **genkw):
        pre_eng = PrefillEngine(_gen(params, 2, **genkw))
        dec_eng = ContinuousDecoder(_gen(params, B, **genkw))
        s1, s2 = ServeServer(pre_eng), ServeServer(dec_eng)
        router = ServeRouter(poll_ms=0)
        router.add_replica(s1.host, s1.port, name="prefill0")
        router.add_replica(s2.host, s2.port, name="decode0")
        router.poll_now()
        return pre_eng, dec_eng, s1, s2, router

    def test_router_disagg_parity(self, params):
        """ACCEPTANCE: the full wire path — role-aware dispatch,
        prefill frame, blob shipped with the admit — matches
        single-process generate; the decode replica never prefills."""
        single = _gen(params, 1)
        pre_eng, dec_eng, s1, s2, router = self._fleet(params)
        try:
            assert {r["role"] for r in router.replicas().values()} \
                == {"prefill", "decode"}
            rng = np.random.RandomState(7)
            prompts, maxnew = _ragged(rng, 4)
            for p, n in zip(prompts, maxnew):
                out = router.generate(p, n, eos_id=0, session="sA")
                np.testing.assert_array_equal(
                    np.asarray(out),
                    single.generate(p[None], n, eos_id=0)[0])
            assert dec_eng.stats()["prefills"] == 0
            assert dec_eng.stats()["imported"] == len(prompts)
            assert pre_eng.stats()["prefills"] == len(prompts)
            # the session pinned to the decode replica, not prefill
            assert router.sessions() == {"sA": "decode0"}
        finally:
            router.close(); s1.close(); s2.close(); dec_eng.close()

    def test_mid_handoff_disconnect_replays_one_admit(self, params):
        """ACCEPTANCE: a disconnect torn into the 2nd prefill frame
        replays the pure prefill on a fresh connection — the replayed
        blob is identical (purity, pinned above), the decode side
        admits exactly once per request, tokens exact."""
        single = _gen(params, 1)
        pre_eng, dec_eng, s1, s2, router = self._fleet(params)
        inj = install_fault_injector(
            FaultInjector("prefill_send:disconnect@2"))
        try:
            rng = np.random.RandomState(11)
            prompts, maxnew = _ragged(rng, 2)
            for p, n in zip(prompts, maxnew):
                out = router.generate(p, n, eos_id=0)
                np.testing.assert_array_equal(
                    np.asarray(out),
                    single.generate(p[None], n, eos_id=0)[0])
            assert inj.fired == [("prefill_send", 2, "disconnect")]
            st = dec_eng.stats()
            assert st["admitted"] == st["imported"] == len(prompts)
            assert st["prefills"] == 0
        finally:
            install_fault_injector(None)
            router.close(); s1.close(); s2.close(); dec_eng.close()

    def test_decode_only_fleet_stays_colocated(self, params):
        """No prefill-role replica → today's colocated path: the
        admitting replica prefills locally, zero imports."""
        single = _gen(params, 1)
        dec_eng = ContinuousDecoder(_gen(params, B))
        srv = ServeServer(dec_eng)
        router = ServeRouter(poll_ms=0)
        router.add_replica(srv.host, srv.port, name="colo0")
        router.poll_now()
        try:
            p = np.random.RandomState(13).randint(0, V, (5,))
            out = router.generate(p, 6, eos_id=0)
            np.testing.assert_array_equal(
                np.asarray(out), single.generate(p[None], 6,
                                                 eos_id=0)[0])
            st = dec_eng.stats()
            assert st["imported"] == 0 and st["prefills"] >= 1
        finally:
            router.close(); srv.close(); dec_eng.close()

    def test_generate_prefers_decode_replicas_in_mixed_fleet(self,
                                                            params):
        """A mixed batch+decode fleet (no prefill role): generate
        frames must land on the decode replica even when the batch
        replica is least-loaded — a 'batch' neighbor has no
        handle_generate() and its typed error would fail the request
        while a decode-capable replica sits idle."""
        from mxnet_tpu.serve import ServeEngine

        class _Echo:
            def forward(self, *arrays):
                return [np.asarray(arrays[0])]
        single = _gen(params, 1)
        eng = ServeEngine(_Echo(), buckets=(1,), max_wait_ms=0.0,
                          feature_shapes=[(4,)], install_sigterm=False)
        dec_eng = ContinuousDecoder(_gen(params, B))
        s1, s2 = ServeServer(eng), ServeServer(dec_eng)
        router = ServeRouter(poll_ms=0)
        router.add_replica(s1.host, s1.port, name="batch0")
        router.add_replica(s2.host, s2.port, name="decode0")
        router.poll_now()
        try:
            p = np.arange(1, 5)
            out = router.generate(p, 4, eos_id=0)
            np.testing.assert_array_equal(
                np.asarray(out), single.generate(p[None], 4,
                                                 eos_id=0)[0])
        finally:
            router.close(); s1.close(); s2.close()
            eng.close(); dec_eng.close()

    def test_caller_supplied_handoff_passes_through_router(self,
                                                           params):
        """The replica-surface contract: a client that already paid
        its remote prefill ships the blob through the router-fronted
        endpoint and the router must NOT prefill again — the blob
        admits scatter-only on the decode replica."""
        single = _gen(params, 1)
        pre = PrefillEngine(_gen(params, 2))
        dec_eng = ContinuousDecoder(_gen(params, B))
        srv = ServeServer(dec_eng)
        router = ServeRouter(poll_ms=0)
        router.add_replica(srv.host, srv.port, name="decode0")
        router.poll_now()
        try:
            p = np.arange(1, 6)
            h = pre.prefill(p)
            out = router.generate(p, 4, eos_id=0, handoff=h)
            np.testing.assert_array_equal(
                np.asarray(out), single.generate(p[None], 4,
                                                 eos_id=0)[0])
            st = dec_eng.stats()
            assert st["imported"] == 1 and st["prefills"] == 0
        finally:
            router.close(); srv.close(); dec_eng.close()

    def test_infer_never_routes_to_prefill_replicas(self, params):
        """A prefill replica cannot answer infer — role-aware dispatch
        must keep ordinary traffic off it even when it is the
        least-loaded replica by score."""
        from mxnet_tpu.serve import ServeEngine

        class _Echo:
            def forward(self, *arrays):
                return [np.asarray(arrays[0])]
        eng = ServeEngine(_Echo(), buckets=(1, 2), max_wait_ms=0.0,
                          feature_shapes=[(4,)], install_sigterm=False)
        pre_eng = PrefillEngine(_gen(params, 1))
        s1, s2 = ServeServer(pre_eng), ServeServer(eng)
        router = ServeRouter(poll_ms=0)
        router.add_replica(s1.host, s1.port, name="prefill0")
        router.add_replica(s2.host, s2.port, name="batch0")
        router.poll_now()
        try:
            x = np.zeros((1, 4), np.float32)
            for _ in range(3):
                router.infer(x, timeout=60.0)
            reps = router.replicas()
            assert reps["prefill0"]["dispatched"] == 0
            assert reps["batch0"]["dispatched"] == 3
        finally:
            router.close(); s1.close(); s2.close(); eng.close()


class TestDrainKnob:
    def test_close_reads_decode_drain_timeout(self, params):
        mxconfig.set_override("MXNET_DECODE_DRAIN_TIMEOUT", 5.0)
        try:
            assert drain_timeout() == 5.0
            dec = ContinuousDecoder(_gen(params, B))
            dec.close()                    # knob-resolved, no raise
        finally:
            mxconfig.clear_override("MXNET_DECODE_DRAIN_TIMEOUT")

    @pytest.mark.parametrize("bad", [0.0, -3.0, float("nan"),
                                     float("inf")])
    def test_invalid_drain_timeout_is_loud(self, bad, params):
        mxconfig.set_override("MXNET_DECODE_DRAIN_TIMEOUT", bad)
        try:
            with pytest.raises(ValueError,
                               match="MXNET_DECODE_DRAIN_TIMEOUT"):
                drain_timeout()
            dec = ContinuousDecoder(_gen(params, B))
            with pytest.raises(ValueError,
                               match="MXNET_DECODE_DRAIN_TIMEOUT"):
                dec.close()
            dec.close(timeout=10.0)        # explicit budget still works
        finally:
            mxconfig.clear_override("MXNET_DECODE_DRAIN_TIMEOUT")

    def test_recycle_of_decode_replica_uses_decode_knob(self, params):
        """recycle() budgets a decode replica's drain from
        MXNET_DECODE_DRAIN_TIMEOUT (the same clock close() honors):
        with the knob invalid, recycling the decode replica trips its
        loud validation while recycling a batch replica never reads
        it."""
        from mxnet_tpu.serve import ServeEngine

        class _Echo:
            def forward(self, *arrays):
                return [np.asarray(arrays[0])]
        eng = ServeEngine(_Echo(), buckets=(1,), max_wait_ms=0.0,
                          feature_shapes=[(4,)], install_sigterm=False)
        dec_eng = ContinuousDecoder(_gen(params, B))
        s1, s2 = ServeServer(eng), ServeServer(dec_eng)
        router = ServeRouter(poll_ms=0)
        router.add_replica(s1.host, s1.port, name="batch0")
        router.add_replica(s2.host, s2.port, name="decode0")
        router.poll_now()
        mxconfig.set_override("MXNET_DECODE_DRAIN_TIMEOUT",
                              float("nan"))
        try:
            with pytest.raises(ValueError,
                               match="MXNET_DECODE_DRAIN_TIMEOUT"):
                router.recycle("decode0")
            router.recycle("batch0", warm=False)   # knob never read
        finally:
            mxconfig.clear_override("MXNET_DECODE_DRAIN_TIMEOUT")
            router.close(); s1.close(); s2.close()
            eng.close(); dec_eng.close()


class TestTraceJoin:
    def test_one_trace_spans_prefill_handoff_decode(self, params,
                                                    tmp_path):
        """The disaggregated request is ONE trace: the router generate
        span parents the prefill and decode legs, and the decode
        replica's import/seq spans join via the wire tc."""
        from mxnet_tpu import trace
        from tools.trace_report import load

        dest = tmp_path / "trace.jsonl"
        trace.start_tracing(str(dest))
        pre_eng, dec_eng, s1, s2, router = TestWire()._fleet(params)
        try:
            router.generate(np.arange(1, 6), 4, eos_id=0)
        finally:
            router.close(); s1.close(); s2.close(); dec_eng.close()
            trace.stop_tracing()
        spans = [r for r in load(str(dest))
                 if r.get("kind") == "span"]
        names = {s["name"] for s in spans}
        for want in ("serve.router.generate", "serve.router.prefill",
                     "serve.router.decode", "serve.prefill.request",
                     "serve.generate.request", "serve.prefill",
                     "serve.decode.import", "serve.decode.seq"):
            assert want in names, (want, sorted(names))
        tid = next(s["trace"] for s in spans
                   if s["name"] == "serve.router.generate")
        joined = {s["name"] for s in spans if s["trace"] == tid}
        assert {"serve.prefill", "serve.decode.import",
                "serve.decode.seq"} <= joined
