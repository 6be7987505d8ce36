"""SSM blocks through the serving stack (ISSUE 19: O(1)-cache
decode): the ContinuousDecoder slot pool holding constant-size
recurrent state blobs instead of (max_len, ...) KV rows.

Load-bearing acceptance gates: ragged pool decode == batch-1 generate
token-for-token across slot turnover (greedy AND seeded sampling),
ONE compiled (B, 1) program across that turnover
(serve.decode.jit_cache_size stays 1 — SSM needs no per-row twin at
all), export/import round-trip exactness including mid-decode
migration, and the O(1) wire property: handoff blob bytes constant in
prompt length.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator, kv_blob_nbytes
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.serve import PrefillEngine, SessionEvacuated

pytestmark = pytest.mark.serve

V, L, H, DIM, T, B = 50, 2, 2, 32, 24, 3


def _params(block_type="ssm", seed=0):
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 block_type=block_type)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(seed)
    state = step.init_state(Xavier(), {"data": (2, 12),
                                       "softmax_label": (2, 12)})
    return state[0]


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def mixed_params():
    return _params(block_type=("attention", "ssm"), seed=1)


def _gen(params, batch_size, block_type="ssm", **kw):
    return Generator(params, V, T, num_layers=L, num_heads=H, dim=DIM,
                     batch_size=batch_size, block_type=block_type,
                     **kw)


class TestParity:
    def test_greedy_matches_static_generate_ragged(self, params):
        """ACCEPTANCE: 7 ragged requests through a 3-slot SSM pool ==
        static per-sequence generate, token for token, with slot
        turnover — and the whole workload compiles ONE (B, 1) step."""
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, V, (p,)) for p in
                   (4, 6, 4, 5, 4, 6, 7)]
        maxnew = [8, 3, 12, 5, 2, 9, 4]
        with pool.serving_decoder() as dec:
            futs = [dec.submit(p, n, eos_id=0)
                    for p, n in zip(prompts, maxnew)]
            got = [f.result(120.0) for f in futs]
            st = dec.stats()
        for i, (p, n) in enumerate(zip(prompts, maxnew)):
            np.testing.assert_array_equal(
                got[i], single.generate(p[None], n, eos_id=0)[0])
        assert st["finished"] == len(prompts) > B   # turnover happened
        # the tentpole's serving invariant: slot membership changed
        # many times and the decode step never recompiled
        assert telemetry.gauge(
            "serve.decode.jit_cache_size").value == 1

    def test_sampled_matches_batch1_generate(self, params):
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, V, (5,))
        with pool.serving_decoder() as dec:
            other = [dec.submit(rng.randint(0, V, (4,)), 10)
                     for _ in range(2)]
            got = dec.submit(prompt, 6, temperature=0.8, top_k=5,
                             seed=42).result(120.0)
            for o in other:
                o.result(120.0)
        want = single.generate(prompt[None], 6, temperature=0.8,
                               top_k=5, seed=42)[0]
        np.testing.assert_array_equal(got, want)

    def test_mixed_stack_greedy_parity(self, mixed_params):
        """Attention + SSM layers in one stack: KV rows and state
        blobs live side by side in the same slot pool."""
        bt = ("attention", "ssm")
        pool = _gen(mixed_params, 2, block_type=bt)
        single = _gen(mixed_params, 1, block_type=bt)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, V, (p,)) for p in (3, 6, 4)]
        with pool.serving_decoder() as dec:
            got = [dec.submit(p, n).result(120.0)
                   for p, n in zip(prompts, (9, 4, 6))]
        for p, n, g in zip(prompts, (9, 4, 6), got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], n)[0])


class TestSlotAccounting:
    def test_bytes_per_slot_state_agnostic(self, params):
        """Generator.state_bytes_per_slot() == the live pool's
        measured figure == the kv_bytes_per_slot gauge — one number
        for sizing whether the state is KV rows or an SSM blob, and
        for SSM it never mentions max_len."""
        gen = _gen(params, B)
        hd = DIM // H
        want = L * H * hd * hd * 4
        assert gen.state_bytes_per_slot() == want
        g = telemetry.gauge("serve.decode.kv_bytes_per_slot")
        with gen.serving_decoder() as dec:
            assert dec._kv_bytes_per_slot == want
            assert g.value == want
            report = dec.describe(hbm_budget=want * 10 + 1)
            assert "ssm state" in report
            assert "kv_bytes_per_slot: %d" % want in report
            assert "10 slot(s) fit" in report

    def test_ssm_slot_beats_attention_slot(self, params):
        """The capacity prize in miniature: even at this toy max_len
        the SSM slot is smaller; the ratio grows linearly with
        max_len."""
        attn = Generator(_params(block_type="attention", seed=2),
                         V, T, num_layers=L, num_heads=H, dim=DIM,
                         batch_size=2)
        ssm = _gen(params, 2)
        assert ssm.state_bytes_per_slot() < \
            attn.state_bytes_per_slot()


class TestHandoff:
    def test_disagg_handoff_parity_and_o1_bytes(self, params):
        """Prefill-replica handoff into an SSM decode pool: replies
        match the colocated path, and the blob on the wire is the
        SAME bytes for a 4-token and a 12-token prompt — the O(1)
        handoff the attention path can't have."""
        single = _gen(params, 1)
        pre = PrefillEngine(_gen(params, 2))
        rng = np.random.RandomState(7)
        p_short = rng.randint(0, V, (4,))
        p_long = rng.randint(0, V, (12,))
        h_short = pre.prefill(p_short)
        h_long = pre.prefill(p_long)
        assert kv_blob_nbytes(h_short["kv_blob"]) == \
            kv_blob_nbytes(h_long["kv_blob"])
        with _gen(params, B).serving_decoder() as dec:
            for p, h, n in ((p_short, h_short, 6), (p_long, h_long, 4)):
                got = dec.submit(p, n, handoff=h).result(120.0)
                np.testing.assert_array_equal(
                    got, single.generate(p[None], n)[0])
            assert dec.stats()["prefills"] == 0

    def test_coalesced_prefill_splits_mixed_lengths(self, params):
        """A mixed-length coalesced group must NOT right-pad under
        SSM (padding would be absorbed into the recurrent state):
        _run_group splits it into per-length subgroups whose replies
        are exactly the solo replies."""
        from mxnet_tpu.serve.prefill import _PendingPrefill
        eng = PrefillEngine(_gen(params, 2))
        rng = np.random.RandomState(11)
        p4 = rng.randint(0, V, (4,))
        p6 = rng.randint(0, V, (6,))
        group = [_PendingPrefill(np.asarray(p, np.int64), 0.0, None,
                                 None, 0) for p in (p4, p6)]
        eng._run_group(group)
        for g, p in zip(group, (p4, p6)):
            assert g.exc is None
            solo = eng.prefill(p)
            tok, blob, _ = g.out
            assert tok == solo["first_token"]
            for name, arr in solo["kv_blob"]["rows"].items():
                np.testing.assert_array_equal(
                    np.asarray(arr),
                    np.asarray(blob["rows"][name]))

    def test_migration_round_trip_mid_decode(self, params):
        """Evacuate a seeded session mid-decode, resume it on a
        second pool: remaining tokens bit-identical — the state blob
        round-trips exactly and the PRNG re-derives its splits."""
        import threading
        import time
        single = _gen(params, 1)
        p = np.arange(1, 6)
        # 16 tokens, not 8: the toy step takes a millisecond or two,
        # so a short row could finish between the poll that sees three
        # tokens and the evacuation (evacuate() == 0, one run in six)
        want = single.generate(p[None], 16, temperature=0.8, top_k=8,
                               seed=7)[0]
        d1 = _gen(params, 2).serving_decoder()
        d2 = _gen(params, 2).serving_decoder()
        try:
            # no race with the clock: the decode thread stops where
            # the row has emitted three tokens (`on_logits` runs on it,
            # before the next is picked) until the evacuation is
            # queued, so the row cannot run out first however late
            # this thread is scheduled
            three = threading.Event()

            def hold(req, _row):
                if len(req.emitted) >= 3 and not three.is_set():
                    three.set()
                    end = time.time() + 60.0
                    while not d1._evac_waiters and time.time() < end:
                        time.sleep(0.0005)

            d1.on_logits = hold
            fut = d1.submit(p, 16, temperature=0.8, top_k=8, seed=7)
            assert three.wait(60.0), "3 emitted tokens"
            assert d1.evacuate() == 1
            with pytest.raises(SessionEvacuated) as ei:
                fut.result(10.0)
            state = ei.value.state
            # the exported blob is the O(1) state: one (H, hd, hd)
            # f32 blob per layer, whatever pos it was exported at
            hd = DIM // H
            for name, arr in state["kv_blob"]["rows"].items():
                assert arr.shape == (H, hd, hd)
                assert arr.dtype == np.float32
            got = d2.submit(p, 16, temperature=0.8, top_k=8, seed=7,
                            resume=state).result(120.0)
            np.testing.assert_array_equal(got, want)
            assert d2.stats()["resumed"] == 1
            assert d2.stats()["prefills"] == 0
        finally:
            d1.close()
            d2.close()


class TestRefusals:
    def test_explicit_draft_refused(self, params):
        gen = _gen(params, 2)
        attn_draft = Generator(_params(block_type="attention", seed=2),
                               V, T, num_layers=L, num_heads=H,
                               dim=DIM, batch_size=2)
        with pytest.raises(ValueError, match="speculative"):
            gen.serving_decoder(draft=attn_draft)

    def test_env_draft_refused(self, params, monkeypatch):
        monkeypatch.setenv("MXNET_SPEC_DRAFT", "layers=1")
        with pytest.raises(ValueError, match="speculative"):
            _gen(params, 2).serving_decoder()

    def test_rolling_cache_refused(self, params):
        with pytest.raises(ValueError, match="rolling_cache"):
            _gen(params, 2, rolling_cache=True)

    def test_streaming_works(self, params):
        """Streaming frames ride the ordinary _emit path — SSM slots
        change nothing (one quick end-to-end check)."""
        pool = _gen(params, 2)
        single = _gen(params, 1)
        p = np.arange(2, 7)
        frames = []
        with pool.serving_decoder() as dec:
            row = dec.handle_generate_stream(
                {"prompt": p.tolist(), "max_new_tokens": 6},
                lambda toks, off: frames.append((off, list(toks))))
        want = single.generate(p[None], 6)[0]
        np.testing.assert_array_equal(row, want)
        streamed = [t for _, chunk in sorted(frames) for t in chunk]
        np.testing.assert_array_equal(streamed, want[len(p):])
