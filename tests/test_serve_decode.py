"""Continuous-batching decode (mxnet_tpu/serve/decode.py): the fixed
slot pool over the on-device KV cache.

Load-bearing acceptance gate: continuous-batching decode matches the
static ``Generator.generate`` token-for-token per sequence — greedy
exactly, sampled against a batch_size=1 generate with the same seed
(each request carries its own PRNG stream). Plus the throughput
property the subsystem exists for: ragged workloads finish in fewer
decode steps than static batching's worst sequence dictates.
"""
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import make_train_step
from mxnet_tpu.serve import EngineClosed, Overloaded, SessionEvacuated

pytestmark = pytest.mark.serve

V, L, H, DIM, T, B = 50, 2, 2, 32, 24, 3


def _params(pos_encoding="learned", seed=0, num_kv_heads=None,
            block_type="attention"):
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T,
                                 pos_encoding=pos_encoding,
                                 num_kv_heads=num_kv_heads,
                                 block_type=block_type)
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


def _hold_admission(dec):
    """Let a test decide how many queued prompts one admit round
    pops: the decode loop skips admission until the queue holds
    ``hold[0]`` requests (one shot: the round that fires resets it to
    1). The loop is never blocked, only its ``_admit`` call skipped."""
    hold = [1]
    admit = dec._admit

    def gated():
        if len(dec._queue) >= hold[0]:
            hold[0] = 1
            admit()

    dec._admit = gated
    return hold


def _submit_together(dec, hold, reqs, **kw):
    """Submit ``reqs`` [(prompt, max_new)] so that ONE admit round
    pops them all: wait until everything submitted before sits in a
    slot or has finished and that many slots are free, then hold
    admission until every one is queued."""
    deadline = time.time() + 120.0
    while True:
        st = dec.stats()
        if st["active"] + st["finished"] == st["admitted"] and \
                dec._B - st["active"] >= len(reqs):
            break
        assert time.time() < deadline
        time.sleep(0.002)
    hold[0] = len(reqs)
    return [dec.submit(p, n, **kw) for p, n in reqs]


class TestParity:
    @pytest.mark.parametrize("group,chunk", [
        (None, None), (1, None), (3, None), (4, None),
        (None, 3), (3, 3)],
        ids=["free", "by1", "by3", "byB", "free-chunked",
             "by3-chunked"])
    def test_greedy_matches_static_generate_ragged(
            self, params, monkeypatch, group, chunk):
        """ACCEPTANCE: ragged requests through a slot pool == static
        per-sequence generate, token for token (eos and budget endings
        both exercised) — free-running through 3 slots, and through 4
        slots with same-length prompts admitted 1, 3 and B to a round
        (the compiled cache merge at every row count, into whichever
        slots the ragged endings freed); likewise with prompts over
        ``MXNET_PREFILL_CHUNK`` prefilled chunk by chunk."""
        if chunk:
            monkeypatch.setenv("MXNET_PREFILL_CHUNK", str(chunk))
        single = _gen(params, 1)
        rng = np.random.RandomState(3)
        if group is None:
            pool = _gen(params, B)
            prompts = [rng.randint(0, V, (p,)) for p in
                       (4, 6, 4, 5, 4, 6, 7)]
            maxnew = [8, 3, 12, 5, 2, 9, 4]
        else:
            pool = _gen(params, 4)
            # one prompt length to a wave, ragged budgets inside it
            lengths = [p for p in (4, 6, 3, 5, 7) for _ in range(group)]
            prompts = [rng.randint(0, V, (p,)) for p in lengths]
            maxnew = [int(n) for n in rng.randint(2, 13, len(prompts))]
        with pool.serving_decoder() as dec:
            if group is None:
                futs = [dec.submit(p, n, eos_id=0)
                        for p, n in zip(prompts, maxnew)]
            else:
                hold = _hold_admission(dec)
                futs = []
                for lo in range(0, len(prompts), group):
                    futs += _submit_together(
                        dec, hold, list(zip(prompts, maxnew))
                        [lo:lo + group], eos_id=0)
            got = [f.result(120.0) for f in futs]
            st = dec.stats()
        for i, (p, n) in enumerate(zip(prompts, maxnew)):
            want = single.generate(p[None], n, eos_id=0)[0]
            np.testing.assert_array_equal(got[i], want)
        # slot reuse happened: more sequences than slots were admitted
        assert st["finished"] == len(prompts) > pool.batch_size
        # every prefill, whole or chunked, ends in ONE compiled merge:
        # one program a rung it merges from
        assert st["merges"] == st["prefills"]
        assert 1 <= st["merge_programs"] <= len(dec._rungs)
        if group is None:
            # the throughput property: static batching pays
            # ceil(N/B) * max(maxnew) decode steps; continuous must
            # beat it
            static_steps = -(-len(prompts) // B) * max(maxnew)
            assert st["steps"] < static_steps
        elif not chunk:
            # each wave was one group: one prefill, one merge
            assert st["prefills"] == len(prompts) // group

    def test_sampled_matches_batch1_generate(self, params):
        """A sampled request reproduces a batch_size=1 generate with
        the same seed — its PRNG stream is per-request, independent of
        pool composition."""
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, V, (5,))
        with pool.serving_decoder() as dec:
            # crowd the pool so the sampled row shares steps with
            # other active slots
            other = [dec.submit(rng.randint(0, V, (4,)), 10)
                     for _ in range(2)]
            f = dec.submit(prompt, 6, temperature=0.8, top_k=5,
                           seed=42)
            got = f.result(120.0)
            for o in other:
                o.result(120.0)
        want = single.generate(prompt[None], 6, temperature=0.8,
                               top_k=5, seed=42)[0]
        np.testing.assert_array_equal(got, want)

    def test_rope_per_row_positions(self):
        """RoPE path: per-row (B, T) position ids rotate each slot at
        its own depth — greedy parity against static generate."""
        params = _params(pos_encoding="rope", seed=4)
        pool = Generator(params, V, T, num_layers=L, num_heads=H,
                         dim=DIM, batch_size=2, pos_encoding="rope")
        single = Generator(params, V, T, num_layers=L, num_heads=H,
                           dim=DIM, batch_size=1, pos_encoding="rope")
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, V, (p,)) for p in (3, 6, 4)]
        maxnew = [9, 4, 6]
        with pool.serving_decoder() as dec:
            got = [dec.submit(p, n).result(120.0)
                   for p, n in zip(prompts, maxnew)]
        for p, n, g in zip(prompts, maxnew, got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], n)[0])

    def test_generate_many_convenience(self, params):
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(13)
        prompts = [rng.randint(0, V, (4,)) for _ in range(4)]
        with pool.serving_decoder() as dec:
            got = dec.generate_many(prompts, 5, eos_id=0,
                                    timeout=120.0)
        for p, g in zip(prompts, got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], 5, eos_id=0)[0])


class TestContract:
    def test_capacity_and_table_validation(self, params):
        pool = _gen(params, B)
        with pool.serving_decoder() as dec:
            with pytest.raises(ValueError, match="max_len"):
                dec.submit(np.zeros(20, np.int64), 10)
            with pytest.raises(ValueError, match="empty"):
                dec.submit(np.zeros(0, np.int64), 2)

    def test_zero_new_tokens_is_the_prompt(self, params):
        pool = _gen(params, B)
        with pool.serving_decoder() as dec:
            prompt = np.arange(5)
            np.testing.assert_array_equal(
                dec.submit(prompt, 0).result(10.0), prompt)

    def test_queue_cap_sheds_typed(self, params):
        pool = _gen(params, B)
        dec = pool.serving_decoder(queue_cap=0)
        try:
            with pytest.raises(Overloaded):
                dec.submit(np.arange(4), 2)
        finally:
            dec.close()

    def test_close_drains_then_rejects(self, params):
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(17)
        prompts = [rng.randint(0, V, (4,)) for _ in range(5)]
        dec = pool.serving_decoder()
        futs = [dec.submit(p, 6) for p in prompts]
        dec.close()
        for p, f in zip(prompts, futs):
            np.testing.assert_array_equal(
                f.result(1.0), single.generate(p[None], 6)[0])
        with pytest.raises(EngineClosed):
            dec.submit(np.arange(4), 2)

    def test_rolling_cache_still_refused(self, params):
        """The whole-stack spelling stays refused in a slot pool (its
        max_len is the circular capacity, not the bound on a request),
        and the refusal names the spelling that is served since the
        circular op reads one depth a row: attention_layers (PR 42)."""
        rolling = Generator(params, V, T, num_layers=L, num_heads=H,
                            dim=DIM, batch_size=B, rolling_cache=True,
                            attention_window=8)
        with pytest.raises(ValueError, match="rolling") as e:
            rolling.serving_decoder()
        assert "attention_layers" in str(e.value)

    def test_sampling_contract_checked_at_submit(self, params):
        pool = _gen(params, B)
        with pool.serving_decoder() as dec:
            with pytest.raises(ValueError, match="temperature"):
                dec.submit(np.arange(4), 2, top_k=3)


def _token_major(c):
    """A cache as every tree before PR 30 held it — (B, Hkv, C, hd),
    its int8 scales (B, Hkv, C) — in the layout the device keeps now:
    (B, C, Hkv*hd) and (B, C, Hkv), a token's heads side by side."""
    c = np.asarray(c)
    return np.moveaxis(c, 1, 2).reshape(c.shape[0], c.shape[2], -1)


def _old_layout_reference(q, k, v, kc, vc, pos, window=0, rolling=False,
                          scales=None):
    """float32 cached attention in the OLD indexing, caches
    (B, Hkv, C, hd): row b's Tn new tokens land at pos[b] + r (slot
    (pos + r) % C when rolling), query row r attends what the causal
    and window masks allow, each kv head serving its G query heads.
    ``scales`` (ks, vs), each (B, Hkv, C): int8 caches, absmax/127 a
    token a head. Returns (out, kc, vc[, ks, vs])."""
    import jax
    import jax.numpy as jnp
    B_, H_, Tn, D_ = q.shape
    Hkv, C = kc.shape[1], kc.shape[2]
    pos = np.broadcast_to(np.asarray(pos, np.int64).reshape(-1), (B_,))
    if scales is not None:
        def quantize(x):
            s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
            return jnp.round(x / s[..., None]).astype(jnp.int8), s
        (k, ksn), (v, vsn) = quantize(k), quantize(v)
        ks, vs = scales
    at = pos[:, None] + np.arange(Tn)[None]              # (B, Tn)
    slot = at % C if rolling else at
    for b in range(B_):
        kc = kc.at[b, :, slot[b]].set(jnp.moveaxis(k[b], 0, 1))
        vc = vc.at[b, :, slot[b]].set(jnp.moveaxis(v[b], 0, 1))
        if scales is not None:
            ks = ks.at[b, :, slot[b]].set(ksn[b].T)
            vs = vs.at[b, :, slot[b]].set(vsn[b].T)
    kf, vf = kc.astype(jnp.float32), vc.astype(jnp.float32)
    if scales is not None:
        kf, vf = kf * ks[..., None], vf * vs[..., None]
    cols = np.arange(C)[None, None]
    if rolling:
        end = at[:, -1:, None]
        held = end - ((end - cols) % C)      # newest position a slot has
        valid = (held >= 0) & (held <= at[..., None]) & \
            (at[..., None] - held < window)
    else:
        valid = cols <= at[..., None]
        if window:
            valid = valid & (at[..., None] - cols < window)
    qg = q.reshape(B_, Hkv, H_ // Hkv, Tn, D_)
    sc = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf,
                    precision=jax.lax.Precision.HIGHEST) * D_ ** -0.5
    sc = jnp.where(jnp.asarray(valid)[:, None, None], sc, -1e30)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(sc, axis=-1),
                     vf, precision=jax.lax.Precision.HIGHEST)
    out = out.reshape(B_, H_, Tn, D_)
    return (out, kc, vc) + (() if scales is None else (ks, vs))


@pytest.mark.parametrize("tn", [1, 5], ids=["Tn1", "TnP"])
@pytest.mark.parametrize("variant", ["scalar", "per_row", "rolling",
                                     "q8_scalar", "q8_per_row"])
def test_cached_attention_reads_token_rows_like_the_old_layout(variant,
                                                               tn):
    """The five cached-attention variants over (B, C, Hkv*hd) token
    rows against a float32 reference written in the old
    (B, Hkv, C, hd) indexing: GQA (4 query heads on 2 kv heads), a
    window mask, rows at unequal depths with one ending on the
    cache's last row (C - 1: the latest start that does not clamp),
    one decode token and a chunk, int8 rows with their scales. The
    caches must be the reference's own, re-laid: bit for bit."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    rng = np.random.RandomState(43)
    B_, H_, Hkv, D_, C, window = 3, 4, 2, 8, 16, 6
    q = jnp.asarray(rng.randn(B_, H_, tn, D_), jnp.float32)
    k = jnp.asarray(rng.randn(B_, Hkv, tn, D_), jnp.float32)
    v = jnp.asarray(rng.randn(B_, Hkv, tn, D_), jnp.float32)
    q8 = variant.startswith("q8")
    if q8:
        kc, vc = (jnp.asarray(rng.randint(-127, 128, (B_, Hkv, C, D_)),
                              jnp.int8) for _ in range(2))
        scales = tuple(jnp.asarray(rng.rand(B_, Hkv, C) + 0.01,
                                   jnp.float32) for _ in range(2))
    else:
        kc, vc = (jnp.asarray(rng.randn(B_, Hkv, C, D_), jnp.float32)
                  for _ in range(2))
        scales = None
    if variant.endswith("per_row"):
        pos = np.array([2, C - tn, 9 - tn])      # unequal; one at the end
    elif variant == "rolling":
        pos = np.array([C + 3])                  # wrapped once already
    else:
        pos = np.array([C - tn])
    want = _old_layout_reference(q, k, v, kc, vc, pos, window=window,
                                 rolling=variant == "rolling",
                                 scales=scales)
    caches = [jnp.asarray(_token_major(c))
              for c in (kc, vc) + (scales or ())]
    posf = jnp.asarray(pos, jnp.float32)
    if variant == "rolling":
        got = att.rolling_cached_attention(q, k, v, *caches, posf,
                                           window)
    elif q8:
        got = att.cached_attention_q8(q, k, v, *caches, posf,
                                      window=window)
    else:
        got = att.cached_attention(q, k, v, *caches, posf,
                                   window=window)
    assert len(got) == len(want)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-6)
    for g, w in zip(got[1:], want[1:]):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), _token_major(w))


def _q8_shared_reference(q, k, v, kc, vc, ks, vs, p0, scale=None,
                         window=0):
    """Pinned copy of the pre-per-row shared-position
    cached_attention_q8 math, in the (B, Hkv, C, hd) indexing of its
    day. What the live op STORES must stay bitwise equal to this
    forever, whatever layout it stores it in."""
    import jax
    import jax.numpy as jnp
    B_, H_, Tn, D_ = q.shape
    Hkv = kc.shape[1]
    G = H_ // Hkv
    if scale is None:
        scale = D_ ** -0.5
    p0 = jnp.reshape(jnp.asarray(p0), ()).astype(jnp.int32)

    def quantize(x):
        xf = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
        return jnp.round(xf / s[..., None]).astype(jnp.int8), s

    kq, kss = quantize(k)
    vq, vss = quantize(v)
    kc = jax.lax.dynamic_update_slice(kc, kq, (0, 0, p0, 0))
    vc = jax.lax.dynamic_update_slice(vc, vq, (0, 0, p0, 0))
    ks = jax.lax.dynamic_update_slice(ks, kss, (0, 0, p0))
    vs = jax.lax.dynamic_update_slice(vs, vss, (0, 0, p0))
    kf = kc.astype(jnp.float32) * ks[..., None]
    vf = vc.astype(jnp.float32) * vs[..., None]
    qg = q.reshape(B_, Hkv, G, Tn, D_)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), kf,
                   precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(kc.shape[2])[None, :]
    rows = jnp.arange(Tn)[:, None]
    valid = cols <= p0 + rows
    if window:
        valid = valid & (p0 + rows - cols < window)
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf,
                     precision=jax.lax.Precision.DEFAULT)
    return (out.reshape(B_, H_, Tn, D_).astype(q.dtype),
            kc, vc, ks, vs)


class TestQuantizedKV:
    """Int8 KV caches through the per-row continuous-batching path
    (PR 13 tentpole): ragged pool decode == batch_size=1 quantized
    generate, scale caches ride the prefill merge, GQA grouping
    holds, and the shared-position op is bitwise untouched."""

    def test_greedy_q8_matches_batch1_quantized_ragged(self, params):
        """ACCEPTANCE: ragged greedy decode under quantize_kv=True
        matches batch_size=1 quantized Generator.generate
        token-for-token, with slot turnover exercised."""
        pool = _gen(params, B, quantize_kv=True)
        single = _gen(params, 1, quantize_kv=True)
        rng = np.random.RandomState(23)
        prompts = [rng.randint(0, V, (p,)) for p in
                   (4, 6, 4, 5, 4, 7)]
        maxnew = [8, 3, 12, 5, 2, 6]
        with pool.serving_decoder() as dec:
            futs = [dec.submit(p, n, eos_id=0)
                    for p, n in zip(prompts, maxnew)]
            got = [f.result(120.0) for f in futs]
            st = dec.stats()
        for i, (p, n) in enumerate(zip(prompts, maxnew)):
            want = single.generate(p[None], n, eos_id=0)[0]
            np.testing.assert_array_equal(got[i], want)
        assert st["finished"] == len(prompts) > B   # slot turnover

    @pytest.mark.slow
    def test_q8_gqa_head_grouping(self):
        """GQA + int8: the per-row q8 op groups q heads over the
        (fewer) cached kv heads exactly like the shared path. Slow
        tier (~9 s on the 1-core tier-1 host); GQA+int8 keeps a fast
        exemplar in test_serve_disagg.py's int8+GQA handoff parity and
        the non-GQA q8 pool parity stays fast above."""
        params = _params(seed=6, num_kv_heads=1)
        pool = Generator(params, V, T, num_layers=L, num_heads=H,
                         dim=DIM, batch_size=2, num_kv_heads=1,
                         quantize_kv=True)
        single = Generator(params, V, T, num_layers=L, num_heads=H,
                           dim=DIM, batch_size=1, num_kv_heads=1,
                           quantize_kv=True)
        rng = np.random.RandomState(29)
        prompts = [rng.randint(0, V, (p,)) for p in (3, 5, 4)]
        maxnew = [7, 4, 6]
        with pool.serving_decoder() as dec:
            got = [dec.submit(p, n).result(120.0)
                   for p, n in zip(prompts, maxnew)]
        for p, n, g in zip(prompts, maxnew, got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], n)[0])

    def test_prefill_merge_scatters_scale_rows(self, params):
        """The batch-axis cache-row merge carries the per-token f32
        scale caches to the RIGHT slots (a merged int8 row without
        its scales would dequantize to garbage)."""
        pool = _gen(params, B, quantize_kv=True)
        rng = np.random.RandomState(31)
        pa, pb = rng.randint(0, V, (4,)), rng.randint(0, V, (6,))
        with pool.serving_decoder() as dec:
            fa = dec.submit(pa, 3)
            fb = dec.submit(pb, 3)
            fa.result(120.0)
            fb.result(120.0)
            aux = {k: np.asarray(v) for k, v in dec._aux.items()}
        for slot, prompt in ((0, pa), (1, pb)):
            rows = np.stack([prompt] * B).astype(np.float32)
            _, ref = pool._forward(pool._fresh_aux(), rows, 0)
            P = len(prompt)
            for name in aux:
                want = np.asarray(ref[name])[0]
                np.testing.assert_array_equal(
                    aux[name][slot, :P], want[:P])
                if name.endswith(("_k_scale", "_v_scale")):
                    assert (aux[name][slot, :P] > 0).all()

    def test_q8_shared_pos_bitwise_vs_pinned_reference(self):
        """cached_attention_q8 stores bitwise what the pre-per-row
        implementation stored, under a (1,) pos and under a (B,) pos
        with equal entries, and its output agrees with it up to the
        products' association order."""
        import jax.numpy as jnp
        from mxnet_tpu.ops.attention import cached_attention_q8
        rng = np.random.RandomState(37)
        B_, H_, Hkv, Tn, D_, C = 2, 4, 2, 3, 8, 16
        q = jnp.asarray(rng.randn(B_, H_, Tn, D_), jnp.float32)
        k = jnp.asarray(rng.randn(B_, Hkv, Tn, D_), jnp.float32)
        v = jnp.asarray(rng.randn(B_, Hkv, Tn, D_), jnp.float32)
        kc = jnp.asarray(rng.randint(-127, 128, (B_, Hkv, C, D_)),
                         jnp.int8)
        vc = jnp.asarray(rng.randint(-127, 128, (B_, Hkv, C, D_)),
                         jnp.int8)
        ks = jnp.asarray(rng.rand(B_, Hkv, C) + 0.01, jnp.float32)
        vs = jnp.asarray(rng.rand(B_, Hkv, C) + 0.01, jnp.float32)
        p0 = 5
        rows = [jnp.asarray(_token_major(c)) for c in (kc, vc, ks, vs)]
        ref = _q8_shared_reference(q, k, v, kc, vc, ks, vs, p0)
        for pos in (jnp.full((1,), p0, jnp.float32),
                    jnp.full((B_,), p0, jnp.float32)):
            got = cached_attention_q8(q, k, v, *rows, pos)
            # the products group heads by lane tile now: the same
            # sums in another order
            np.testing.assert_allclose(
                np.asarray(got[0]), np.asarray(ref[0]),
                rtol=1e-6, atol=1e-6)
            for g, r in zip(got[1:], ref[1:]):
                np.testing.assert_array_equal(np.asarray(g),
                                              _token_major(r))

    def test_per_row_q8_capacity_check(self):
        import jax.numpy as jnp
        from mxnet_tpu.ops.attention import cached_attention_q8
        rng = np.random.RandomState(41)
        B_, Hkv, Tn, D_, C = 2, 2, 2, 8, 8
        q = jnp.asarray(rng.randn(B_, Hkv, Tn, D_), jnp.float32)
        k = v = q
        kc = vc = jnp.zeros((B_, C, Hkv * D_), jnp.int8)
        ks = vs = jnp.zeros((B_, C, Hkv), jnp.float32)
        with pytest.raises(ValueError, match="overrun"):
            cached_attention_q8(q, k, v, kc, vc, ks, vs,
                                jnp.asarray([0.0, 7.0]))

    def test_kv_bytes_gauge_and_slot_sizing(self, params):
        """serve.decode.kv_bytes_per_slot is published by Generator
        (static) and ContinuousDecoder (live pool, same number), int8
        caches genuinely shrink it, and describe() turns an HBM
        budget into a slot count."""
        from mxnet_tpu import telemetry
        g = telemetry.gauge("serve.decode.kv_bytes_per_slot")
        fp32 = _gen(params, B)
        fp32_bps = fp32.kv_cache_bytes() // B
        assert g.value == fp32_bps
        q8 = _gen(params, B, quantize_kv=True)
        q8_bps = q8.kv_cache_bytes() // B
        assert g.value == q8_bps
        assert q8_bps < 0.55 * fp32_bps
        with q8.serving_decoder() as dec:
            # the live pool republishes the same figure, measured from
            # the actual device arrays
            assert dec._kv_bytes_per_slot == q8_bps
            assert g.value == q8_bps
            report = dec.describe(hbm_budget=q8_bps * 10 + 1)
            assert "kv_bytes_per_slot: %d" % q8_bps in report
            assert "10 slot(s) fit" in report
            # introspect(): the stats-frame shape a decode replica
            # publishes — decode_free_slots is what the fleet
            # router's session placement consumes (serve/router.py)
            intro = dec.introspect()
            assert intro["decode_free_slots"] == B
            assert intro["slots"] == B
            assert intro["queue_depth"] == 0
            assert intro["in_flight"] == 0
            assert intro["draining"] is False


def _spec_dec(pool, lookahead=3, draft_layers=1, **kw):
    return pool.serving_decoder(
        draft=pool.truncated_draft(num_layers=draft_layers),
        lookahead=lookahead, **kw)


class TestSpeculative:
    """Per-slot draft/verify continuous batching (PR 18 tentpole):
    rounds of gamma compiled (B, 1) draft steps plus ONE (B, gamma+1)
    target verify forward, with common-random-numbers acceptance —
    so every output stays byte-identical to plain ``generate`` and
    ``speculative`` is a pure performance hint."""

    def test_spec_mixed_pool_matches_generate_ragged(self, params):
        """ACCEPTANCE: speculative and plain requests share the slot
        pool mid-flight; every sequence == static generate token for
        token, with eos and budget endings and slot turnover."""
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(43)
        prompts = [rng.randint(0, V, (p,)) for p in
                   (4, 6, 4, 5, 4, 6)]
        maxnew = [8, 3, 12, 5, 4, 9]
        spec = [True, False, True, False, True, True]
        with _spec_dec(pool) as dec:
            futs = [dec.submit(p, n, eos_id=0, speculative=s)
                    for p, n, s in zip(prompts, maxnew, spec)]
            got = [f.result(120.0) for f in futs]
            st = dec.stats()
        for p, n, g in zip(prompts, maxnew, got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], n, eos_id=0)[0])
        assert st["finished"] == len(prompts) > B    # slot turnover
        # the draft genuinely ran: rounds happened, proposals were
        # verified, and speculative admissions paid draft prefills
        # (batched admissions may share one, so <= the request count)
        assert st["spec_rounds"] > 0
        assert st["draft_steps"] >= st["spec_rounds"]
        assert 0 < st["spec_accepted"] <= st["spec_proposed"]
        assert 0 < st["draft_prefills"] <= sum(spec)

    def test_spec_sampled_matches_batch1_generate(self, params):
        """Sampled speculative request reproduces a batch_size=1
        generate with the same seed — acceptance reuses the EXACT
        per-token noise the verify pick consumes (common random
        numbers), so the distribution is not just equal, the draws
        are."""
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(47)
        prompt = rng.randint(0, V, (5,))
        with _spec_dec(pool) as dec:
            # crowd the pool: a plain greedy row rides every verify
            # forward as a passenger
            other = dec.submit(rng.randint(0, V, (4,)), 10)
            f = dec.submit(prompt, 6, temperature=0.8, top_k=5,
                           seed=42, speculative=True)
            got = f.result(120.0)
            other.result(120.0)
        want = single.generate(prompt[None], 6, temperature=0.8,
                               top_k=5, seed=42)[0]
        np.testing.assert_array_equal(got, want)

    def test_spec_streaming_one_token_at_a_time(self, params):
        """A round commits up to gamma+1 tokens at once, but sinks
        still see them ONE at a time, in order, then the None
        terminator — the streaming contract is spec-oblivious."""
        pool = _gen(params, B)
        rng = np.random.RandomState(53)
        prompt = rng.randint(0, V, (4,))
        seen = []
        with _spec_dec(pool) as dec:
            fut = dec.submit(prompt, 8, speculative=True)
            fut.subscribe(seen.append)
            got = fut.result(120.0)
            deadline = time.monotonic() + 10.0
            while (not seen or seen[-1] is not None) and \
                    time.monotonic() < deadline:
                time.sleep(0.005)
        assert seen[-1] is None
        np.testing.assert_array_equal(np.asarray(seen[:-1]),
                                      got[len(prompt):])

    def test_spec_headroom_checked_at_submit(self, params):
        """Verify rounds write up to gamma speculative cache entries
        past EVERY live row's depth, so with a draft attached each
        admission needs P + n <= min(max_lens) - gamma — plain
        requests included, checked loudly at submit."""
        pool = _gen(params, B)
        with _spec_dec(pool, lookahead=4) as dec:   # cap = 24 - 4
            with pytest.raises(ValueError, match="headroom"):
                dec.submit(np.arange(1, 16), 8, speculative=True)
            with pytest.raises(ValueError, match="headroom"):
                dec.submit(np.arange(1, 16), 8)     # plain rows too
            # at the cap is fine
            dec.submit(np.arange(1, 13), 8,
                       speculative=True).result(120.0)
        # a lookahead that leaves no usable headroom at all is a
        # construction-time error, not a submit-time surprise
        with pytest.raises(ValueError, match="headroom"):
            _spec_dec(pool, lookahead=T)

    def test_spec_jit_cache_discipline(self, params):
        """The throughput contract: the target owns exactly TWO
        compiled programs — the (B, 1) step and the (B, gamma+1)
        verify — and the draft exactly ONE, however ragged the
        workload."""
        pool = _gen(params, B)
        rng = np.random.RandomState(59)
        with _spec_dec(pool) as dec:
            assert dec.introspect()["speculative"] is True
            # plain request first, alone: pins the (B, 1) step trace
            dec.submit(rng.randint(0, V, (4,)), 6).result(120.0)
            for p, n in ((3, 8), (6, 4), (5, 11)):
                dec.submit(rng.randint(0, V, (p,)), n,
                           speculative=True).result(120.0)
            assert telemetry.gauge(
                "serve.decode.jit_cache_size").value == 2
            assert telemetry.gauge(
                "serve.spec.draft_jit_cache_size").value == 1

    def test_spec_evacuate_resume_carries_hint(self, params):
        """Mid-decode migration of a speculative session: the export
        state records the hint, and the resumed stream on a second
        draft-attached pool emits the remaining tokens
        bit-identically."""
        single = _gen(params, 1)
        p = np.arange(1, 6)
        want = single.generate(p[None], 8, temperature=0.8, top_k=8,
                               seed=7)[0]
        d1 = _spec_dec(_gen(params, 2))
        d2 = _spec_dec(_gen(params, 2))
        try:
            fut = d1.submit(p, 8, temperature=0.8, top_k=8, seed=7,
                            speculative=True)
            deadline = time.monotonic() + 10.0
            while len(fut.emitted) < 3 and \
                    time.monotonic() < deadline:
                time.sleep(0.005)
            assert len(fut.emitted) >= 3
            assert d1.evacuate() == 1
            with pytest.raises(SessionEvacuated) as ei:
                fut.result(10.0)
            state = ei.value.state
            assert state["speculative"] is True
            got = d2.submit(p, 8, temperature=0.8, top_k=8, seed=7,
                            resume=state,
                            speculative=True).result(120.0)
            np.testing.assert_array_equal(got, want)
            assert d2.stats()["resumed"] == 1
        finally:
            d1.close()
            d2.close()

    def test_spec_env_draft_config(self, params, monkeypatch):
        """MXNET_SPEC_DRAFT attaches a truncated draft to every
        decoder built without an explicit ``draft=`` — the
        zero-code-change opt-in subprocess replicas use — and gamma
        is honored."""
        monkeypatch.setenv("MXNET_SPEC_DRAFT", "layers=1,gamma=2")
        pool = _gen(params, B)
        single = _gen(params, 1)
        rng = np.random.RandomState(61)
        prompt = rng.randint(0, V, (5,))
        with pool.serving_decoder() as dec:
            assert dec._draft is not None
            assert dec._draft.num_layers == 1
            assert dec._gamma == 2
            got = dec.submit(prompt, 7,
                             speculative=True).result(120.0)
            st = dec.stats()
        np.testing.assert_array_equal(
            got, single.generate(prompt[None], 7)[0])
        assert st["spec_rounds"] > 0

    def test_spec_env_parse_errors(self, monkeypatch):
        """spec_draft() validates loudly — a typo'd fleet env var must
        fail fast, not silently decode draft-less."""
        from mxnet_tpu.serve.decode import spec_draft
        for raw, msg in [("1,gamma=2", "fieldless"),
                         ("layers=one", "integer"),
                         ("layers=1,speed=9", "unknown field"),
                         ("gamma=2", "layers >= 1"),
                         ("layers=0", "layers >= 1"),
                         ("layers=1,gamma=0", "gamma >= 1")]:
            monkeypatch.setenv("MXNET_SPEC_DRAFT", raw)
            with pytest.raises(ValueError, match=msg):
                spec_draft()
        monkeypatch.setenv("MXNET_SPEC_DRAFT", "  ")
        assert spec_draft() is None
        monkeypatch.setenv("MXNET_SPEC_DRAFT", "layers=2,gamma=5")
        assert spec_draft() == (2, 5)


# ---------------------------------------------------------------------------
# the compiled, donated cache merge and the compiled fresh pool (ISSUE 25)
# ---------------------------------------------------------------------------

MB = 4                                   # pool width of the merge cases
KINDS = ["kv", "q8", "ssm", "draft", "sharded"]


def _mesh_2x2():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))


@pytest.fixture(scope="module")
def merge_decs(params):
    """One idle decoder per kind of decode-state pytree: bf16/f32 k/v
    rows, int8 rows with their f32 scale caches, SSM state blobs, a
    target with a one-layer draft pool, and k/v rows sharded batch
    over 'data' and heads over 'model'."""
    gens = {
        "kv": _gen(params, MB),
        "q8": _gen(params, MB, quantize_kv=True),
        "ssm": _gen(_params(block_type="ssm"), MB, block_type="ssm"),
        "draft": _gen(params, MB),
        "sharded": _gen(params, MB, mesh=_mesh_2x2()),
    }
    decs = {k: (_spec_dec(g) if k == "draft" else g.serving_decoder())
            for k, g in gens.items()}
    yield decs
    for dec in decs.values():
        dec.close()


def _random_like(aux, seed):
    """A pytree shaped, typed and placed like ``aux`` with every
    entry distinct: a merge that moved a wrong row would show."""
    import jax
    rng = np.random.RandomState(seed)
    out = {}
    for name in sorted(aux):
        a = aux[name]
        if a.dtype == np.int8:
            v = rng.randint(-127, 128, a.shape).astype(np.int8)
        else:
            v = rng.standard_normal(a.shape).astype(a.dtype)
        out[name] = jax.device_put(v, a.sharding)
    return out


def _eager_zeros(gen):
    """`_fresh_aux` as it was: one eager zeros (+ device_put under a
    mesh) per aux entry — the reference the compiled one must equal."""
    import jax
    import jax.numpy as jnp
    out = {}
    for name in gen._sym.list_auxiliary_states():
        shape, dtype = gen._aux_spec(name)
        z = jnp.zeros(shape, dtype)
        shard = None if gen.mesh is None else gen._aux_shardings()[name]
        out[name] = z if shard is None else jax.device_put(z, shard)
    return out


class TestCacheMerge:
    @pytest.mark.parametrize("n", range(1, MB + 1))
    @pytest.mark.parametrize("kind", KINDS)
    def test_merge_rows_bit_equal_eager_scatter(self, merge_decs,
                                                kind, n):
        """ACCEPTANCE: for every row count and scattered, unordered
        slots, the pool `_merge_rows` returns is bit-equal to the
        eager per-array ``.at[idx].set`` it replaced; rows outside
        ``slots`` are untouched; a sharded pool keeps its placement
        (the donation's condition)."""
        import jax.numpy as jnp
        dec = merge_decs[kind]
        draft = kind == "draft"
        like = dec._daux if draft else dec._aux
        if kind == "q8":
            assert any(a.ndim == 3 for a in like.values())
        if kind == "ssm":
            assert all(k.endswith("_state") for k in like)
        pool = _random_like(like, seed=n)
        src = _random_like(like, seed=100 + n)
        slots = [2, 0, 3, 1][:n]
        idx = jnp.asarray(np.array(slots, np.int32))
        before = {k: np.asarray(v) for k, v in pool.items()}
        want = {k: np.asarray(pool[k].at[idx].set(src[k][:n]))
                for k in pool}
        placed = {k: v.sharding for k, v in pool.items()}
        got = dec._merge_rows(pool, src, slots, draft=draft)
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])
            for row in set(range(MB)) - set(slots):
                np.testing.assert_array_equal(
                    np.asarray(got[k])[row], before[k][row])
            assert got[k].sharding.is_equivalent_to(placed[k],
                                                    got[k].ndim)

    @pytest.mark.parametrize("kind", KINDS)
    def test_fresh_aux_equals_per_array_zeros(self, merge_decs, kind):
        """The one-program `_fresh_aux` hands back what the eager walk
        did: same names, values, dtypes and shardings."""
        dec = merge_decs[kind]
        gen = dec._draft if kind == "draft" else dec._gen
        got, want = gen._fresh_aux(), _eager_zeros(gen)
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype
            assert got[k].shape == want[k].shape
            assert not np.asarray(got[k]).any()
            assert got[k].sharding.is_equivalent_to(
                want[k].sharding, got[k].ndim)
        # one compiled program, reused: a second pool is new buffers
        again = gen._fresh_aux()
        assert gen._loop_cache["fresh_aux"]._cache_size() == 1
        assert all(again[k] is not got[k] for k in got)

    @pytest.mark.parametrize("spec", [False, True],
                             ids=["target", "with-draft"])
    def test_every_group_size_twice_compiles_once(self, params, spec):
        """ACCEPTANCE: admitting every n in 1..B a second time
        compiles nothing — `merge_programs` constant and no
        backend-compile event — and `merges` counts one per admitted
        group and per pool."""
        import jax.monitoring
        pool = _gen(params, MB)
        rng = np.random.RandomState(71)
        compiles = []

        def on_event(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                compiles.append(name)

        def every_size(dec, hold):
            for n in range(1, MB + 1):
                futs = _submit_together(
                    dec, hold, [(rng.randint(0, V, (5,)), 3)] * n,
                    speculative=spec)
                for f in futs:
                    f.result(120.0)

        with (_spec_dec(pool) if spec else
              pool.serving_decoder()) as dec:
            hold = _hold_admission(dec)
            every_size(dec, hold)
            first = dec.stats()
            jax.monitoring.register_event_duration_secs_listener(
                on_event)
            try:
                every_size(dec, hold)
            finally:
                jax.monitoring.unregister_event_duration_listener(
                    on_event)
            second = dec.stats()
        pools = 2 if spec else 1
        # a merge program a pool and a rung it merges from (1 and MB
        # rows), all built at the length's first sight
        assert first["merge_programs"] == pools * len(dec._rungs)
        assert second["merge_programs"] == first["merge_programs"]
        assert compiles == []
        assert first["prefills"] == MB          # one group per size
        assert first["merges"] == pools * MB
        assert second["merges"] == 2 * pools * MB


# -- the step programs donate the pool they update (PR 28) ----------------
STEP_KINDS = ["kv", "q8", "gqa", "windowed", "ssm", "mamba2", "draft",
              "sharded"]


def _step_pool(kind, params, batch):
    """A Generator whose pool holds one kind of decode state: bf16/f32
    k/v rows, int8 rows with f32 scale caches, grouped k/v heads, rows
    under a sliding-window mask (a ROLLING cache is refused at
    construction, so this is the windowed pool that can be served),
    SSM blobs, Mamba-2 window + scan state beside k/v rows, a pool
    with a draft, rows sharded over a 2x2 mesh. Returns (generator,
    vocabulary)."""
    if kind == "mamba2":
        from cellbench.models import granite as model
        from cellbench.reference import granite as ref
        import test_granite_serve as toy
        return Generator(ref.make_params(toy.TOY, toy.SEED, "float32"),
                         toy.V, toy.T, batch_size=batch,
                         **model.generator_args(toy.TOY)), toy.V
    if kind == "ssm":
        return _gen(_params(block_type="ssm"), batch,
                    block_type="ssm"), V
    if kind == "gqa":
        return _gen(_params(seed=5, num_kv_heads=1), batch,
                    num_kv_heads=1), V
    over = {"q8": dict(quantize_kv=True),
            "windowed": dict(attention_window=4)}.get(kind, {})
    if kind == "sharded":      # built only where asked: it needs 4 devices
        over = dict(mesh=_mesh_2x2())
    return _gen(params, batch, **over), V


def _serving(kind, gen):
    return _spec_dec(gen) if kind == "draft" else gen.serving_decoder()


def _without_donation(dec):
    """Rebuild ``dec``'s step programs from their own functions with
    no donation: the program as it was, made here and not by a switch
    in the program. Before the first request only."""
    import jax
    dec._step_fn = jax.jit(dec._step_fn.__wrapped__)
    if dec._draft_step_fn is not None:
        dec._draft_step_fn = jax.jit(dec._draft_step_fn.__wrapped__)
    return dec


def _watch_pools(dec):
    """Wrap the loop's step and speculative round: for each one that
    dispatched (the pool was rebound: a call may only read the step in
    flight), record whether every leaf of the pool(s) held BEFORE it
    is deleted afterwards and every leaf of the pool(s) bound after it
    is live."""
    seen = []

    def watched(run):
        def inner():
            held = dec._aux
            before = dict(held), dict(dec._daux or {})
            d = dec._draft_steps
            out = run()
            if dec._aux is not held:
                gone = all(a.is_deleted() for a in before[0].values())
                if dec._draft_steps > d:
                    gone = gone and all(a.is_deleted()
                                        for a in before[1].values())
                live = not any(
                    a.is_deleted() for a in
                    list(dec._aux.values()) +
                    list((dec._daux or {}).values()))
                seen.append((gone, live, before[0]))
            return out
        return inner

    dec._step = watched(dec._step)
    dec._spec_round = watched(dec._spec_round)
    return seen


def _ragged_load(dec, vocab, spec):
    """More sequences than slots, ragged prompts and budgets, greedy
    and sampled: slots turn over while others decode."""
    rng = np.random.RandomState(28)
    futs = []
    for i, (p, n) in enumerate(zip((4, 6, 3, 5, 7, 4, 6),
                                   (8, 3, 10, 5, 2, 9, 4))):
        kw = dict(temperature=0.8, top_k=5, seed=i) if i % 3 == 2 \
            else {}
        futs.append(dec.submit(rng.randint(1, vocab, (p,)), n,
                               speculative=spec and i % 2 == 0, **kw))
    return [f.result(120.0) for f in futs]


class TestDonatedStep:
    @pytest.mark.parametrize("kind", STEP_KINDS)
    def test_step_donates_its_pool_same_rows(self, params, kind):
        """ACCEPTANCE: with the pool donated to `decode_step` (and
        `draft_step`), (a) every served row is identical to the row of
        a decoder whose step programs were compiled without donation;
        (b) after each step the pool held before it is deleted, leaf
        by leaf, and the pool bound after it is live; a stale
        reference raises instead of reading old rows; (c) the step
        stays ONE compiled program across slot turnover (two with a
        draft: step + verify; the draft's own stays one); (d) XLA
        could use every donated buffer: no warning."""
        import warnings
        draft = kind == "draft"
        gen, vocab = _step_pool(kind, params, 3)
        plain, _ = _step_pool(kind, params, 3)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with _serving(kind, gen) as dec:
                seen = _watch_pools(dec)
                got = _ragged_load(dec, vocab, draft)
                st = dec.stats()
                programs = dec._step_fn._cache_size()
                drafts = dec._draft_step_fn._cache_size() \
                    if draft else None
                gauge = telemetry.gauge(
                    "serve.decode.jit_cache_size").value
            with _without_donation(_serving(kind, plain)) as ref:
                kept = _watch_pools(ref)
                want = _ragged_load(ref, vocab, draft)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert st["finished"] == len(got) > gen.batch_size
        assert st["step_failures"] == 0
        assert seen and all(gone and live for gone, live, _ in seen)
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(next(iter(seen[-1][2].values())))
        # the twin really is undonated: its old pools stay readable
        assert kept and not any(gone for gone, _, _ in kept)
        assert programs == (2 if draft else 1)
        assert drafts in (None, 1)
        assert gauge == programs
        assert not [w for w in caught if "onat" in str(w.message)]

    @pytest.mark.parametrize("kind", KINDS)
    def test_describe_reports_the_aliased_bytes(self, merge_decs,
                                                kind):
        """`describe()` states how many of the pool's bytes on one
        device the compiled step writes in place: all of them."""
        dec = merge_decs[kind]
        aliased, held = dec._step_alias_bytes()
        if aliased is None:
            pytest.skip("this backend's memory_analysis reports no "
                        "alias_size_in_bytes")
        devices = 4 if kind == "sharded" else 1
        assert held * devices == sum(int(a.nbytes)
                                     for a in dec._aux.values())
        assert aliased == held
        text = dec.describe(hbm_budget=1e9)
        assert "writes %d of the pool's %d bytes" % (held, held) in text
        # and what a step writes of them: one contiguous row a token
        # for each length-indexed cache array and slot on a device
        rows = [a.sharding.shard_shape(a.shape) + (a.dtype.itemsize,)
                for n, a in dec._aux.items() if not n.endswith("_state")]
        said = "a token is %d contiguous bytes a row; %d row writes a " \
            "step" % (max([r[2] * r[3] for r in rows], default=0),
                      sum(r[0] for r in rows))
        assert (said in text) if rows else ("contiguous" not in text)
        # a report, not a step: the pool was lowered by shape only
        assert not any(a.is_deleted() for a in dec._aux.values())

    def test_unusable_donation_is_logged(self, params, caplog):
        """A step program that aliases less than the pool it is given
        is reported through the decoder's logger."""
        import logging
        with _without_donation(
                _gen(params, 2).serving_decoder()) as dec:
            with caplog.at_level(logging.WARNING):
                aliased, held = dec._step_alias_bytes()
            if aliased is None:
                pytest.skip("this backend's memory_analysis reports "
                            "no alias_size_in_bytes")
            assert aliased < held
            assert "could not use every donated" in caplog.text

    @pytest.mark.parametrize("kind", ["kv", "q8", "ssm"])
    def test_migration_round_trips_between_steps(self, params, kind):
        """`evacuate` (export_session on the loop thread) ->
        `submit(resume=)` (import_kv_rows) and a prefill handoff
        (import_kv_rows) stay bit-exact with donated steps before,
        between and after them: nothing holds a pool across a step."""
        from mxnet_tpu.serve import PrefillEngine
        single, vocab = _step_pool(kind, params, 1)
        donor, _ = _step_pool(kind, params, 2)
        heir, _ = _step_pool(kind, params, 3)
        rng = np.random.RandomState(7)
        p, q, r = (rng.randint(1, vocab, (n,)) for n in (5, 4, 6))
        opts = dict(temperature=0.8, top_k=8, seed=7)
        with donor.serving_decoder() as d1, \
                heir.serving_decoder() as d2:
            other = d2.submit(q, 16)          # d2 steps all the while
            fut = d1.submit(p, 10, **opts)
            deadline = time.time() + 120.0
            while len(fut.emitted) < 3:
                assert time.time() < deadline
                time.sleep(0.002)
            assert d1.evacuate() == 1
            with pytest.raises(SessionEvacuated) as ei:
                fut.result(10.0)
            resumed = d2.submit(p, 10, resume=ei.value.state, **opts)
            shipped = d2.submit(
                r, 7, handoff=PrefillEngine(single).prefill(r))
            got = [f.result(120.0) for f in (resumed, shipped, other)]
            st = d2.stats()
            # the donor's pool survived its own export: it serves on
            again = d1.submit(q, 5).result(120.0)
        np.testing.assert_array_equal(
            got[0], single.generate(p[None], 10, **opts)[0])
        np.testing.assert_array_equal(
            got[1], single.generate(r[None], 7)[0])
        np.testing.assert_array_equal(
            got[2], single.generate(q[None], 16)[0])
        np.testing.assert_array_equal(
            again, single.generate(q[None], 5)[0])
        assert (st["resumed"], st["imported"]) == (1, 2)
        assert st["prefills"] == 1            # `other` alone prefilled

    @pytest.mark.parametrize("spec", [False, True],
                             ids=["step", "spec-round"])
    def test_failed_step_fails_its_rows_and_rebuilds_the_pool(
            self, params, spec, caplog):
        """A step that raises has consumed its donated pool. The
        active sequences fail with the error, the pools are built
        anew, the loop lives and never steps on the deleted buffers:
        the next request is served, and served right."""
        single = _gen(params, 1)
        pool = _gen(params, 2)
        rng = np.random.RandomState(5)
        with (_spec_dec(pool) if spec else
              pool.serving_decoder()) as dec:
            real, armed = dec._step_fn, []

            def failing(args, aux, rng_):
                out = real(args, aux, rng_)
                if armed:
                    armed.pop()
                    raise RuntimeError("injected step fault")
                return out

            dec._step_fn = failing
            first = [dec.submit(rng.randint(1, V, (n,)), 12,
                                speculative=spec) for n in (4, 6)]
            deadline = time.time() + 120.0
            while min(len(f.emitted) for f in first) < 2:
                assert time.time() < deadline
                time.sleep(0.002)
            lost = dec._aux
            armed.append(1)
            for f in first:
                with pytest.raises(RuntimeError,
                                   match="injected step fault"):
                    f.result(120.0)
            prompt = rng.randint(1, V, (5,))
            got = dec.submit(prompt, 6,
                             speculative=spec).result(120.0)
            st = dec.stats()
            assert dec._thread.is_alive()
            assert all(a.is_deleted() for a in lost.values())
            assert not any(
                a.is_deleted() for a in list(dec._aux.values()) +
                list((dec._daux or {}).values()))
        np.testing.assert_array_equal(
            got, single.generate(prompt[None], 6)[0])
        assert st["step_failures"] == 1
        assert (st["finished"], st["active"]) == (1, 0)
        assert "decode step failed" in caplog.text


# -- one step rides ahead of what the host has read (PR 41) ----------------
AHEAD_KINDS = ["kv", "mamba2", "lfm2"]


def _ahead_pool(kind, params, batch):
    """A Generator whose pool holds the decode state of a serve cell:
    k/v rows (OPT); Mamba-2 window + scan state beside k/v rows
    (Granite, Nemotron); a short convolution's window and routed
    experts beside rotary QK-normed k/v rows (LFM2). Returns
    (generator, vocabulary)."""
    if kind == "lfm2":
        from cellbench.models import lfm2_moe as model
        from cellbench.reference import lfm2_moe as ref
        import test_lfm2_moe as toy
        return Generator(ref.make_params(toy.TOY, toy.SEED, "float32"),
                         toy.V, toy.T, batch_size=batch,
                         **model.generator_args(toy.TOY)), toy.V
    return _step_pool(kind, params, batch)


def _count_dispatches(dec):
    """Count the step program's dispatches (a call of the loop may
    only read)."""
    real, calls = dec._step_fn, []

    def counted(args, aux, rng):
        calls.append(1)
        return real(args, aux, rng)

    dec._step_fn = counted
    return calls


def _wait_emitted(fut, n):
    deadline = time.time() + 120.0
    while len(fut.emitted) < n:
        assert time.time() < deadline
        time.sleep(0.002)


class TestStepAhead:
    @pytest.mark.parametrize("kind", AHEAD_KINDS)
    @pytest.mark.parametrize("budgets", [(9, 9), (9, 4)],
                             ids=["kept-full", "ragged"])
    def test_greedy_rows_ahead_are_generate_s_rows(self, params, kind,
                                                   budgets):
        """ACCEPTANCE: greedy rows served with every step dispatched
        before the one before it is read are `generate`'s rows, token
        for token; over a kept-full pool every step but the first
        rides ahead; a row that ends by its budget is not dispatched
        again (as many dispatches and steps as the longest row needs:
        the count of a loop that reads before it dispatches), and no
        forward is spent on a row let go."""
        single, vocab = _ahead_pool(kind, params, 1)
        pool, _ = _ahead_pool(kind, params, 2)
        rng = np.random.RandomState(41)
        prompts = [rng.randint(1, vocab, (n,)) for n in (5, 7)]
        with pool.serving_decoder() as dec:
            calls = _count_dispatches(dec)
            futs = _submit_together(dec, _hold_admission(dec),
                                    list(zip(prompts, budgets)))
            got = [f.result(120.0) for f in futs]
            st = dec.stats()
            assert dec._inflight is None
        for p, n, g in zip(prompts, budgets, got):
            np.testing.assert_array_equal(
                g, single.generate(p[None], n)[0])
        assert st["steps"] == len(calls) == max(budgets) - 1
        assert st["steps_ahead"] == st["steps"] - 1
        assert st["idle_forwards"] == 0
        assert dec._next_fn._cache_size() == 1

    @pytest.mark.parametrize("kind", AHEAD_KINDS)
    def test_eos_with_a_step_ahead_costs_one_forward_and_frees_a_clean_slot(
            self, params, kind):
        """A row whose eos id comes up in step t was dispatched in
        t + 1 already: that result is skipped and counted, the row
        beside it goes on, and the slot's next tenant (its prefill
        merged behind the step in flight, recurrent state and all) is
        served as a solo `generate` would serve it."""
        single, vocab = _ahead_pool(kind, params, 1)
        pool, _ = _ahead_pool(kind, params, 2)
        rng = np.random.RandomState(3)
        first, beside, tenant = (rng.randint(1, vocab, (n,))
                                 for n in (5, 6, 4))
        with pool.serving_decoder() as dec:
            def ending(req, row):
                # on the decode thread: the token the row's fourth
                # step is about to pick becomes its eos id (the toy
                # models repeat themselves, so no id given at submit
                # comes up mid-stream in all of them)
                if req is a and len(req.emitted) == 4:
                    req.eos_id = int(np.argmax(row))

            a = None
            dec.on_logits = ending
            a, = _submit_together(dec, _hold_admission(dec),
                                  [(first, 12)])
            b = dec.submit(beside, 18)
            c = dec.submit(tenant, 8)      # waits for the freed slot
            got = [f.result(120.0) for f in (a, b, c)]
            st = dec.stats()
        np.testing.assert_array_equal(
            got[0], single.generate(first[None], 12)[0][:5 + 5])
        np.testing.assert_array_equal(
            got[1], single.generate(beside[None], 18)[0])
        np.testing.assert_array_equal(
            got[2], single.generate(tenant[None], 8)[0])
        assert st["idle_forwards"] == 1
        assert st["step_failures"] == 0

    def test_nothing_rides_ahead_of_a_sampled_row(self, params):
        """A sampled request beside a greedy one is `generate`'s stream
        for its seed, and while it lives every step is read before the
        next is formed (its pick is the host's): `steps_ahead` counts
        only the steps after it has gone."""
        single, pool = _gen(params, 1), _gen(params, 2)
        rng = np.random.RandomState(9)
        p, q = rng.randint(1, V, (5,)), rng.randint(1, V, (4,))
        opts = dict(temperature=0.8, top_k=5, seed=42)
        with pool.serving_decoder() as dec:
            hold = _hold_admission(dec)
            hold[0] = 2
            s = dec.submit(p, 6, **opts)
            g = dec.submit(q, 15)
            got = s.result(120.0), g.result(120.0)
            st = dec.stats()
        np.testing.assert_array_equal(
            got[0], single.generate(p[None], 6, **opts)[0])
        np.testing.assert_array_equal(
            got[1], single.generate(q[None], 15)[0])
        assert st["steps"] == 14
        # steps 1-5 carried the sampled row; step 6 was formed in the
        # call that read its last token: nothing was in flight
        assert st["steps_ahead"] == 14 - 6
        assert st["idle_forwards"] == 0

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_ties_pick_the_same_id_on_the_device_and_the_host(self,
                                                              dtype):
        """`next_tokens` and `DecodeFuture._pick` read the same
        float32 values and both take the first index among equals: a
        constant row, a largest value that repeats (in `dtype`'s
        rounding: neighbours that bfloat16 makes equal), the largest
        last, and a row whose token is the host's."""
        import jax.numpy as jnp
        from mxnet_tpu.serve.decode import DecodeFuture, _next_program
        rng = np.random.RandomState(0)
        logits = rng.randn(6, 1, 300).astype(np.float32)
        logits[0] = 0.25                          # a constant row
        logits[1, 0, [7, 100, 299]] = 9.0         # the largest, thrice
        logits[2, 0, 200:] = 5.0 + 1e-4 * np.arange(100)  # equal in bf16
        logits[3, 0, -1] = 11.0                   # the last id
        logits[4] = -np.inf                       # nothing is largest
        host_tok = np.full((6, 1), 123.0, np.float32)
        use_host = np.arange(6) == 5
        data, last = _next_program(None)(
            jnp.asarray(logits, dtype), host_tok, use_host)
        data, last = np.asarray(data), np.asarray(last)
        assert data.shape == (6, 1) and data.dtype == np.float32
        assert last.shape == (6, 300) and last.dtype == np.float32
        np.testing.assert_array_equal(
            last, np.asarray(jnp.asarray(logits, dtype)[:, -1],
                             np.float32))
        req = DecodeFuture(np.zeros(1, np.int64), 4, None, 0.0, None,
                           None, 0)
        picks = [req._pick(last[i]) for i in range(5)]
        assert data[:5, 0].tolist() == picks
        assert picks[:2] == [0, 7] and picks[3:] == [299, 0]
        assert picks[2] == (200 if dtype == "bfloat16" else 299)
        assert data[5, 0] == 123.0

    def test_a_dispatch_that_raises_loses_no_token_of_the_step_before(
            self, params, caplog):
        """The step in flight when a dispatch raises is still read and
        emitted (its logits were picked from before the dispatch); then
        the rows fail, the pool is built anew and serves on."""
        single, pool = _gen(params, 1), _gen(params, 2)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, V, (n,)) for n in (4, 6)]
        with pool.serving_decoder() as dec:
            real, calls = dec._step_fn, []

            def failing(args, aux, rng_):
                calls.append(1)
                if len(calls) == 4:
                    raise RuntimeError("injected dispatch fault")
                return real(args, aux, rng_)

            dec._step_fn = failing
            futs = _submit_together(dec, _hold_admission(dec),
                                    [(p, 12) for p in prompts])
            for f in futs:
                with pytest.raises(RuntimeError,
                                   match="injected dispatch fault"):
                    f.result(120.0)
            later = rng.randint(1, V, (5,))
            got = dec.submit(later, 6).result(120.0)
            st = dec.stats()
        for p, f in zip(prompts, futs):
            # the first token came with admission, three steps were
            # dispatched and all three were read
            np.testing.assert_array_equal(
                f.emitted, single.generate(p[None], 12)[0][len(p):][:4])
        np.testing.assert_array_equal(
            got, single.generate(later[None], 6)[0])
        assert st["step_failures"] == 1
        assert (st["finished"], st["active"]) == (1, 0)
        assert "decode step failed" in caplog.text

    @pytest.mark.parametrize("kind", AHEAD_KINDS)
    def test_evacuate_with_a_step_in_flight_resumes_token_for_token(
            self, params, kind):
        """`evacuate` finds a greedy row's next step on its way: it is
        read first, so the exported depth, pending token and cache rows
        agree and the heir continues `generate`'s row."""
        single, vocab = _ahead_pool(kind, params, 1)
        donor, _ = _ahead_pool(kind, params, 2)
        heir, _ = _ahead_pool(kind, params, 2)
        p = np.random.RandomState(7).randint(1, vocab, (5,))
        with donor.serving_decoder() as d1, \
                heir.serving_decoder() as d2:
            step, evacuate = d1._step, d1._do_evacuate
            futs, found = [], []

            def stepping():
                # the loop stands still once three tokens are out
                if not futs or len(futs[0].emitted) < 3:
                    step()

            def seen():
                found.append(d1._inflight is not None)
                evacuate()

            d1._step, d1._do_evacuate = stepping, seen
            futs.append(d1.submit(p, 16))
            _wait_emitted(futs[0], 3)
            assert d1.evacuate() == 1
            with pytest.raises(SessionEvacuated) as ei:
                futs[0].result(10.0)
            state = ei.value.state
            got = d2.submit(p, 16, resume=state).result(120.0)
            assert d1._inflight is None
        assert found == [True]
        # the step in flight was the fourth token's
        assert len(state["emitted"]) == 4
        assert state["kv_blob"]["pos"] == 5 + 4 - 1
        assert state["pending"] == state["emitted"][-1]
        np.testing.assert_array_equal(
            got, single.generate(p[None], 16)[0])

    def test_on_logits_sees_what_pick_is_handed(self, params):
        """`decoder.on_logits(req, row)`: None by default; set, it is
        called before each step's picks with the float32 rows that
        `_pick` is handed next (admission's first token is picked from
        the prefill and is not a step's)."""
        from mxnet_tpu.serve.decode import DecodeFuture
        pool = _gen(params, 2)
        rng = np.random.RandomState(2)
        prompts = [rng.randint(1, V, (n,)) for n in (4, 6, 5)]
        hooked, picked, pick = {}, {}, DecodeFuture._pick

        def keeping(self, row):
            picked.setdefault(id(self), []).append(np.array(row))
            return pick(self, row)

        with pool.serving_decoder() as dec:
            assert dec.on_logits is None
            dec.on_logits = lambda req, row: hooked.setdefault(
                id(req), []).append(np.array(row))
            DecodeFuture._pick = keeping
            try:
                futs = [dec.submit(p, n)
                        for p, n in zip(prompts, (6, 3, 5))]
                for f in futs:
                    f.result(120.0)
            finally:
                DecodeFuture._pick = pick
        for f, n in zip(futs, (6, 3, 5)):
            assert len(picked[id(f)]) == n
            assert len(hooked[id(f)]) == n - 1
            assert hooked[id(f)][0].dtype == np.float32
            np.testing.assert_array_equal(
                np.stack(hooked[id(f)]), np.stack(picked[id(f)][1:]))
