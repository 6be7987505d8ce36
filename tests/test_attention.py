"""Flash attention kernel + ring attention sequence parallelism tests.

The Pallas kernel runs in interpreter mode on the CPU test mesh (same
numerics as compiled TPU execution); ring attention runs as a real
8-device shard_map program on the forced CPU mesh (tests/conftest.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.attention import flash_attention, _attn_reference
from mxnet_tpu.parallel import ring_attention


def _qkv(B, T, D, seed=0, heads=None):
    rng = np.random.RandomState(seed)
    shape = (B, T, D) if heads is None else (B, heads, T, D)
    return tuple(jnp.asarray(rng.randn(*shape).astype("float32"))
                 for _ in range(3))


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(2, 64, 16)
        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32)
        ref = _attn_reference(q, k, v, 16 ** -0.5, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    def test_4d_and_cross_lengths(self):
        q, _, _ = _qkv(2, 32, 16, heads=4)
        _, k, v = _qkv(2, 48, 16, seed=1, heads=4)
        out = flash_attention(q, k, v)
        assert out.shape == (2, 4, 32, 16)
        ref = _attn_reference(q.reshape(8, 32, 16), k.reshape(8, 48, 16),
                              v.reshape(8, 48, 16), 16 ** -0.5, False)
        np.testing.assert_allclose(np.asarray(out).reshape(8, 32, 16),
                                   np.asarray(ref), rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ragged_lengths(self, causal):
        """T not a multiple of the block size: padded keys must not leak
        into the softmax."""
        q, _, _ = _qkv(2, 40, 16, seed=5)
        _, k, v = _qkv(2, 40, 16, seed=6)
        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32)
        ref = _attn_reference(q, k, v, 16 ** -0.5, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_match_reference(self):
        q, k, v = _qkv(1, 32, 8, seed=2)

        def loss_flash(q_, k_, v_):
            return (flash_attention(q_, k_, v_, causal=True) ** 2).sum()

        def loss_ref(q_, k_, v_):
            return (_attn_reference(q_, k_, v_, 8 ** -0.5, True) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_multiblock_ragged(self, causal):
        """Pallas backward over several blocks incl. a ragged tail: the
        dq pass and the dk/dv pass must both mask padded rows/cols."""
        q, k, v = _qkv(2, 72, 16, seed=7)

        def loss_flash(q_, k_, v_):
            return (flash_attention(q_, k_, v_, causal=causal,
                                    block_q=32, block_k=32) ** 2).sum()

        def loss_ref(q_, k_, v_):
            return (_attn_reference(q_, k_, v_, 16 ** -0.5,
                                    causal) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_cross_lengths(self, causal):
        """Backward with Tk != Tq (cross attention), incl. the causal
        row>=col masking against ragged q AND k tails."""
        q, _, _ = _qkv(1, 40, 16, seed=8)
        _, k, v = _qkv(1, 56, 16, seed=9)

        def loss_flash(q_, k_, v_):
            return (flash_attention(q_, k_, v_, causal=causal,
                                    block_q=32, block_k=32) ** 2).sum()

        def loss_ref(q_, k_, v_):
            return (_attn_reference(q_, k_, v_, 16 ** -0.5,
                                    causal) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_registered_op(self):
        q, k, v = _qkv(1, 16, 8, heads=2)
        out = nd._contrib_FlashAttention(nd.array(np.asarray(q)),
                                         nd.array(np.asarray(k)),
                                         nd.array(np.asarray(v)),
                                         causal=True)
        assert out.shape == (1, 2, 16, 8)

    @pytest.mark.parametrize("window", [1, 3, 16, 100])
    def test_window_attention_matches_dense(self, window):
        """Sliding-window flash (fwd + Pallas bwd) equals the dense
        banded-mask oracle, across window widths incl. degenerate
        (1 = self-only) and wider-than-T (= plain causal)."""
        q, k, v = _qkv(2, 40, 16, seed=17)

        def dense(q_, k_, v_):
            s = jnp.einsum("bqd,bkd->bqk", q_, k_) * 16 ** -0.5
            r = jnp.arange(40)[:, None]
            c = jnp.arange(40)[None, :]
            s = jnp.where((r >= c) & (r - c < window), s, -1e30)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(s, axis=-1), v_)

        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-5, atol=2e-6)

        gf = jax.grad(lambda a, b, c: (flash_attention(
            a, b, c, causal=True, window=window, block_q=16,
            block_k=16) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: (dense(a, b, c) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_window_requires_causal(self):
        q, k, v = _qkv(1, 16, 8)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_lse_variant_gradients(self, causal):
        """flash_attention_with_lse: gradient flow through BOTH outputs
        (the lse cotangent folds into the backward's delta term)."""
        from mxnet_tpu.ops.attention import flash_attention_with_lse
        q, k, v = _qkv(2, 72, 16, seed=13)

        def loss_flash(q_, k_, v_):
            o, lse = flash_attention_with_lse(q_, k_, v_,
                                              causal=causal,
                                              block_q=32, block_k=32)
            return (o ** 2).sum() + (jnp.sin(lse) ** 2).sum()

        def loss_ref(q_, k_, v_):
            s = jnp.einsum("bqd,bkd->bqk", q_, k_) * 16 ** -0.5
            if causal:
                m = jnp.arange(72)[:, None] >= jnp.arange(72)[None, :]
                s = jnp.where(m, s, -1e30)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            o = jnp.einsum("bqk,bkd->bqd",
                           jnp.exp(s - lse[..., None]), v_)
            return (o ** 2).sum() + (jnp.sin(lse) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.skipif(jax.device_count() < 2,
                        reason="needs a 2-device mesh")
    def test_replicated_shard_map_runs_kernel(self):
        """Fully-replicated q/k/v under a vma-checking shard_map: the
        kernel path itself runs (no varying operand, so no interpret
        fallback) and the out aval must declare vma=empty — omitting
        vma entirely raises under check_vma."""
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        q, k, v = _qkv(2, 32, 16, seed=12)
        fn = shard_map(
            lambda a, b, c: flash_attention(a, b, c, block_q=16,
                                            block_k=16),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=P())
        out = fn(q, k, v)
        ref = _attn_reference(q, k, v, 16 ** -0.5, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.skipif(jax.device_count() < 2,
                        reason="needs a 2-device mesh")
    def test_grad_mixed_variance_shard_map(self):
        """Backward under a vma-checking shard_map where q is replicated
        while k/v vary over the mesh axis: the cotangent dq must come
        back replicated (psum over the extra axis), not union-varying
        (regression: the Pallas backward stamps outputs with the union
        vma; _narrow_vma reduces it to each primal's variance)."""
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
        q, k, v = _qkv(2, 32, 16, seed=11)

        def body(q_, k_, v_):
            # each device attends its local half of the keys; q is
            # shared, so its cotangent must be psum'd back to replicated
            def loss(a, b, c):
                return (flash_attention(a, b, c, block_q=16,
                                        block_k=16)
                        .astype(jnp.float32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(P(), P(None, "sp"), P(None, "sp")),
                       out_specs=(P(), P(None, "sp"), P(None, "sp")))
        dq, dk, dv = fn(q, k, v)   # raises if dq variance is wrong
        assert dq.shape == q.shape
        assert dk.shape == k.shape


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8-device mesh")
class TestRingAttention:
    def _mesh(self):
        return Mesh(np.array(jax.devices()[:8]), ("sp",))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device(self, causal):
        mesh = self._mesh()
        q, k, v = _qkv(2, 8 * 16, 32, heads=2)
        shard = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        out = ring_attention(qs, ks, vs, mesh, "sp", causal=causal)
        B, H, T, D = q.shape
        ref = _attn_reference(q.reshape(B * H, T, D),
                              k.reshape(B * H, T, D),
                              v.reshape(B * H, T, D), D ** -0.5, causal)
        np.testing.assert_allclose(
            np.asarray(out).reshape(B * H, T, D), np.asarray(ref),
            rtol=2e-5, atol=2e-6)

    def test_output_stays_sequence_sharded(self):
        mesh = self._mesh()
        q, k, v = _qkv(1, 8 * 8, 16, heads=1)
        shard = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        out = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh, "sp"))(qs, ks, vs)
        assert out.sharding.spec == P(None, None, "sp", None)

    def test_collectives_in_hlo(self):
        mesh = self._mesh()
        q, k, v = _qkv(1, 8 * 8, 16, heads=1)
        shard = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        hlo = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh, "sp")).lower(qs, ks, vs).compile()\
            .as_text()
        assert "collective-permute" in hlo

    def test_gqa_through_flash_op_on_ring(self):
        """GQA kv broadcast happens BEFORE the ring branch in the flash
        op, so num_kv_heads < H trains sequence-parallel: the op with
        (B, 2, T, D) kv against (B, 4, T, D) q over the sp mesh must
        equal the dense GQA reference."""
        from mxnet_tpu.ops.attention import _flash_attention_op
        from mxnet_tpu.ops import _mesh_ctx
        mesh = self._mesh()
        B, H, Hkv, T, D = 1, 4, 2, 8 * 8, 16
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
        qs = jax.device_put(q, NamedSharding(
            mesh, P(None, None, "sp", None)))
        ks, vs = (jax.device_put(x, NamedSharding(
            mesh, P(None, None, "sp", None))) for x in (k, v))
        with _mesh_ctx.use_mesh(mesh):
            out = _flash_attention_op(qs, ks, vs, causal=True,
                                      seq_axis="sp")
        kr = jnp.repeat(k, H // Hkv, axis=1)
        vr = jnp.repeat(v, H // Hkv, axis=1)
        ref = _attn_reference(q.reshape(B * H, T, D),
                              kr.reshape(B * H, T, D),
                              vr.reshape(B * H, T, D), D ** -0.5,
                              True)
        np.testing.assert_allclose(
            np.asarray(out).reshape(B * H, T, D), np.asarray(ref),
            rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_dense(self, causal):
        """Long-context TRAINING path: autodiff through the ring
        (scan + ppermute) must equal dense-attention gradients."""
        mesh = self._mesh()
        B, H, T, D = 2, 2, 8 * 8, 16
        q, k, v = _qkv(B, T, D, heads=H)
        shard = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))

        grads = jax.jit(jax.grad(
            lambda a, b, c: ring_attention(
                a, b, c, mesh, "sp", causal=causal).sum(),
            argnums=(0, 1, 2)))(qs, ks, vs)

        def dense(a, b, c):
            r = _attn_reference(a.reshape(B * H, T, D),
                                b.reshape(B * H, T, D),
                                c.reshape(B * H, T, D),
                                D ** -0.5, causal)
            return r.sum()

        want = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(grads, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg="d%s" % name)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8-device mesh")
class TestSeqAxisOp:
    """seq_axis on _contrib_FlashAttention: the symbol-level
    sequence-parallel path (ring attention under an ambient mesh)."""

    def test_symbol_graph_rings_on_mesh(self):
        import mxnet_tpu as mx
        from mxnet_tpu.executor import _graph_eval_fn

        mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
        B, H, T, D = 2, 2, 8 * 8, 16
        q, k, v = (mx.sym.Variable(n) for n in "qkv")
        out = mx.sym.contrib.FlashAttention(q, k, v, causal=True,
                                            seq_axis="sp")
        qv, kv, vv = _qkv(B, T, D, heads=H)
        shard = NamedSharding(mesh, P(None, None, "sp", None))

        fn = _graph_eval_fn(out, mesh=mesh)
        args = {"q": jax.device_put(qv, shard),
                "k": jax.device_put(kv, shard),
                "v": jax.device_put(vv, shard)}
        jitted = jax.jit(lambda a: fn(a, {}, jax.random.PRNGKey(0),
                                      False)[0][0])
        got = jitted(args)
        # ring == dense reference
        ref = _attn_reference(qv.reshape(B * H, T, D),
                              kv.reshape(B * H, T, D),
                              vv.reshape(B * H, T, D), D ** -0.5, True)
        np.testing.assert_allclose(
            np.asarray(got).reshape(B * H, T, D), np.asarray(ref),
            rtol=2e-5, atol=2e-6)
        # and it really went around the ring
        hlo = jitted.lower(args).compile().as_text()
        assert "collective-permute" in hlo

    def test_no_mesh_falls_back_to_flash(self):
        import mxnet_tpu as mx
        from mxnet_tpu.executor import _graph_eval_fn

        q, k, v = (mx.sym.Variable(n) for n in "qkv")
        out = mx.sym.contrib.FlashAttention(q, k, v, causal=True,
                                            seq_axis="sp")
        qv, kv, vv = _qkv(1, 32, 16, heads=2)
        fn = _graph_eval_fn(out)   # no mesh
        got = fn({"q": qv, "k": kv, "v": vv}, {},
                 jax.random.PRNGKey(0), False)[0][0]
        ref = _attn_reference(qv.reshape(2, 32, 16),
                              kv.reshape(2, 32, 16),
                              vv.reshape(2, 32, 16), 16 ** -0.5, True)
        np.testing.assert_allclose(np.asarray(got).reshape(2, 32, 16),
                                   np.asarray(ref), rtol=2e-5,
                                   atol=2e-6)

    @pytest.mark.slow
    def test_transformer_trains_sequence_parallel(self):
        """End to end: transformer LM symbol with seq_axis, TrainStep
        over an {'sp': 8} mesh — compiles, runs, loss sane, ring
        collectives present. Slow tier (~14 s on the 1-core tier-1
        host); the seq-axis op keeps fast coverage in
        test_symbol_graph_rings_on_mesh/test_no_mesh_falls_back."""
        import mxnet_tpu as mx
        from mxnet_tpu.initializer import Xavier
        from mxnet_tpu.models import transformer
        from mxnet_tpu.parallel import make_mesh, make_train_step

        mesh = make_mesh({"sp": 8})
        vocab, T, B = 64, 8 * 8, 2
        sym_ = transformer.get_symbol(vocab, T, num_layers=1,
                                      num_heads=2, dim=32,
                                      seq_axis="sp")
        step = make_train_step(sym_, optimizer="adam", mesh=mesh)
        state = step.init_state(Xavier(), {"data": (B, T),
                                           "softmax_label": (B, T)})
        rng_np = np.random.RandomState(0)
        toks = rng_np.randint(0, vocab, (B, T)).astype(np.float32)
        labels = np.roll(toks, -1, axis=1)
        labels[:, -1] = -1
        batch = step.place_batch({"data": toks,
                                  "softmax_label": labels})
        hlo = step.lower(state, batch, 1e-3,
                         jax.random.PRNGKey(0)).compile().as_text()
        assert "collective-permute" in hlo
        state, outs = step(state, batch, 1e-3, jax.random.PRNGKey(0))
        probs = np.asarray(outs[0])
        assert np.isfinite(probs).all()
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)


@pytest.mark.slow
@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8-device mesh")
def test_full_composition_dp_sp_zero1_bf16():
    """The whole v5e-pod recipe in one step: 2-D data x sp mesh, ring
    attention per layer, ZeRO-1 optimizer sharding over 'data', bf16
    compute with f32 masters and protected token ids — compiles,
    rings, shards, and converges. Slow tier (~24 s on the 1-core
    tier-1 host); every ingredient keeps fast coverage (ring attention
    in TestRingAttention, seq-axis in TestSeqAxisOp, ZeRO-1/bf16 in
    test_gspmd.py)."""
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.models import transformer
    from mxnet_tpu.parallel import make_mesh, make_train_step

    mesh = make_mesh({"data": 2, "sp": 4})
    vocab, T, B = 512, 64, 4
    sym = transformer.get_symbol(vocab, T, num_layers=1, num_heads=2,
                                 dim=32, seq_axis="sp")
    step = make_train_step(sym, optimizer="adam", mesh=mesh,
                           optimizer_sharding="zero1",
                           compute_dtype="bfloat16")
    assert step._id_inputs == {"data"}   # ids survive the bf16 cast
    state = step.init_state(Xavier(), {"data": (B, T),
                                       "softmax_label": (B, T)})
    from tests._lm_utils import arith_corpus, lm_nll
    toks, labels = arith_corpus(B, T, vocab)
    batch = step.place_batch({"data": toks, "softmax_label": labels})
    rng = jax.random.PRNGKey(0)
    hlo = step.lower(state, batch, 1e-3, rng).compile().as_text()
    assert "collective-permute" in hlo          # the ring is real

    state, outs = step(state, batch, 2e-3, rng)
    first = lm_nll(outs, labels, vocab)
    for _ in range(60):
        state, outs = step(state, batch, 2e-3, rng)
    assert lm_nll(outs, labels, vocab) < first / 2
    # optimizer state stayed ZeRO-1 sharded through the run
    m = state[1]["layer0_qkv_weight"][0]
    assert "data" in str(m.sharding.spec), m.sharding


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8-device mesh")
class TestWindowedRingAttention:
    """Banded causal ring: compute and ring hops scale with the window.
    Every (window, shard) regime checked against the dense banded
    oracle — partial band blocks, full blocks, window under one shard,
    window past the whole context."""

    def _mesh(self):
        return Mesh(np.array(jax.devices()[:8]), ("sp",))

    # window=1000 (> T: the degenerate all-visible band) costs ~9 s of
    # compile on the tier-1 host — slow tier; 24 already exercises a
    # window spanning multiple ring hops
    @pytest.mark.parametrize("window",
                             [1, 5, 8, 13, 24,
                              pytest.param(1000,
                                           marks=pytest.mark.slow)])
    def test_matches_dense_banded(self, window):
        mesh = self._mesh()
        B, H, T, D = 1, 2, 8 * 8, 16
        q, k, v = _qkv(B, T, D, heads=H)
        shard = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        out = ring_attention(qs, ks, vs, mesh, "sp", causal=True,
                             window=window)
        from mxnet_tpu.ops.attention import _dense_with_lse
        ref = _dense_with_lse(
            jnp.asarray(q).reshape(B * H, T, D),
            jnp.asarray(k).reshape(B * H, T, D),
            jnp.asarray(v).reshape(B * H, T, D),
            D ** -0.5, True, window)[0]
        np.testing.assert_allclose(
            np.asarray(out).reshape(B * H, T, D), np.asarray(ref),
            rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("window", [5, 13])
    def test_gradients_match_dense_banded(self, window):
        mesh = self._mesh()
        B, H, T, D = 1, 1, 8 * 8, 16
        q, k, v = _qkv(B, T, D, heads=H)
        shard = NamedSharding(mesh, P(None, None, "sp", None))
        qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
        grads = jax.jit(jax.grad(
            lambda a, b, c: ring_attention(
                a, b, c, mesh, "sp", causal=True,
                window=window).sum(), argnums=(0, 1, 2)))(qs, ks, vs)
        from mxnet_tpu.ops.attention import _dense_with_lse

        def dense(a, b, c):
            return _dense_with_lse(
                a.reshape(B * H, T, D), b.reshape(B * H, T, D),
                c.reshape(B * H, T, D), D ** -0.5, True,
                window)[0].sum()

        want = jax.grad(dense, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for g, w, name in zip(grads, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg="d%s" % name)

    def test_window_requires_causal(self):
        mesh = self._mesh()
        q, k, v = _qkv(1, 8 * 8, 16, heads=1)
        with pytest.raises(ValueError, match="causal"):
            ring_attention(q, k, v, mesh, "sp", causal=False, window=4)
