"""KV-cache decode: _contrib_CachedAttention + get_decode_symbol +
Generator.

The load-bearing check is teacher-forcing consistency: feeding a
sequence through the incremental decode path (prefill + one token at a
time) must reproduce the training symbol's per-position softmax.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.executor import _graph_eval_fn
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.ops.attention import cached_attention, _attn_reference
from mxnet_tpu.parallel import make_train_step

V, L, H, DIM, T, B = 50, 2, 2, 32, 12, 2


def _trained_params(seed=0):
    sym = transformer.get_symbol(V, T, num_layers=L, num_heads=H,
                                 dim=DIM)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(seed)      # distinct seeds -> genuinely distinct
    state = step.init_state(Xavier(),
                            {"data": (B, T),
                             "softmax_label": (B, T)})
    return sym, state[0]


class TestCachedAttentionOp:
    def test_matches_reference_incremental(self):
        """Appending one token at a time over a causal sequence equals
        dense causal attention."""
        rng = np.random.RandomState(0)
        Tmax, hd = 8, 16
        q = jnp.asarray(rng.randn(1, 2, Tmax, hd), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, Tmax, hd), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, Tmax, hd), jnp.float32)
        kc = jnp.zeros((1, Tmax, 2 * hd), jnp.float32)
        vc = jnp.zeros_like(kc)
        outs = []
        for t in range(Tmax):
            o, kc, vc = cached_attention(
                q[:, :, t:t + 1], k[:, :, t:t + 1], v[:, :, t:t + 1],
                kc, vc, jnp.full((1,), t))
            outs.append(o)
        inc = jnp.concatenate(outs, axis=2).reshape(2, Tmax, hd)
        ref = _attn_reference(q.reshape(2, Tmax, hd),
                              k.reshape(2, Tmax, hd),
                              v.reshape(2, Tmax, hd),
                              hd ** -0.5, True)
        np.testing.assert_allclose(np.asarray(inc), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    def test_prefill_then_steps(self):
        """A multi-token prefill chunk equals the same tokens appended
        one by one."""
        rng = np.random.RandomState(1)
        Tmax, hd, P = 8, 8, 5
        mk = lambda: jnp.asarray(rng.randn(1, 1, Tmax, hd), jnp.float32)
        q, k, v = mk(), mk(), mk()
        kc = jnp.zeros((1, Tmax, hd), jnp.float32)
        vc = jnp.zeros_like(kc)
        o_chunk, kc1, vc1 = cached_attention(
            q[:, :, :P], k[:, :, :P], v[:, :, :P], kc, vc,
            jnp.zeros((1,)))
        kc2, vc2 = kc, vc
        outs = []
        for t in range(P):
            o, kc2, vc2 = cached_attention(
                q[:, :, t:t + 1], k[:, :, t:t + 1], v[:, :, t:t + 1],
                kc2, vc2, jnp.full((1,), t))
            outs.append(o)
        np.testing.assert_allclose(
            np.asarray(o_chunk), np.asarray(jnp.concatenate(outs, 2)),
            rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(kc1), np.asarray(kc2),
                                   rtol=1e-6, atol=1e-7)

    def test_registered_with_cache_aux(self):
        s = transformer.get_decode_symbol(V, T, num_layers=L,
                                          num_heads=H, dim=DIM)
        aux = s.list_auxiliary_states()
        assert sorted(aux) == sorted(
            ["layer%d_attn_%s" % (i, n)
             for i in range(L) for n in ("k_cache", "v_cache")])
        args = s.list_arguments()
        assert "cache_pos" in args and "positions" in args


class TestTeacherForcingConsistency:
    def test_decode_matches_training_symbol(self):
        """Incremental logits == training-symbol softmax at every
        position (prefill of 4, then token-by-token)."""
        train_sym, params = _trained_params()
        rng = np.random.RandomState(3)
        toks = rng.randint(0, V, (B, T)).astype(np.float32)

        # full forward through the training graph -> per-position probs
        eval_fn = _graph_eval_fn(train_sym)
        raw = {k: getattr(v, "_data", v) for k, v in params.items()}
        labels = np.zeros((B * T,), np.float32)
        outs, _ = eval_fn({**raw, "data": jnp.asarray(toks),
                           "softmax_label": jnp.asarray(labels)},
                          {}, jax.random.PRNGKey(0), False)
        probs_full = np.asarray(outs[0]).reshape(B, T, V)

        # incremental: prefill 4 tokens, then one at a time
        dec = transformer.get_decode_symbol(V, T, num_layers=L,
                                            num_heads=H, dim=DIM)
        dfn = _graph_eval_fn(dec)
        aux = {n: jnp.zeros((B, T, DIM), jnp.float32)
               for n in dec.list_auxiliary_states()}
        P = 4
        logits_inc = []

        def fwd(chunk, pos):
            nonlocal aux
            tn = chunk.shape[1]
            outs, aux = dfn(
                {**raw, "data": jnp.asarray(chunk),
                 "positions": jnp.arange(pos, pos + tn,
                                         dtype=jnp.float32),
                 "cache_pos": jnp.full((1,), pos, jnp.float32)},
                aux, jax.random.PRNGKey(0), False)
            return np.asarray(outs[0])

        logits_inc.append(fwd(toks[:, :P], 0))
        for t in range(P, T):
            logits_inc.append(fwd(toks[:, t:t + 1], t))
        logits_inc = np.concatenate(logits_inc, axis=1)
        probs_inc = np.asarray(
            jax.nn.softmax(jnp.asarray(logits_inc), axis=-1))
        np.testing.assert_allclose(probs_inc, probs_full,
                                   rtol=1e-4, atol=1e-5)


class TestRoPE:
    def test_rope_op_oracle(self):
        """Rotation matches the hand-rolled complex-multiply form and
        preserves norms."""
        from mxnet_tpu.ops.attention import rope
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(1, 2, 5, 8), jnp.float32)
        pos = jnp.arange(5)
        out = np.asarray(rope(x, pos))
        half = 4
        freqs = 10000.0 ** (-np.arange(half) / half)
        ang = np.arange(5)[:, None] * freqs[None, :]
        x1, x2 = np.asarray(x)[..., :half], np.asarray(x)[..., half:]
        want = np.concatenate(
            [x1 * np.cos(ang) - x2 * np.sin(ang),
             x1 * np.sin(ang) + x2 * np.cos(ang)], axis=-1)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.linalg.norm(out, axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)

    def test_rope_relative_shift_invariance(self):
        """RoPE attention scores depend only on relative positions:
        shifting all positions by a constant leaves q·k unchanged."""
        from mxnet_tpu.ops.attention import rope
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(1, 1, 6, 16), jnp.float32)
        k = jnp.asarray(rng.randn(1, 1, 6, 16), jnp.float32)

        def scores(shift):
            pos = jnp.arange(6) + shift
            qr, kr = rope(q, pos), rope(k, pos)
            return np.asarray(jnp.einsum("bhqd,bhkd->bhqk", qr, kr))

        np.testing.assert_allclose(scores(0), scores(37),
                                   rtol=1e-4, atol=1e-4)

    def test_rope_teacher_forcing_consistency(self):
        """RoPE decode (rotate-then-cache) reproduces the RoPE training
        forward per position."""
        sym = transformer.get_symbol(V, T, num_layers=L, num_heads=H,
                                     dim=DIM, pos_encoding="rope")
        step = make_train_step(sym, optimizer="sgd")
        state = step.init_state(Xavier(), {"data": (B, T),
                                           "softmax_label": (B, T)})
        params = state[0]
        assert "pos_embed_weight" not in params   # no position table
        raw = {k: getattr(v, "_data", v) for k, v in params.items()}
        rng = np.random.RandomState(6)
        toks = rng.randint(0, V, (B, T)).astype(np.float32)

        eval_fn = _graph_eval_fn(sym)
        outs, _ = eval_fn({**raw, "data": jnp.asarray(toks),
                           "softmax_label": jnp.zeros((B * T,),
                                                      jnp.float32)},
                          {}, jax.random.PRNGKey(0), False)
        probs_full = np.asarray(outs[0]).reshape(B, T, V)

        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        pos_encoding="rope")
        aux = gen._fresh_aux()
        logits = []
        for t in range(T):
            lg, aux = gen._forward(aux, toks[:, t:t + 1], t)
            logits.append(np.asarray(lg))
        probs_inc = np.asarray(jax.nn.softmax(jnp.asarray(
            np.concatenate(logits, axis=1)), axis=-1))
        np.testing.assert_allclose(probs_inc, probs_full,
                                   rtol=1e-4, atol=1e-5)

    def test_rope_validation(self):
        with pytest.raises(ValueError, match="even head_dim"):
            transformer.get_symbol(V, T, num_heads=2, dim=6,
                                   pos_encoding="rope")
        with pytest.raises(ValueError, match="seq_len"):
            transformer.get_stage_symbol(pos_encoding="rope")
        # a rope stage with seq_len builds fine
        s = transformer.get_stage_symbol(pos_encoding="rope",
                                         seq_len=8, num_heads=2,
                                         dim=16)
        assert "data" in s.list_arguments()

    def test_rope_generates(self):
        sym = transformer.get_symbol(V, T, num_layers=L, num_heads=H,
                                     dim=DIM, pos_encoding="rope")
        step = make_train_step(sym, optimizer="sgd")
        state = step.init_state(Xavier(), {"data": (B, T),
                                           "softmax_label": (B, T)})
        gen = Generator(state[0], V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        pos_encoding="rope")
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        host = gen.generate(prompt, max_new_tokens=5)
        dev = gen.generate_on_device(prompt, max_new_tokens=5)
        assert (host == dev).all()
        with pytest.raises(ValueError, match="pos_encoding"):
            transformer.get_symbol(V, T, pos_encoding="alibi")


class TestWindowedDecode:
    def test_window_teacher_forcing_consistency(self):
        """Sliding-window decode (banded cache masking) reproduces the
        windowed training forward per position."""
        W = 4
        sym = transformer.get_symbol(V, T, num_layers=L, num_heads=H,
                                     dim=DIM, attention_window=W)
        step = make_train_step(sym, optimizer="sgd")
        state = step.init_state(Xavier(), {"data": (B, T),
                                           "softmax_label": (B, T)})
        raw = {k: getattr(v, "_data", v) for k, v in state[0].items()}
        rng = np.random.RandomState(8)
        toks = rng.randint(0, V, (B, T)).astype(np.float32)

        eval_fn = _graph_eval_fn(sym)
        outs, _ = eval_fn({**raw, "data": jnp.asarray(toks),
                           "softmax_label": jnp.zeros((B * T,),
                                                      jnp.float32)},
                          {}, jax.random.PRNGKey(0), False)
        probs_full = np.asarray(outs[0]).reshape(B, T, V)

        gen = Generator(state[0], V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        attention_window=W)
        aux = gen._fresh_aux()
        logits = []
        for t in range(T):
            lg, aux = gen._forward(aux, toks[:, t:t + 1], t)
            logits.append(np.asarray(lg))
        probs_inc = np.asarray(jax.nn.softmax(jnp.asarray(
            np.concatenate(logits, axis=1)), axis=-1))
        np.testing.assert_allclose(probs_inc, probs_full,
                                   rtol=1e-4, atol=1e-5)
        # the window genuinely bites: a plain-causal model differs
        sym_c = transformer.get_symbol(V, T, num_layers=L,
                                       num_heads=H, dim=DIM)
        outs_c, _ = _graph_eval_fn(sym_c)(
            {**raw, "data": jnp.asarray(toks),
             "softmax_label": jnp.zeros((B * T,), jnp.float32)},
            {}, jax.random.PRNGKey(0), False)
        assert np.abs(np.asarray(outs_c[0]).reshape(B, T, V)
                      - probs_full).max() > 1e-3


class TestRollingCache:
    def _rope_windowed_params(self, W):
        sym = transformer.get_symbol(V, T, num_layers=L, num_heads=H,
                                     dim=DIM, pos_encoding="rope",
                                     attention_window=W)
        step = make_train_step(sym, optimizer="sgd")
        state = step.init_state(Xavier(), {"data": (B, T),
                                           "softmax_label": (B, T)})
        return state[0]

    def test_rolling_matches_plain_windowed_decode(self):
        """Within the plain cache's reach, a circular cache of capacity
        W+P-1 must produce identical greedy output."""
        W = 4
        params = self._rope_windowed_params(W)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        P = prompt.shape[1]
        plain = Generator(params, V, max_len=T, num_layers=L,
                          num_heads=H, dim=DIM, batch_size=B,
                          pos_encoding="rope", attention_window=W)
        rolling = Generator(params, V, max_len=W + P - 1, num_layers=L,
                            num_heads=H, dim=DIM, batch_size=B,
                            pos_encoding="rope", attention_window=W,
                            rolling_cache=True)
        a = plain.generate(prompt, max_new_tokens=8)
        b = rolling.generate(prompt, max_new_tokens=8)
        assert (a == b).all(), (a, b)

    def test_rolling_generates_past_capacity(self):
        """The point of the circular buffer: generation length far
        beyond the cache capacity (impossible for the plain cache),
        still matching a large-capacity plain run token for token."""
        W = 4
        params = self._rope_windowed_params(W)
        prompt = np.array([[1, 2], [3, 4]])
        P, N = prompt.shape[1], 30          # 32 total >> capacity 5
        rolling = Generator(params, V, max_len=W + P - 1, num_layers=L,
                            num_heads=H, dim=DIM, batch_size=B,
                            pos_encoding="rope", attention_window=W,
                            rolling_cache=True)
        big = Generator(params, V, max_len=P + N, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        pos_encoding="rope", attention_window=W)
        a = rolling.generate(prompt, max_new_tokens=N)
        b = big.generate(prompt, max_new_tokens=N)
        assert a.shape == (B, P + N)
        assert (a == b).all()

    def test_rolling_validation(self):
        W = 4
        params = self._rope_windowed_params(W)
        gen = Generator(params, V, max_len=W + 1, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        pos_encoding="rope", attention_window=W,
                        rolling_cache=True)
        with pytest.raises(ValueError, match="rolling cache capacity"):
            gen.generate(np.zeros((B, 4)), max_new_tokens=2)
        with pytest.raises(ValueError, match="rolling_cache needs"):
            transformer.get_decode_symbol(V, 8, rolling_cache=True)
        with pytest.raises(ValueError, match="speculative"):
            gen.generate_speculative(gen, np.zeros((B, 2)), 2)


class TestQuantizedDecode:
    def test_quantized_fc_op_matches_dequant(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        w = rng.randn(6, 8).astype(np.float32)
        scale = np.abs(w).max(axis=1) / 127.0
        wq = np.rint(w / scale[:, None]).astype(np.int8)
        b = rng.randn(6).astype(np.float32)
        out = nd._contrib_QuantizedFullyConnected(
            nd.array(np.asarray(x)), nd.array(wq), nd.array(scale),
            nd.array(b), num_hidden=6)
        ref = x @ (wq.astype(np.float32) * scale[:, None]).T + b
        np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_int8_decode_close_to_float(self):
        """Weight-only int8 greedy decode: per-position softmax stays
        close to the float path, weights actually land int8."""
        _, params = _trained_params()
        gen_f = Generator(params, V, max_len=T, num_layers=L,
                          num_heads=H, dim=DIM, batch_size=B)
        gen_q = Generator(params, V, max_len=T, num_layers=L,
                          num_heads=H, dim=DIM, batch_size=B,
                          quantize="int8")
        assert gen_q._params["layer0_qkv_weight"].dtype == jnp.int8
        assert gen_q._params["lm_head_weight"].dtype == jnp.int8
        assert gen_q._params["tok_embed_weight"].dtype == jnp.int8
        assert "layer0_qkv_scale" in gen_q._params
        assert "tok_embed_scale" in gen_q._params

        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        rng_toks = np.random.RandomState(4).randint(
            0, V, (B, 8)).astype(np.float32)
        aux_f = gen_f._fresh_aux()
        aux_q = gen_q._fresh_aux()
        lf, _ = gen_f._forward(aux_f, rng_toks, 0)
        lq, _ = gen_q._forward(aux_q, rng_toks, 0)
        pf = np.asarray(jax.nn.softmax(lf.astype(jnp.float32), -1))
        pq = np.asarray(jax.nn.softmax(lq.astype(jnp.float32), -1))
        assert np.abs(pf - pq).max() < 0.05
        # end-to-end still generates
        out = gen_q.generate(prompt, max_new_tokens=5)
        assert out.shape == (B, 8)

    def test_cache_dtype_ignores_int8_params(self):
        """Param-dict ordering must not leak int8 into the KV caches
        (regression: cache dtype was taken from the dict's first
        entry)."""
        _, params = _trained_params()
        reordered = {"layer0_qkv_weight": params["layer0_qkv_weight"]}
        reordered.update(params)
        gen = Generator(reordered, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        quantize="int8")
        assert jnp.issubdtype(gen._cache_dtype, jnp.floating)

    def test_quantize_rejects_unknown(self):
        _, params = _trained_params()
        with pytest.raises(ValueError, match="quantize"):
            Generator(params, V, max_len=T, num_layers=L, num_heads=H,
                      dim=DIM, batch_size=B, quantize="fp4")


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs a 4-device mesh")
class TestMeshDecode:
    def test_tensor_parallel_greedy_matches_single(self):
        """Generator over a data x model mesh: sharded params + head-
        sharded caches produce the same greedy tokens as one device."""
        from jax.sharding import Mesh
        _, params = _trained_params()
        single = Generator(params, V, max_len=T, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        tp = Generator(params, V, max_len=T, num_layers=L,
                       num_heads=H, dim=DIM, batch_size=B, mesh=mesh)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        a = single.generate(prompt, max_new_tokens=6)
        b = tp.generate(prompt, max_new_tokens=6)
        assert (a == b).all()
        # params actually went down sharded (column-parallel qkv)
        qkv = tp._params["layer0_qkv_weight"]
        assert qkv.sharding.spec[0] == "model"

    def test_on_device_loop_under_mesh(self):
        """The whole-generation lax.scan program also runs with TP
        sharded params + caches and matches the host loop."""
        from jax.sharding import Mesh
        _, params = _trained_params()
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        tp = Generator(params, V, max_len=T, num_layers=L,
                       num_heads=H, dim=DIM, batch_size=B, mesh=mesh)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        host = tp.generate(prompt, max_new_tokens=5)
        dev = tp.generate_on_device(prompt, max_new_tokens=5)
        assert (host == dev).all()

    def test_int8_composes_with_mesh(self):
        """quantize='int8' + TP mesh: int8 weights shard like float
        ones and decode still runs."""
        from jax.sharding import Mesh
        _, params = _trained_params()
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B, mesh=mesh,
                        quantize="int8")
        w = gen._params["layer0_qkv_weight"]
        assert w.dtype == jnp.int8 and w.sharding.spec[0] == "model"
        out = gen.generate(np.array([[1, 2], [3, 4]]),
                           max_new_tokens=3)
        assert out.shape == (B, 5)


class TestMoEDecode:
    def test_moe_teacher_forcing_consistency(self):
        """A Switch-MoE-FFN checkpoint decodes identically to its
        training forward (expert gating runs per appended token)."""
        E = 4
        # capacity raised to E on the training side too: dropping is a
        # training-throughput knob, and a dropped token's FFN output is
        # legitimately zero there while decode always serves it
        sym = transformer.get_symbol(V, T, num_layers=L, num_heads=H,
                                     dim=DIM, num_experts=E,
                                     moe_capacity_factor=E)
        step = make_train_step(sym, optimizer="sgd")
        state = step.init_state(Xavier(),
                                {"data": (B, T),
                                 "softmax_label": (B, T)})
        params = state[0]
        raw = {k: getattr(v, "_data", v) for k, v in params.items()}
        rng = np.random.RandomState(5)
        toks = rng.randint(0, V, (B, T)).astype(np.float32)

        eval_fn = _graph_eval_fn(sym)
        outs, _ = eval_fn({**raw, "data": jnp.asarray(toks),
                           "softmax_label": jnp.zeros((B * T,),
                                                      jnp.float32)},
                          {}, jax.random.PRNGKey(0), False)
        probs_full = np.asarray(outs[0]).reshape(B, T, V)

        dec = transformer.get_decode_symbol(V, T, num_layers=L,
                                            num_heads=H, dim=DIM,
                                            num_experts=E)
        dfn = _graph_eval_fn(dec)
        aux = {n: jnp.zeros((B, T, DIM), jnp.float32)
               for n in dec.list_auxiliary_states()}
        logits = []
        for t in range(T):
            outs, aux = dfn(
                {**raw, "data": jnp.asarray(toks[:, t:t + 1]),
                 "positions": jnp.full((1,), t, jnp.float32),
                 "cache_pos": jnp.full((1,), t, jnp.float32)},
                aux, jax.random.PRNGKey(0), False)
            logits.append(np.asarray(outs[0]))
        probs_inc = np.asarray(jax.nn.softmax(
            jnp.asarray(np.concatenate(logits, axis=1)), axis=-1))
        np.testing.assert_allclose(probs_inc, probs_full,
                                   rtol=1e-4, atol=1e-5)


class TestGenerator:
    def test_greedy_deterministic_and_shapes(self):
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        out1 = gen.generate(prompt, max_new_tokens=5)
        out2 = gen.generate(prompt, max_new_tokens=5)
        assert out1.shape == (B, 8)
        assert (out1 == out2).all()
        assert (out1[:, :3] == prompt).all()
        assert (out1 >= 0).all() and (out1 < V).all()

    def test_sampling_seeded(self):
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        a = gen.generate(prompt, max_new_tokens=5, temperature=1.0,
                         top_k=5, seed=7)
        b = gen.generate(prompt, max_new_tokens=5, temperature=1.0,
                         top_k=5, seed=7)
        c = gen.generate(prompt, max_new_tokens=5, temperature=1.0,
                         top_k=5, seed=8)
        assert (a == b).all()
        assert a.shape == c.shape

    def test_capacity_and_param_errors(self):
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        with pytest.raises(ValueError, match="exceeds the cache"):
            gen.generate(np.zeros((B, T - 1)), max_new_tokens=2)
        with pytest.raises(ValueError, match="missing parameters"):
            Generator({"tok_embed_weight": np.zeros((V, DIM))}, V,
                      max_len=T, num_layers=L, num_heads=H, dim=DIM)

    def test_on_device_matches_python_loop(self):
        """The lax.scan whole-generation program must emit exactly the
        greedy tokens the per-step python loop emits."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        host = gen.generate(prompt, max_new_tokens=6)
        dev = gen.generate_on_device(prompt, max_new_tokens=6)
        assert (host == dev).all()
        # sampled path: deterministic per seed, right shape
        s1 = gen.generate_on_device(prompt, 4, temperature=1.0,
                                    top_k=5, seed=9)
        s2 = gen.generate_on_device(prompt, 4, temperature=1.0,
                                    top_k=5, seed=9)
        assert (s1 == s2).all() and s1.shape == (B, 7)

    def test_beam_w1_equals_greedy(self):
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        greedy = gen.generate(prompt, max_new_tokens=6)
        beam1 = gen.beam_search(prompt, max_new_tokens=6, beam_size=1)
        assert (greedy == beam1).all()

    def test_beam_finds_no_worse_sequence(self):
        """Beam-4's total log-likelihood must be >= greedy's (greedy is
        in beam's search space)."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        N = 6

        def seq_logprob(full):
            # score continuation under the training symbol (teacher
            # forcing over the produced sequence)
            sym, _ = _trained_params()
            eval_fn = _graph_eval_fn(sym)
            raw = {k: getattr(v, "_data", v) for k, v in
                   params.items()}
            toks = np.zeros((B, T), np.float32)
            toks[:, :full.shape[1]] = full
            outs, _ = eval_fn(
                {**raw, "data": jnp.asarray(toks),
                 "softmax_label": jnp.zeros((B * T,), jnp.float32)},
                {}, jax.random.PRNGKey(0), False)
            probs = np.asarray(outs[0]).reshape(B, T, V)
            lp = np.zeros(B)
            for b in range(B):
                for t in range(2, 2 + N):   # positions preceding gen
                    nxt = int(full[b, t + 1])
                    lp[b] += np.log(max(probs[b, t, nxt], 1e-9))
            return lp

        greedy = gen.generate(prompt, max_new_tokens=N)
        beam = gen.beam_search(prompt, max_new_tokens=N, beam_size=4)
        lg, lb = seq_logprob(greedy), seq_logprob(beam)
        assert (lb >= lg - 1e-4).all(), (lb, lg)

    def test_beam_eos_freezes(self):
        """With beam_size=1 and eos = the greedy first token, row 0
        freezes at step 1 — every later token MUST be eos (padding by
        the freeze rule), guaranteed non-vacuous."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2], [3, 4]])
        greedy = gen.generate(prompt, max_new_tokens=1)
        eos = int(greedy[0, 2])   # row 0's argmax first token
        out = gen.beam_search(prompt, max_new_tokens=6, beam_size=1,
                              eos_id=eos)
        row = out[0, 2:]
        assert row[0] == eos
        assert (row == eos).all()   # frozen: eos continues for free

    def test_gqa_teacher_forcing_consistency(self):
        """Grouped-query attention (num_kv_heads=2, H=4): incremental
        decode must reproduce the training symbol's per-position
        softmax, and the caches must hold only the kv heads."""
        sym_t = transformer.get_symbol(V, T, num_layers=L, num_heads=4,
                                       dim=DIM, num_kv_heads=2)
        step = make_train_step(sym_t, optimizer="sgd")
        mx.random.seed(3)
        params = step.init_state(Xavier(), {"data": (B, T),
                                            "softmax_label": (B, T)})[0]
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=4, dim=DIM, batch_size=B,
                        num_kv_heads=2)
        hd = DIM // 4
        assert gen._cache_shape == (B, T, 2 * hd)

        rng = np.random.RandomState(0)
        toks = rng.randint(0, V, (B, T))
        eval_fn = _graph_eval_fn(sym_t)
        raw = {k: getattr(v, "_data", v) for k, v in params.items()}
        outs, _ = eval_fn(
            {**raw, "data": jnp.asarray(toks, jnp.float32),
             "softmax_label": jnp.zeros((B * T,), jnp.float32)},
            {}, jax.random.PRNGKey(0), False)
        want = np.asarray(outs[0]).reshape(B, T, V)

        aux = gen._fresh_aux()
        got = []
        for t in range(T):
            logits, aux = gen._forward(aux, toks[:, t:t + 1], t)
            p = np.asarray(jax.nn.softmax(
                logits[:, -1].astype(jnp.float32), axis=-1))
            got.append(p)
        got = np.stack(got, axis=1)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_gqa_generates_and_validates(self):
        """qkv projection shrinks to (H + 2*Hkv)*hd; generation runs;
        invalid head grouping raises."""
        sym_t = transformer.get_symbol(V, T, num_layers=L, num_heads=4,
                                       dim=DIM, num_kv_heads=1)
        step = make_train_step(sym_t, optimizer="sgd")
        mx.random.seed(4)
        params = step.init_state(Xavier(), {"data": (B, T),
                                            "softmax_label": (B, T)})[0]
        hd = DIM // 4
        w = getattr(params["layer0_qkv_weight"], "_data",
                    params["layer0_qkv_weight"])
        assert w.shape[0] == DIM + 2 * hd      # H*hd + 2*(1*hd)
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=4, dim=DIM, batch_size=B,
                        num_kv_heads=1)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        host = gen.generate(prompt, max_new_tokens=5)
        dev = gen.generate_on_device(prompt, max_new_tokens=5)
        assert host.shape == (B, 8) and (host == dev).all()

        with pytest.raises(ValueError, match="multiple of"):
            transformer.get_symbol(V, T, num_heads=4, num_kv_heads=3)

    def test_speculative_on_device_matches_host_and_greedy(self):
        """The compiled speculative loop (draft scan + verify + accept
        inside lax.while_loop) must emit EXACTLY the target's greedy
        continuation — same contract as the host speculative path."""
        cap = 3 + 8 + 4                            # P + n + lookahead

        def params_with_table(seed):
            sym_t = transformer.get_symbol(V, T, num_layers=L,
                                           num_heads=H, dim=DIM,
                                           max_len=cap)
            step = make_train_step(sym_t, optimizer="sgd")
            mx.random.seed(seed)
            return step.init_state(Xavier(), {
                "data": (B, T), "softmax_label": (B, T)})[0]

        target = Generator(params_with_table(0), V, max_len=cap,
                           num_layers=L, num_heads=H, dim=DIM,
                           batch_size=B)
        draft = Generator(params_with_table(1), V, max_len=cap,
                          num_layers=L, num_heads=H, dim=DIM,
                          batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        greedy = target.generate(prompt, max_new_tokens=8)
        host = target.generate_speculative(draft, prompt, 8,
                                           lookahead=4)
        dev = target.generate_speculative_on_device(draft, prompt, 8,
                                                    lookahead=4)
        assert (host == greedy).all()
        assert (dev == greedy).all(), (dev, greedy)
        # self-drafting: always fully accepts, still exact
        dev2 = target.generate_speculative_on_device(target, prompt,
                                                     8, lookahead=4)
        assert (dev2 == greedy).all()

    def test_speculative_on_device_validates_capacity(self):
        _, t_params = _trained_params(seed=0)
        gen = Generator(t_params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        with pytest.raises(ValueError, match="headroom"):
            gen.generate_speculative_on_device(
                gen, prompt, T - 3, lookahead=4)

    def test_gqa_composes_with_window_and_rolling(self):
        """GQA + RoPE + sliding window + rolling circular caches — the
        full modern-serving composition; rolling caches keep only
        (B, Hkv, C, hd)."""
        sym_t = transformer.get_symbol(V, 24, num_layers=L, num_heads=4,
                                       dim=DIM, num_kv_heads=2,
                                       pos_encoding="rope",
                                       attention_window=8)
        step = make_train_step(sym_t, optimizer="sgd")
        mx.random.seed(5)
        params = step.init_state(Xavier(), {"data": (B, 24),
                                            "softmax_label": (B, 24)})[0]
        gen = Generator(params, V, max_len=12, num_layers=L,
                        num_heads=4, dim=DIM, num_kv_heads=2,
                        batch_size=B, pos_encoding="rope",
                        attention_window=8, rolling_cache=True)
        assert gen._cache_shape == (B, 12, 2 * (DIM // 4))
        out = gen.generate(np.array([[1, 2, 3], [4, 5, 6]]),
                           max_new_tokens=20)   # past plain capacity
        assert out.shape == (B, 23)

    def test_beam_on_device_matches_host(self):
        """beam_search_on_device (one compiled scan, in-scan cache
        reorder) must reproduce the host-loop beam exactly — tokens
        and W=1/W=4, with and without length penalty."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        for w in (1, 4):
            host = gen.beam_search(prompt, max_new_tokens=6,
                                   beam_size=w)
            dev = gen.beam_search_on_device(prompt, max_new_tokens=6,
                                            beam_size=w)
            assert (host == dev).all(), (w, host, dev)
        host = gen.beam_search(prompt, 6, beam_size=4,
                               length_penalty=1.0)
        dev = gen.beam_search_on_device(prompt, 6, beam_size=4,
                                        length_penalty=1.0)
        assert (host == dev).all()

    def test_beam_on_device_eos_freeze(self):
        """eos freezing inside the scan: frozen beams pad with eos at
        no score cost, like the host loop (modulo the host's early
        break — same tokens, fixed length)."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2], [3, 4]])
        greedy = gen.generate(prompt, max_new_tokens=1)
        eos = int(greedy[0, 2])
        out = gen.beam_search_on_device(prompt, max_new_tokens=6,
                                        beam_size=1, eos_id=eos)
        assert out.shape == (B, 8)
        row = out[0, 2:]
        assert row[0] == eos and (row == eos).all()

    def test_top_p_sampling(self):
        """Nucleus sampling: seeded determinism; top_p=tiny degenerates
        to greedy (only the argmax survives the nucleus)."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        a = gen.generate(prompt, 5, temperature=1.0, top_p=0.9, seed=3)
        b = gen.generate(prompt, 5, temperature=1.0, top_p=0.9, seed=3)
        assert (a == b).all()
        greedy = gen.generate(prompt, 5)
        tiny = gen.generate(prompt, 5, temperature=1.0, top_p=1e-9,
                            seed=11)
        assert (tiny == greedy).all()
        dev = gen.generate_on_device(prompt, 5, temperature=1.0,
                                     top_p=1e-9, seed=11)
        assert (dev == greedy).all()

    def test_log_likelihood(self):
        """Scoring matches a hand-rolled teacher-forcing sum, and the
        greedy continuation scores >= a perturbed one."""
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        toks = np.random.RandomState(9).randint(0, V, (B, 8))
        ll = gen.log_likelihood(toks)
        logits, _ = gen._forward(gen._fresh_aux(), toks, 0)
        lp = np.asarray(jax.nn.log_softmax(
            logits.astype(jnp.float32), -1))
        want = np.zeros(B)
        for b_ in range(B):
            for t in range(7):
                want[b_] += lp[b_, t, toks[b_, t + 1]]
        np.testing.assert_allclose(ll, want, rtol=1e-5, atol=1e-5)

        greedy = gen.generate(toks[:, :3], max_new_tokens=5)
        other = greedy.copy()
        other[:, -1] = (other[:, -1] + 1) % V
        assert (gen.log_likelihood(greedy)
                >= gen.log_likelihood(other) - 1e-6).all()

    def test_bf16_decode(self):
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B,
                        dtype="bfloat16")
        assert gen._cache_dtype == jnp.bfloat16
        out = gen.generate(np.array([[1, 2], [3, 4]]),
                           max_new_tokens=4)
        assert out.shape == (B, 6)

    def test_checkpoint_roundtrip(self, tmp_path):
        """save_checkpoint -> load_checkpoint -> Generator: the
        deployment path the docs promise, end to end."""
        sym, params = _trained_params()
        mod = mx.mod.Module(sym, context=mx.cpu(),
                            label_names=("softmax_label",))
        mod.bind(data_shapes=[("data", (B, T))],
                 label_shapes=[("softmax_label", (B, T))])
        mod.set_params({k: mx.nd.array(np.asarray(
            getattr(v, "_data", v))) for k, v in params.items()}, {},
            allow_missing=False)
        prefix = str(tmp_path / "lm")
        mod.save_checkpoint(prefix, 1)

        _, arg, _ = mx.model.load_checkpoint(prefix, 1)
        gen = Generator(arg, V, max_len=T, num_layers=L, num_heads=H,
                        dim=DIM, batch_size=B)
        direct = Generator(params, V, max_len=T, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        assert (gen.generate(prompt, 5)
                == direct.generate(prompt, 5)).all()

    # lookahead=5 re-specializes every draft/verify shape for ~7 s of
    # CPU compile — slow tier; 1 and 3 already span the degenerate and
    # multi-token acceptance paths
    @pytest.mark.parametrize("lookahead",
                             [1, 3,
                              pytest.param(5,
                                           marks=pytest.mark.slow)])
    def test_speculative_equals_greedy(self, lookahead):
        """Speculative output must be EXACTLY the target's greedy
        continuation, for any draft: a weak draft (different seed),
        a perfect draft (the target itself), across lookaheads."""
        _, params = _trained_params()
        target = Generator(params, V, max_len=T, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        _, params2 = _trained_params(seed=1)
        weak = Generator(params2, V, max_len=T, num_layers=L,
                         num_heads=H, dim=DIM, batch_size=B)
        perfect = Generator(params, V, max_len=T, num_layers=L,
                            num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        want = target.generate(prompt, max_new_tokens=9)
        for draft in (weak, perfect):
            got = target.generate_speculative(
                draft, prompt, max_new_tokens=9, lookahead=lookahead)
            assert (got == want).all(), (lookahead, got, want)

    def test_speculative_perfect_draft_efficiency(self):
        """A perfect draft (the target itself) must accept every
        proposal: ceil(N / (lookahead+1)) verification forwards. This
        is the test that catches draft-cache staleness — a corrupted
        draft cache degrades acceptance, not output."""
        _, params = _trained_params()
        target = Generator(params, V, max_len=T, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        perfect = Generator(params, V, max_len=T, num_layers=L,
                            num_heads=H, dim=DIM, batch_size=B)
        calls = {"target": 0}
        orig = target._forward

        def counting(aux, tokens, pos):
            calls["target"] += 1
            return orig(aux, tokens, pos)

        target._forward = counting
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        target.generate_speculative(perfect, prompt,
                                    max_new_tokens=8, lookahead=3)
        # 1 prefill + ceil(8/4)=2 verification rounds
        assert calls["target"] == 3, calls

    def test_speculative_validation(self):
        _, params = _trained_params()
        target = Generator(params, V, max_len=T, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        small = Generator(params, V, max_len=4, num_layers=L,
                          num_heads=H, dim=DIM, batch_size=B)
        with pytest.raises(ValueError, match="draft max_len"):
            target.generate_speculative(small, np.zeros((B, 2)), 6)

    # each on-device case compiles its own (temp, top_k, top_p)
    # specialization of the fused loop — keep the fast tier to the
    # two distinct verification regimes (plain temp, temp+top_k) and
    # ride top_p on the slow tier
    @pytest.mark.parametrize("kw", [
        {"temperature": 0.8, "seed": 0},
        {"temperature": 1.2, "top_k": 5, "seed": 7},
        pytest.param({"temperature": 0.9, "top_p": 0.9, "seed": 3},
                     marks=pytest.mark.slow),
    ])
    def test_speculative_sampled_equals_generate(self, kw):
        """SAMPLED speculative decoding is byte-identical to plain
        generate(seed) — host and compiled paths alike. The contract
        is shared-noise verification (docs/serving.md §speculative):
        emission j is always _pick_token(target_logits_j, sub_j) on
        the request key's (j+1)-th split, the draft merely proposes
        with the same noise — so speculation changes the SCHEDULE,
        never the distribution, and a resumed/failed-over replica
        replays the identical stream."""
        cap = 3 + 8 + 4                        # P + n + lookahead
        sym_t = transformer.get_symbol(V, T, num_layers=L,
                                       num_heads=H, dim=DIM,
                                       max_len=cap)
        step = make_train_step(sym_t, optimizer="sgd")
        mx.random.seed(0)
        params = step.init_state(Xavier(), {
            "data": (B, T), "softmax_label": (B, T)})[0]
        target = Generator(params, V, max_len=cap, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        draft = target.truncated_draft(num_layers=1)
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        want = target.generate(prompt, max_new_tokens=8, **kw)
        host = target.generate_speculative(draft, prompt, 8,
                                           lookahead=3, **kw)
        dev = target.generate_speculative_on_device(draft, prompt, 8,
                                                    lookahead=3, **kw)
        assert (host == want).all(), (kw, host, want)
        assert (dev == want).all(), (kw, dev, want)

    def test_truncated_draft_shares_params_and_validates(self):
        """truncated_draft: the self-drafting constructor — the
        SHALLOW prefix of the target (same embeddings, first k
        layers, same head) as an independent Generator over the same
        param dict. Depth bounds and unsupported variants fail
        loudly."""
        _, params = _trained_params()
        target = Generator(params, V, max_len=T, num_layers=L,
                           num_heads=H, dim=DIM, batch_size=B)
        draft = target.truncated_draft(num_layers=1)
        assert draft.num_layers == 1
        assert draft.batch_size == target.batch_size
        prompt = np.array([[1, 2, 3], [4, 5, 6]])
        want = target.generate(prompt, max_new_tokens=6)
        got = target.generate_speculative(draft, prompt, 6,
                                          lookahead=2)
        assert (got == want).all(), (got, want)
        for bad in (0, L + 1):
            with pytest.raises(ValueError, match="num_layers"):
                target.truncated_draft(num_layers=bad)

    def test_eos_early_stop(self):
        _, params = _trained_params()
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.array([[1, 2], [3, 4]])
        full = gen.generate(prompt, max_new_tokens=6)
        eos = int(full[0, 2])     # force the first greedy pick as eos
        out = gen.generate(prompt, max_new_tokens=6, eos_id=eos)
        assert out.shape[1] <= full.shape[1]
        assert (out[0, 2:] == eos).any()


class TestQuantizedKVCache:
    """quantize_kv=True: int8 k/v caches with per-token scales — the
    serving-bandwidth feature for long-prompt decode. Checks: the op
    is a faithful (to int8) attention, the Generator path stays close
    to the float cache, and a TRAINED model's greedy continuation is
    token-identical (confident logits swallow the quantization
    noise)."""

    def test_q8_op_matches_float_cache(self):
        from mxnet_tpu.ops.attention import cached_attention_q8

        rng = np.random.RandomState(0)
        Tmax, hd = 8, 16
        q = jnp.asarray(rng.randn(1, 2, Tmax, hd), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, Tmax, hd), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, Tmax, hd), jnp.float32)
        kc = jnp.zeros((1, Tmax, 2 * hd), jnp.int8)
        vc = jnp.zeros_like(kc)
        ks = jnp.zeros((1, Tmax, 2), jnp.float32)
        vs = jnp.zeros_like(ks)
        kcf = jnp.zeros((1, Tmax, 2 * hd), jnp.float32)
        vcf = jnp.zeros_like(kcf)
        for t in range(Tmax):
            o8, kc, vc, ks, vs = cached_attention_q8(
                q[:, :, t:t + 1], k[:, :, t:t + 1], v[:, :, t:t + 1],
                kc, vc, ks, vs, jnp.full((1,), t))
            of, kcf, vcf = cached_attention(
                q[:, :, t:t + 1], k[:, :, t:t + 1], v[:, :, t:t + 1],
                kcf, vcf, jnp.full((1,), t))
            # int8 absmax/127 keeps ~2 decimal digits; the softmax
            # weighting keeps the output within ~1%
            np.testing.assert_allclose(np.asarray(o8), np.asarray(of),
                                       rtol=0.05, atol=0.02)
        # the caches really are int8 + per-token scales
        assert kc.dtype == jnp.int8 and vs.dtype == jnp.float32
        assert float(jnp.abs(ks[0, :Tmax]).min()) > 0

    def test_q8_generator_close_and_aux_dtypes(self):
        _, params = _trained_params()
        gen8 = Generator(params, V, max_len=T, num_layers=L,
                         num_heads=H, dim=DIM, batch_size=B,
                         quantize_kv=True)
        genf = Generator(params, V, max_len=T, num_layers=L,
                         num_heads=H, dim=DIM, batch_size=B)
        aux = gen8._fresh_aux()
        kinds = {n: a.dtype for n, a in aux.items()}
        assert any(n.endswith("_k_cache") and d == jnp.int8
                   for n, d in kinds.items())
        assert any(n.endswith("_k_scale") and d == jnp.float32
                   for n, d in kinds.items())
        toks = np.arange(B * 6).reshape(B, 6) % V
        l8, _ = gen8._forward(gen8._fresh_aux(), toks, 0)
        lf, _ = genf._forward(genf._fresh_aux(), toks, 0)
        # logits track the float path to quantization tolerance
        np.testing.assert_allclose(np.asarray(l8), np.asarray(lf),
                                   rtol=0.1, atol=0.05)

    @pytest.mark.slow
    def test_q8_trained_greedy_token_identical(self):
        """Train the arithmetic-stride LM (confident logits), then the
        int8-cache greedy continuation must equal the float-cache one
        token for token — the serving-accuracy contract. Slow tier
        (~13 s on the 1-core tier-1 host: it trains a model first);
        the q8 cache keeps fast exactness coverage on untrained params
        above and through the ragged pool in test_serve_decode.py."""
        from tests._lm_utils import arith_corpus

        vocab, Tt, Bt = 16, 12, 8
        sym = transformer.get_symbol(vocab, Tt, num_layers=2,
                                     num_heads=2, dim=32)
        step = make_train_step(sym, optimizer="adam",
                               optimizer_params={"rescale_grad":
                                                 1.0 / Bt})
        state = step.init_state(Xavier(), {"data": (Bt, Tt),
                                           "softmax_label": (Bt, Tt)})
        toks, labels = arith_corpus(Bt, Tt, vocab)
        batch = step.place_batch({"data": toks,
                                  "softmax_label": labels})
        rng = jax.random.PRNGKey(0)
        for _ in range(300):
            state, _outs = step(state, batch, 5e-3, rng)
        params = state[0]

        kw = dict(num_layers=2, num_heads=2, dim=32, batch_size=Bt,
                  max_len=Tt)
        genf = Generator(params, vocab, **kw)
        gen8 = Generator(params, vocab, quantize_kv=True, **kw)
        prompt = toks[:, :4].astype(np.int64)
        outf = genf.generate(prompt, 6)
        out8 = gen8.generate(prompt, 6)
        np.testing.assert_array_equal(outf, out8)
        # and the model really learned the progression (the check has
        # teeth only against a confident model)
        strides = (toks[:, 1] - toks[:, 0]) % vocab
        want = (prompt[:, -1][:, None]
                + strides[:, None] * np.arange(1, 7)) % vocab
        np.testing.assert_array_equal(outf[:, 4:], want)

    def test_q8_composes_with_gqa_and_window(self):
        """The int8 cache must compose with grouped-query heads and
        sliding-window attention (the modes share the cache layout):
        logits track the float path within quantization tolerance."""
        sym = transformer.get_symbol(V, T, num_layers=L, num_heads=4,
                                     dim=DIM, num_kv_heads=2,
                                     attention_window=6)
        step = make_train_step(sym, optimizer="sgd")
        mx.random.seed(7)
        state = step.init_state(Xavier(), {"data": (B, T),
                                           "softmax_label": (B, T)})
        kw = dict(num_layers=L, num_heads=4, dim=DIM, num_kv_heads=2,
                  attention_window=6, batch_size=B, max_len=T)
        gen8 = Generator(state[0], V, quantize_kv=True, **kw)
        genf = Generator(state[0], V, **kw)
        toks = np.arange(B * 8).reshape(B, 8) % V
        l8, _ = gen8._forward(gen8._fresh_aux(), toks, 0)
        lf, _ = genf._forward(genf._fresh_aux(), toks, 0)
        np.testing.assert_allclose(np.asarray(l8), np.asarray(lf),
                                   rtol=0.1, atol=0.05)
        # and generation runs end to end under the combo
        out = gen8.generate(toks[:, :4].astype(np.int64), 4)
        assert out.shape == (B, 8)


class TestEosOnDevice:
    def test_eos_while_loop_matches_host(self):
        """generate_on_device(eos_id=...) — the serving early-stop as a
        while_loop in one program — must emit exactly the host
        generate(eos_id=...) tokens, with finished rows padded by eos
        to the static length (the host truncates instead)."""
        _, params = _trained_params(seed=2)
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.arange(B * 3).reshape(B, 3) % V
        n = 6
        free = gen.generate(prompt, n)           # no-eos greedy probe
        # pick the token some row emits mid-stream so the exit binds
        eos = int(free[0, 4])
        host = gen.generate(prompt, n, eos_id=eos)
        dev = gen.generate_on_device(prompt, n, eos_id=eos)
        assert dev.shape == (B, 3 + n)           # static shape
        # host may truncate once every row finished; token-for-token
        # equality on the emitted region, eos padding after
        np.testing.assert_array_equal(dev[:, :host.shape[1]], host)
        assert np.all(dev[:, host.shape[1]:] == eos)
        # and without eos_id the scan path is unchanged
        np.testing.assert_array_equal(
            gen.generate_on_device(prompt, n), free)

    def test_eos_while_loop_matches_host_sampled(self):
        """The SAMPLED path through the eos while_loop (per-iteration
        key splits + _pick_token inside the carried loop) must track
        host generate() token for token — the scan path's sampled
        parity test doesn't cover this trace."""
        _, params = _trained_params(seed=3)
        gen = Generator(params, V, max_len=T, num_layers=L,
                        num_heads=H, dim=DIM, batch_size=B)
        prompt = np.arange(B * 3).reshape(B, 3) % V
        n = 6
        probe = gen.generate(prompt, n, temperature=1.0, top_k=5,
                             seed=11)
        eos = int(probe[0, 4])
        host = gen.generate(prompt, n, temperature=1.0, top_k=5,
                            eos_id=eos, seed=11)
        dev = gen.generate_on_device(prompt, n, temperature=1.0,
                                     top_k=5, eos_id=eos, seed=11)
        assert dev.shape == (B, 3 + n)
        np.testing.assert_array_equal(dev[:, :host.shape[1]], host)
        assert np.all(dev[:, host.shape[1]:] == eos)
