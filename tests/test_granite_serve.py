"""A Granite 4.0-H stack (Mamba-2 mixers beside position-free GQA
attention, RMSNorm, gated-SiLU MLP, the four multipliers, a tied head)
through Generator -> ContinuousDecoder, against the benchmark's plain
float32 reference on LOGITS, at toy widths with seeded weights; the
three kinds of decode state through cache_merge, fresh_aux,
export -> import and evacuate -> resume; and the promise that the
OPT-style symbol is what it was."""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cellbench.models import granite as model
from cellbench.models.opt import served_logits
from cellbench.reference import granite as ref
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator
from mxnet_tpu.models import transformer
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.serve import PrefillEngine, SessionEvacuated
from mxnet_tpu.serve.decode import _merge_program

pytestmark = pytest.mark.serve

V, T, SEED = 97, 48, 11
TOY = {"family": "granite", "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "shared_intermediate_size": 48,
       "vocab_size": V, "num_hidden_layers": 4,
       "layer_types": ["mamba", "attention", "mamba", "mamba"],
       "max_position_embeddings": 64, "mamba_n_heads": 8,
       "mamba_d_head": 8, "mamba_d_state": 16, "mamba_d_conv": 4,
       "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 8,
       "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
       "residual_multiplier": 0.22, "attention_multiplier": 0.25,
       "logits_scaling": 8, "initializer_range": 0.2,
       "compute_dtype": "float32"}
KINDS = {"kv_rows", "scan_state", "conv_window"}


@pytest.fixture(scope="module")
def params():
    return ref.make_params(TOY, SEED, "float32")


def _gen(params, batch_size, **over):
    args = dict(model.generator_args(TOY), **over)
    return Generator(params, V, T, batch_size=batch_size, **args)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, n, dtype=np.int64) for n in lengths]


def _reference(rows):
    return list(ref.served_logits(TOY, SEED, rows, "float32"))


def _forward_logits(gen, tokens, prompt):
    """Prefill `prompt` tokens then one step a token, every row alike:
    the logits (B, n, V) that predict tokens[:, prompt:]."""
    aux = gen._fresh_aux()
    logits, aux = gen._forward(aux, tokens[:, :prompt], 0)
    outs = [np.asarray(logits[:, -1], np.float32)]
    for i in range(prompt, tokens.shape[1] - 1):
        logits, aux = gen._forward(aux, tokens[:, i:i + 1], i)
        outs.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(outs, 1)


def _error(got, want):
    """Largest error over the reference's spread across the
    vocabulary."""
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got) - want).max() / want.std())


# float32 program against float32 reference: rounding of sums of a
# few dozen terms, through four layers
TOL = 2e-4


def test_logits_through_the_slot_pool_match_the_reference(params):
    """Five requests of four prompt lengths through a pool of two
    slots (so slots turn over and the pool's states are reused): the
    logits every served token was picked from are the reference's full
    forward over prompt + served tokens."""
    prompts = _prompts([5, 13, 8, 5, 21])
    with _gen(params, 2).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 9)
        assert dec.stats()["prefills"] >= 3
        assert telemetry.gauge("serve.decode.jit_cache_size").value == 1
    want = _reference([(len(p), r) for p, r in zip(prompts, rows)])
    for got, exp in zip(logits, want):
        assert got.shape == exp.shape == (9, V)
        assert _error(got, exp) < TOL


@pytest.fixture(scope="module")
def teacher(params):
    """Seeded rows of 20 tokens and the reference's logits at the 8
    positions after a 12-token prompt."""
    tokens = np.stack(_prompts([20, 20], seed=3))
    where = np.tile(np.arange(11, 19), (2, 1))
    want = np.asarray(ref.logits_at(TOY, SEED, tokens, where, "float32"))
    return tokens, want


def test_prefill_then_steps_match_the_reference(params, teacher):
    tokens, want = teacher
    assert _error(_forward_logits(_gen(params, 2), tokens, 12),
                  want) < TOL


def _with_param(params, **new):
    out = dict(params)
    out.update(new)
    return out


@pytest.mark.parametrize("left_out", [
    "tie_embeddings", "pos_encoding", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "attention_scale",
    "d_skip", "gate"])
def test_each_piece_left_out_fails_the_comparison(params, teacher,
                                                  monkeypatch, left_out):
    """The comparison the sound program passes at TOL is failed, by a
    wide margin, by a program that leaves one piece of the equations
    out."""
    tokens, want = teacher
    rng = np.random.default_rng(5)
    over, weights = {}, params
    if left_out == "tie_embeddings":
        # an untied head is a second table: the checkpoint has none, so
        # a loader that forgot the tie brings its own
        over = {"tie_embeddings": False}
        weights = _with_param(params, lm_head_weight=jnp.asarray(
            0.2 * rng.standard_normal((V, 32)), jnp.float32))
    elif left_out == "pos_encoding":
        over = {"pos_encoding": "learned"}
        weights = _with_param(params, pos_embed_weight=jnp.asarray(
            0.2 * rng.standard_normal((T, 32)), jnp.float32))
    elif left_out == "d_skip":
        weights = {k: jnp.zeros_like(v) if k.endswith("mamba_d_skip")
                   else v for k, v in params.items()}
    elif left_out == "gate":
        op = get_op("_contrib_GatedRMSNorm")
        plain = get_op("RMSNorm").fn
        monkeypatch.setattr(
            op, "fn", lambda data, gate, gamma, **kw:
            plain(data, gamma, **kw))
    elif left_out == "attention_scale":
        over = {"attention_scale": None}
    else:
        over = {left_out: 1.0}
    got = _forward_logits(_gen(weights, 2, **over), tokens, 12)
    assert _error(got, want) > 50 * TOL


def test_the_tied_head_is_one_array(params):
    gen = _gen(params, 1)
    args = gen._sym.list_arguments()
    assert "tok_embed_weight" in args
    assert not [a for a in args if a.startswith("lm_head")]
    assert "positions" not in args and "pos_embed_weight" not in args
    assert not [a for a in args if a.endswith(("_bias", "_beta"))
                and "mamba" not in a]


def test_state_bytes_by_kind_in_describe_stats_and_gauges(params):
    gen = _gen(params, 3)
    by = gen.state_bytes_by_kind()
    assert set(by) == KINDS
    assert by == {"scan_state": 3 * 8 * 8 * 16 * 4,
                  "conv_window": 3 * 3 * (64 + 32) * 4,
                  "kv_rows": 2 * 2 * T * 8 * 4}
    assert sum(by.values()) == gen.state_bytes_per_slot()
    with gen.serving_decoder() as dec:
        assert dec.stats()["bytes_per_slot"] == by
        text = dec.describe(hbm_budget=1e9)
        for word in ("KV rows", "mamba2 scan state 8x8x16",
                     "mamba2 convolution window 3x96",
                     str(by["scan_state"]), str(by["conv_window"])):
            assert word in text, (word, text)
        assert telemetry.gauge(
            "serve.decode.kv_bytes_per_slot").value == sum(by.values())
        assert telemetry.gauge(
            "serve.decode.scan_state_bytes_per_slot").value == \
            by["scan_state"]
        assert telemetry.gauge(
            "serve.decode.conv_window_bytes_per_slot").value == \
            by["conv_window"]


def _prefilled(gen, seed):
    """A pool's worth of real state: every row prefilled with its own
    random prompt of 9 tokens."""
    toks = np.stack(_prompts([9] * gen.batch_size, seed=seed))
    _logits, aux = gen._forward(gen._fresh_aux(), toks, 0)
    return aux


def test_fresh_aux_and_cache_merge_carry_all_three_kinds(params):
    gen = _gen(params, 3)
    fresh = gen._fresh_aux()
    assert {gen._aux_kind(n) for n in fresh} == KINDS
    for name, v in fresh.items():
        shape, dtype = gen._aux_spec(name)
        assert v.shape == shape and v.dtype == dtype
        assert not np.asarray(v).any()
    assert fresh["layer0_mamba_scan_state"].dtype == jnp.float32
    pool = {k: np.asarray(v) for k, v in _prefilled(gen, 1).items()}
    src = {k: np.asarray(v) for k, v in _prefilled(gen, 2).items()}
    merged = _merge_program(gen)(
        {k: jnp.asarray(v) for k, v in pool.items()},
        {k: jnp.asarray(v) for k, v in src.items()},
        np.array([2, 0, 0], np.int32), np.int32(2))
    for name in pool:
        got = np.asarray(merged[name])
        np.testing.assert_array_equal(got[2], src[name][0])
        np.testing.assert_array_equal(got[0], src[name][1])
        np.testing.assert_array_equal(got[1], pool[name][1])


def test_export_import_bit_preserves_all_three_kinds(params):
    gen = _gen(params, 3)
    aux = _prefilled(gen, 4)
    blob = gen.export_kv_rows(aux, 1, 9)
    rows = blob["rows"]
    assert rows["layer0_mamba_scan_state"].shape == (8, 8, 16)
    assert rows["layer0_mamba_conv_state"].shape == (3, 96)
    assert rows["layer1_attn_k_cache"].shape == (2, 9, 8)
    # neither Mamba-2 state has a length axis: the same bytes at any
    # depth, the k/v rows grow
    longer = gen.export_kv_rows(aux, 1, 5)["rows"]
    for name, arr in rows.items():
        same = arr.nbytes == longer[name].nbytes
        assert same == name.endswith("_state"), name
    with gen.serving_decoder() as dec:
        dec.import_kv_rows(2, blob)
        for name, arr in rows.items():
            got = np.asarray(dec._aux[name])[2]
            if not name.endswith("_state"):
                # the pool keeps (C, Hkv*hd) token rows; the wire is
                # head-major
                got = got[:9].reshape(9, arr.shape[0], -1).swapaxes(
                    0, 1).reshape(arr.shape)
            np.testing.assert_array_equal(got, arr)
        bad = dict(blob, rows=dict(rows, layer0_mamba_conv_state=rows[
            "layer0_mamba_conv_state"][:2]))
        with pytest.raises(ValueError, match="conv_state"):
            dec.import_kv_rows(0, bad)


def test_evacuate_then_resume_continues_bit_for_bit(params):
    """A session evacuated mid-decode and resumed on a second pool
    emits the tokens an undisturbed run emits: all three kinds of
    state round-trip exactly."""
    p = _prompts([7], seed=6)[0]
    want = _gen(params, 1).generate(p[None], 24)[0]
    d1 = _gen(params, 2).serving_decoder()
    d2 = _gen(params, 2).serving_decoder()
    try:
        fut = d1.submit(p, 24)
        deadline = time.time() + 60.0
        while len(fut.emitted) < 3:
            assert time.time() < deadline, "3 emitted tokens"
            time.sleep(0.001)
        assert d1.evacuate() == 1
        with pytest.raises(SessionEvacuated) as ei:
            fut.result(10.0)
        state = ei.value.state
        assert {Generator._aux_kind(n)
                for n in state["kv_blob"]["rows"]} == KINDS
        got = d2.submit(p, 24, resume=state).result(120.0)
        np.testing.assert_array_equal(got, want)
        assert d2.stats()["resumed"] == 1
        assert d2.stats()["prefills"] == 0
    finally:
        d1.close()
        d2.close()


def test_handoff_prefill_splits_lengths_and_decodes_alike(params):
    """The prefill engine never right-pads a recurrent model: prompts
    of two lengths run as two forwards, and a decoder fed the shipped
    state emits what it would have prefilled itself."""
    pre = PrefillEngine(_gen(params, 2))
    single = _gen(params, 1)
    with _gen(params, 2).serving_decoder() as dec:
        for p in _prompts([5, 11], seed=7):
            got = dec.submit(p, 6, handoff=pre.prefill(p)).result(120.0)
            np.testing.assert_array_equal(
                got, single.generate(p[None], 6)[0])
        assert dec.stats()["prefills"] == 0


def test_chunked_prefill_carries_both_states(params, monkeypatch):
    """MXNET_PREFILL_CHUNK feeds a long prompt in pieces: the window
    and the scan state cross every piece's edge."""
    p = _prompts([19], seed=8)[0]
    want = _gen(params, 1).generate(p[None], 5)[0]
    monkeypatch.setenv("MXNET_PREFILL_CHUNK", "8")
    with _gen(params, 2).serving_decoder() as dec:
        np.testing.assert_array_equal(
            dec.submit(p, 5).result(120.0), want)


def test_speculation_and_training_refuse_the_third_kind(params):
    gen = _gen(params, 2)
    assert gen._has_ssm
    with pytest.raises(ValueError, match="speculative"):
        gen.serving_decoder(draft=_gen(params, 2))
    with pytest.raises(ValueError, match="speculative"):
        gen.truncated_draft(num_layers=1)
    with pytest.raises(ValueError, match="decode path only"):
        transformer.get_symbol(V, 8, num_layers=1, block_type="mamba2")
    with pytest.raises(ValueError, match="mamba2=dict"):
        transformer.get_decode_symbol(V, 8, num_layers=1,
                                      block_type="mamba2")
    with pytest.raises(ValueError, match="no block_type"):
        transformer.get_decode_symbol(V, 8, num_layers=1,
                                      mamba2={"num_heads": 2})


OPT_ARGS = [
    "data", "tok_embed_weight", "pos_embed_weight", "positions",
    "layer0_ln1_gamma", "layer0_ln1_beta", "layer0_qkv_weight",
    "layer0_qkv_bias", "cache_pos", "layer0_proj_weight",
    "layer0_proj_bias", "layer0_ln2_gamma", "layer0_ln2_beta",
    "layer0_fc1_weight", "layer0_fc1_bias", "layer0_fc2_weight",
    "layer0_fc2_bias", "ln_f_gamma", "ln_f_beta", "lm_head_weight",
    "lm_head_bias"]


@pytest.mark.parametrize("per_row_pos", [False, True])
def test_the_opt_style_symbol_is_what_it_was(per_row_pos):
    """The new arguments' defaults leave the block the benchmark's
    other configuration serves as it was: argument list, aux names,
    and the graph itself (no node of the new kinds, no multiplier)."""
    sym = transformer.get_decode_symbol(V, 16, num_layers=1,
                                        num_heads=2, dim=16,
                                        per_row_pos=per_row_pos)
    assert sym.list_arguments() == OPT_ARGS
    assert sym.list_auxiliary_states() == ["layer0_attn_k_cache",
                                           "layer0_attn_v_cache"]
    text = sym.tojson()
    for word in ("RMSNorm", "Mamba2", "_mul_scalar", "_div_scalar",
                 "silu", '"no_bias": "true"'):
        assert word not in text, word


def test_multipliers_are_not_rounded_to_the_arrays_dtype():
    """A Python scalar times a bfloat16 array rounds the SCALAR first
    (0.22 -> 0.2197, every product 0.12% low); the symbol's
    multipliers go through ops that multiply in float32 and round
    once. Seen on the chip as a tenth of the error lying along the
    int8 direction (PERF.md, PR 27)."""
    import mxnet_tpu as mx
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.bfloat16)
    h = jnp.asarray(rng.standard_normal((4, 64)), jnp.bfloat16)
    exact = np.asarray(x, np.float32) * np.float32(0.22)
    got = get_op("_contrib_ScaleF32").fn(x, scalar=0.22)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jnp.asarray(exact).astype(jnp.bfloat16), np.float32))
    naive = np.asarray(x * 0.22, np.float32)
    assert abs((naive / exact).mean() - 1) > 5e-4    # the bias
    assert abs((np.asarray(got, np.float32) / exact).mean() - 1) < 2e-4
    both = get_op("_contrib_AddScaledF32").fn(h, x, scalar=0.22)
    want = (np.asarray(h, np.float32) + exact)
    np.testing.assert_array_equal(
        np.asarray(both, np.float32),
        np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32))
    sym = transformer.get_decode_symbol(
        V, T, **dict(model.generator_args(TOY))).tojson()
    assert "_contrib_AddScaledF32" in sym and "_contrib_ScaleF32" in sym
    assert "_mul_scalar" not in sym and "_div_scalar" not in sym
    assert mx.nd.contrib.ScaleF32(mx.nd.ones((2,)), scalar=3.0) \
        .asnumpy().tolist() == [3.0, 3.0]
