"""What an LFM2-MoE stack forces (ISSUE 39), at toy widths on the CPU:
the gated short convolution (one operator over one carried window, no
scan state, no key/value row); a stack whose FFN differs by layer (a
dense SwiGLU in the leading layer, routed experts after), spelled one
sublayer a layer; a tied head beside routed experts; the routing rule's
published 1e-6; rotary QK-normed GQA in the autoregressive slot pool;
and all of it through Generator -> ContinuousDecoder against the
benchmark's plain reference on logits. Pattern: conv + dense, attn +
experts, conv + experts x 3."""
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_programs
from cellbench.models import lfm2_moe as model
from cellbench.models.opt import served_logits
from cellbench.reference import lfm2_moe as ref
from mxnet_tpu import telemetry
from mxnet_tpu.generation import Generator
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import mamba2, shortconv
from mxnet_tpu.parallel.moe import route_topk
from mxnet_tpu.serve import SessionEvacuated
from mxnet_tpu.serve.decode import _merge_program

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, SEED = 97, 48, 11
with open(os.path.join(ROOT, "cellbench", "configs",
                       "lfm2-24b-a2b.json")) as _f:
    TOY = json.load(_f)
TOY.update(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=48, num_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=16, vocab_size=V, num_hidden_layers=5,
           num_dense_layers=1,
           layer_types=["conv", "full_attention", "conv", "conv", "conv"],
           max_position_embeddings=64, initializer_range=0.2,
           compute_dtype="float32")
TOY["assumed"] = dict(TOY["assumed"], head_dim=8)
KINDS = {"kv_rows", "conv_window"}
# float32 program against float32 reference: rounding of sums of a few
# dozen terms, through ten sublayers (sound runs read 4e-6 to 2e-5). A
# program that computed in bfloat16 reads 1e-2 and more, five hundred
# times the limit: test_bfloat16_in_float32_s_place_fails
TOL = 2e-4
# bfloat16 program (weights, activations, window and key/value rows in
# bfloat16; router and taps in float32) against the float32 reference on
# the same bfloat16 weights: 8 bits of mantissa through ten sublayers at
# 32 channels (sound runs read 0.04 to 0.06 where no router decides)
TOL_BF16 = 0.15


# -- the operator ---------------------------------------------------------------

B, D, K = 2, 24, 3


def _operator(T_, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    return dict(x=f(B, T_, D), w_in=0.3 * f(3 * D, D),
                taps=jnp.asarray(rng.uniform(-0.5, 0.5, (D, K)), dtype),
                w_out=0.3 * f(D, D))


def _whole(p):
    """The equations over the whole sequence, in float64 numpy: rows
    before position 0 are zero, no activation. Returns (out, the gated
    rows g)."""
    x, w_in, taps, w_out = (np.asarray(p[k], np.float64)
                            for k in ("x", "w_in", "taps", "w_out"))
    bcu = x @ w_in.T
    b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    g = b * u
    pad = np.concatenate([np.zeros((B, K - 1, D)), g], axis=1)
    v = sum(pad[:, j:j + x.shape[1]] * taps[:, j] for j in range(K))
    return (c * v) @ w_out.T, g


_OP = jax.jit(shortconv.short_conv)


def _run(p, window, lo, hi):
    return _OP(p["x"][:, lo:hi], p["w_in"], p["taps"], p["w_out"], window)


@pytest.mark.parametrize("cuts", [(13,), (9, 13), (1, 2, 3, 13),
                                  (5, 6, 7, 8, 9, 10, 11, 12, 13),
                                  tuple(range(1, 14))])
def test_prefill_and_steps_through_the_window_are_the_whole_sequence(cuts):
    """Any split of 13 positions into prefills and one-token steps,
    each continuing from the window the one before left: the outputs
    are the full-sequence equations', and the window is the last two
    gated rows."""
    p = _operator(13, seed=len(cuts))
    want, g = _whole(p)
    window = jnp.zeros((B, K - 1, D), jnp.float32)
    outs, lo = [], 0
    for hi in cuts:
        y, window = _run(p, window, lo, hi)
        outs.append(y)
        np.testing.assert_allclose(
            window, np.concatenate([np.zeros((B, K - 1, D)), g], 1)
            [:, hi:hi + K - 1], rtol=1e-5, atol=1e-6)
        lo = hi
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want,
                               rtol=2e-5, atol=2e-5)


def test_the_window_holds_the_gated_rows_in_the_served_dtype():
    """bfloat16: the gated rows are rounded once, before the taps, so a
    step reads exactly the rows a longer prefill would have read: a
    prefill of 12 and a step give the window (bit for bit) and the
    output of one prefill of 13."""
    p = _operator(13, seed=3, dtype=jnp.bfloat16)
    zero = jnp.zeros((B, K - 1, D), jnp.bfloat16)
    y_all, w_all = _run(p, zero, 0, 13)
    _y, w = _run(p, zero, 0, 12)
    y_last, w = _run(p, w, 12, 13)
    assert w.dtype == w_all.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(w, np.float32),
                                  np.asarray(w_all, np.float32))
    np.testing.assert_allclose(np.asarray(y_last, np.float32),
                               np.asarray(y_all[:, 12:], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_the_window_s_update_is_mamba2_s_own():
    """One function moves both windows: Mamba-2's convolution is it
    with a bias and SiLU, the gated one's without either."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 5, D)), jnp.float32)
    win = jnp.asarray(rng.standard_normal((B, K - 1, D)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((D, K)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((D,)), jnp.float32)
    plain, w1 = mamba2.causal_conv(x, win, taps)
    act, w2 = mamba2.mamba2_conv(x, win, taps, bias)
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(w1, jnp.concatenate([win, x], 1)[:, 5:])
    np.testing.assert_allclose(act, jax.nn.silu(plain + bias), rtol=1e-6,
                               atol=1e-6)
    with pytest.raises(ValueError, match="conv_state"):
        mamba2.causal_conv(x, win[:, :1], taps)
    with pytest.raises(ValueError, match="ShortConv"):
        shortconv.short_conv(x, taps, taps, taps, win)


# -- the router -------------------------------------------------------------------

def _router(E=64, d=12, n=9, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((d, E)), jnp.float32),
            jnp.asarray(rng.uniform(-0.17, 0.17, E), jnp.float32))


def test_top_4_of_64_against_the_reference_s_rule():
    """The program's rule with the family's 1e-6 and the reference's
    dense (tokens, experts) weights: the same four experts a token, the
    same weights, and the bias nowhere in them."""
    x, g, b = _router()
    w, e = route_topk(x, g, 4, True, "sigmoid", b, 1.0, renorm_eps=1e-6)
    s = dict(top_k=4, renorm=True, scale=1.0)
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(ref._chosen(
            x, {"gate_weight": g, "gate_score_bias": b}, s))
    assert ((dense > 0).sum(1) == 4).all()
    got = np.zeros_like(dense)
    np.put_along_axis(got, np.asarray(e), np.asarray(w), axis=1)
    np.testing.assert_allclose(got, dense, rtol=2e-6, atol=0)
    score = np.asarray(jax.nn.sigmoid(x @ g), np.float64)
    chosen = np.take_along_axis(score, np.asarray(e), 1)
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    # the bias changed who was chosen
    _, e0 = route_topk(x, g, 4, True, "sigmoid", jnp.zeros_like(b))
    assert not np.array_equal(np.sort(e, 1), np.sort(e0, 1))


def test_the_published_1e_6_is_not_the_default_s_1e_20():
    """4 float32 ulps of a sum near 2: the two rules differ in the last
    bits, so the family's term is an argument and not assumed away;
    the default is the rule the other families were served by."""
    x, g, b = _router(seed=1)
    w6, e6 = route_topk(x, g, 4, True, "sigmoid", b, renorm_eps=1e-6)
    w20, e20 = route_topk(x, g, 4, True, "sigmoid", b)
    wd, _ = route_topk(x, g, 4, True, "sigmoid", b, renorm_eps=1e-20)
    np.testing.assert_array_equal(e6, e20)
    np.testing.assert_array_equal(w20, wd)
    assert float(jnp.abs(w6 - w20).max()) > 0
    assert float(jnp.abs(w6 - w20).max()) < 2e-6
    args = model.generator_args(TOY)
    sym = transformer.get_decode_symbol(
        V, T, num_layers=len(args["layer_kinds"]), **args).tojson()
    assert '"renorm_eps": "1e-06"' in sym


# -- through Generator ---------------------------------------------------------

def _toy(types, dense=1):
    return dict(TOY, layer_types=list(types),
                num_hidden_layers=len(types), num_dense_layers=dense)


def _gen(cfg, batch_size, dtype=None, seed=SEED):
    params = ref.make_params(cfg, seed, dtype or "float32")
    return Generator(params, V, T, batch_size=batch_size, dtype=dtype,
                     **model.generator_args(cfg))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, n, dtype=np.int64) for n in lengths]


def _error(got, want):
    """Largest error over the reference's spread across the
    vocabulary."""
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max() /
                 want.std())


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


TOKENS = np.stack(_prompts([20, 20], seed=3))
WHERE = np.tile(np.arange(11, 19), (2, 1))

CONV, ATTN = "conv", "full_attention"


@pytest.mark.parametrize("types,dense", [
    ((CONV, CONV), 2), ((CONV, CONV), 0), ((ATTN, ATTN), 0),
    ((CONV, ATTN, CONV, CONV, CONV), 1)])
def test_prefill_then_steps_match_one_full_forward(types, dense):
    """Stacks of one kind of operator and of FFN alone, and the mixed
    one: a 12-token prefill then one step a token through the cache,
    against the reference's one forward over all 20."""
    cfg = _toy(types, dense)
    gen = _gen(cfg, 2)
    want = ref.logits_at(cfg, SEED, TOKENS, WHERE, "float32")
    assert _error(_forward_logits(gen, TOKENS, 12), want) < TOL
    kinds = {CONV: {"conv_window"}, ATTN: {"kv_rows"}}
    assert set(gen.state_bytes_by_kind()) == set().union(
        *(kinds[t] for t in types))


def test_bfloat16_against_the_float32_reference():
    """Every FFN dense, so that no router stands between a rounding
    and the logits: the operators in bfloat16 stay inside TOL_BF16 at
    every position. With the experts in, one token of 32 channels
    crosses a near-tie among 8 experts in most runs and its positions
    read whatever that expert did; the positions that saw no such
    crossing, the median among them, stay inside it too."""
    dense = _toy(TOY["layer_types"], 5)
    want = ref.logits_at(dense, SEED, TOKENS, WHERE, "bfloat16")
    err = _error(_forward_logits(_gen(dense, 2, dtype="bfloat16"),
                                 TOKENS, 12), want)
    assert TOL < err < TOL_BF16
    want = np.asarray(ref.logits_at(TOY, SEED, TOKENS, WHERE,
                                    "bfloat16"), np.float64)
    got = _forward_logits(_gen(TOY, 2, dtype="bfloat16"), TOKENS, 12)
    by_position = np.abs(got - want).max(-1) / want.std()
    assert TOL < np.median(by_position) < TOL_BF16


def test_bfloat16_in_float32_s_place_fails():
    """The float32 comparison is tight enough that a program computing
    in the next precision down fails it: the same float32 weights
    served in bfloat16 against the float32 reference."""
    params = ref.make_params(TOY, SEED, "float32")
    low = Generator(params, V, T, batch_size=2, dtype="bfloat16",
                    **model.generator_args(TOY))
    want = ref.logits_at(TOY, SEED, TOKENS, WHERE, "float32")
    assert _error(_forward_logits(low, TOKENS, 12), want) > 20 * TOL


@pytest.mark.parametrize("left_out", [
    "taps", "input_gate", "qk_norm", "rope_base", "score_bias",
    "tie_embeddings"])
def test_each_piece_left_out_fails_the_comparison(left_out):
    """The comparison the sound program passes at TOL is failed, by a
    wide margin, by a program that leaves one piece of the equations
    out."""
    params = dict(ref.make_params(TOY, SEED, "float32"))
    over = {}
    if left_out == "taps":          # only the current row's tap
        for n in list(params):
            if n.endswith("shortconv_conv_weight"):
                params[n] = params[n].at[:, :K - 1].set(0.0)
    elif left_out == "input_gate":  # b and c swapped: c gates the input
        for n in list(params):
            if n.endswith("in_proj_weight"):
                b, c, u = jnp.split(params[n], 3, axis=0)
                params[n] = jnp.concatenate([c, b, u], axis=0)
    elif left_out == "qk_norm":
        over = {"qk_norm": False}
    elif left_out == "rope_base":
        over = {"rope_base": None}
    elif left_out == "score_bias":
        for n in list(params):
            if n.endswith("gate_score_bias"):
                params[n] = jnp.zeros_like(params[n])
    else:
        over = {"tie_embeddings": False}
        params["lm_head_weight"] = jnp.asarray(
            0.2 * np.random.default_rng(5).standard_normal((V, 32)),
            jnp.float32)
    gen = Generator(params, V, T, batch_size=2,
                    **dict(model.generator_args(TOY), **over))
    want = ref.logits_at(TOY, SEED, TOKENS, WHERE, "float32")
    assert _error(_forward_logits(gen, TOKENS, 12), want) > 50 * TOL


def test_a_tied_head_beside_experts_is_one_array():
    gen = _gen(TOY, 1)
    args = gen._sym.list_arguments()
    assert "tok_embed_weight" in args
    assert not [a for a in args if a.startswith("lm_head")]
    assert [a for a in args if a.endswith("experts_w1_weight")]
    assert not [a for a in args if a.endswith(("_bias", "_beta"))
                and not a.endswith("gate_score_bias")]
    # the lookup and the head read the same buffer
    nodes = json.loads(gen._sym.tojson())["nodes"]
    head = next(n for n in nodes if n["name"] == "lm_head")
    assert "tok_embed_weight" in [nodes[i[0]]["name"]
                                  for i in head["inputs"]]
    dtypes = {n: a.dtype for n, a in
              _gen(_toy((CONV, ATTN)), 1, dtype="bfloat16")
              ._params.items()}
    assert dtypes.pop("layer3_gate_score_bias") == jnp.float32
    assert set(dtypes.values()) == {jnp.dtype(jnp.bfloat16)}


def test_an_ffn_that_differs_by_layer_is_spelled_as_it_is():
    args = model.generator_args(TOY)
    assert args["layer_kinds"] == [
        "shortconv", "mlp", "attention", "experts", "shortconv",
        "experts", "shortconv", "experts", "shortconv", "experts"]
    assert (args["ffn_hidden"], args["expert_hidden"]) == (48, 16)
    sym = transformer.get_decode_symbol(
        V, T, num_layers=len(args["layer_kinds"]), **args)
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(2, 1), positions=(1,), cache_pos=(1,))[0]))
    assert shapes["layer1_fc1_weight"] == (2 * 48, 32)
    assert shapes["layer3_experts_w1_weight"] == (8, 32, 2 * 16)
    assert shapes["layer0_in_proj_weight"] == (3 * 32, 32)
    assert shapes["layer0_shortconv_conv_weight"] == (32, 3)
    assert sym.list_auxiliary_states() == [
        "layer0_shortconv_conv_state", "layer2_attn_k_cache",
        "layer2_attn_v_cache", "layer4_shortconv_conv_state",
        "layer6_shortconv_conv_state", "layer8_shortconv_conv_state"]


@pytest.mark.parametrize("bad,match", [
    (dict(block_type="shortconv"), "block_type entries"),
    (dict(layer_kinds=("shortconv", "mlp"), shortconv_kernel=1),
     "shortconv_kernel"),
    (dict(layer_kinds=("shortconv", "mlp"), rolling_cache=True,
          attention_window=4), "rolling_cache")])
def test_spellings_that_disagree_are_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        transformer.get_decode_symbol(V, 32, num_layers=2, num_heads=4,
                                      dim=32, **bad)


def test_the_lowered_step_carries_the_operator_s_names():
    """`shortconv.step` on the one-token program and `shortconv.conv`
    on a prefill's, under the node's own name and its operator's."""
    gen = _gen(_toy((CONV,), 1), 2)

    def text(tnew):
        args = dict(gen._params,
                    data=jnp.zeros((2, tnew), jnp.float32),
                    positions=jnp.arange(tnew, dtype=jnp.float32),
                    cache_pos=jnp.zeros((1,), jnp.float32))
        return gen._step_fn.lower(
            args, gen._fresh_aux(), jax.random.PRNGKey(0)).as_text(
                debug_info=True)

    step, prefill = text(1), text(7)
    assert "layer0_shortconv/op._contrib_ShortConvCached/" \
        "shortconv.step" in step
    assert "shortconv.conv" not in step
    assert "layer0_shortconv/op._contrib_ShortConvCached/" \
        "shortconv.conv" in prefill
    assert "shortconv.step" not in prefill


# -- through the slot pool ----------------------------------------------------------

@pytest.fixture(scope="module")
def pool():
    """Five requests of four prompt lengths through a pool of two
    slots (so rows are admitted while others are mid-flight, at other
    depths, and the pool's windows are reused), with the logits behind
    every token."""
    prompts = _prompts([5, 13, 8, 5, 21])
    with _gen(TOY, 2).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 9)
        jit = telemetry.gauge("serve.decode.jit_cache_size").value
        return prompts, rows, logits, dec.stats(), dec.describe(), jit


def test_logits_through_the_slot_pool_match_the_reference(pool):
    prompts, rows, logits, _stats, _text, jit = pool
    assert jit == 1
    want = ref.served_logits(TOY, SEED, [(len(p), r) for p, r in
                                         zip(prompts, rows)], "float32")
    for got, exp in zip(logits, want):
        assert got.shape == exp.shape == (9, V)
        assert _error(got, exp) < TOL


def test_each_row_of_the_pool_equals_its_lone_run(pool):
    """A slot's window is fresh at admission and survives the merge:
    whatever its neighbours held, a row is the row its prompt gives
    alone."""
    prompts, rows, _logits, stats, _text, _jit = pool
    assert stats["admit_rounds"] >= 3           # admitted mid-flight
    one = _gen(TOY, 1)
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(
            row, np.asarray(one.generate(p[None], 9))[0])


def test_stats_and_the_sizing_report_by_hand(pool):
    """Four expert layers, every step runs both of the pool's rows: 2
    x 2 pairs a layer and step, every expert held here. A slot holds
    four windows of 2 x 32 values and one attention layer's rows."""
    _prompts_, _rows, _logits, stats, text, _jit = pool
    assert stats["moe_assignments"] == stats["steps"] * 4 * 2 * 2
    assert stats["moe_pairs_here"] == stats["moe_assignments"]
    assert stats["steps"] * 4 <= stats["moe_experts_hit"] <= \
        stats["moe_assignments"]
    assert stats["bytes_per_slot"] == {
        "conv_window": 4 * 2 * 32 * 4, "kv_rows": 2 * T * 16 * 4}
    assert "10 layer(s) (5 hold no decode state)" in text
    assert "shortconv convolution window 2x32 (float32" in text
    assert "scan state" not in text and "ssm state" not in text
    assert telemetry.gauge(
        "serve.decode.conv_window_bytes_per_slot").value == 4 * 2 * 32 * 4


def _prefilled(gen, seed):
    toks = np.stack(_prompts([9] * gen.batch_size, seed=seed))
    _logits, aux = gen._forward(gen._fresh_aux(), toks, 0)
    return aux


def test_fresh_aux_and_cache_merge_carry_the_window():
    gen = _gen(TOY, 3)
    fresh = gen._fresh_aux()
    assert {gen._aux_kind(n) for n in fresh} == KINDS
    for name, v in fresh.items():
        shape, dtype = gen._aux_spec(name)
        assert v.shape == shape and v.dtype == dtype
        assert not np.asarray(v).any()
    assert fresh["layer0_shortconv_conv_state"].shape == (3, 2, 32)
    pool_ = {k: np.asarray(v) for k, v in _prefilled(gen, 1).items()}
    src = {k: np.asarray(v) for k, v in _prefilled(gen, 2).items()}
    merged = _merge_program(gen)(
        {k: jnp.asarray(v) for k, v in pool_.items()},
        {k: jnp.asarray(v) for k, v in src.items()},
        np.array([2, 0, 0], np.int32), np.int32(2))
    for name in pool_:
        got = np.asarray(merged[name])
        np.testing.assert_array_equal(got[2], src[name][0])
        np.testing.assert_array_equal(got[0], src[name][1])
        np.testing.assert_array_equal(got[1], pool_[name][1])


def test_export_import_bit_preserves_the_window():
    gen = _gen(TOY, 3)
    aux = _prefilled(gen, 4)
    blob = gen.export_kv_rows(aux, 1, 9)
    rows = blob["rows"]
    assert rows["layer0_shortconv_conv_state"].shape == (2, 32)
    assert rows["layer2_attn_k_cache"].shape == (2, 9, 8)
    # the window has no length axis: the same bytes at any depth, the
    # key/value rows grow
    shorter = gen.export_kv_rows(aux, 1, 5)["rows"]
    for name, arr in rows.items():
        same = arr.nbytes == shorter[name].nbytes
        assert same == name.endswith("_state"), name
    with gen.serving_decoder() as dec:
        dec.import_kv_rows(2, blob)
        for name, arr in rows.items():
            got = np.asarray(dec._aux[name])[2]
            if not name.endswith("_state"):
                got = got[:9].reshape(9, arr.shape[0], -1).swapaxes(
                    0, 1).reshape(arr.shape)
            np.testing.assert_array_equal(got, arr)
        bad = dict(blob, rows=dict(
            rows, layer0_shortconv_conv_state=rows[
                "layer0_shortconv_conv_state"][:1]))
        with pytest.raises(ValueError, match="conv_state"):
            dec.import_kv_rows(0, bad)


def test_evacuate_then_resume_continues_bit_for_bit():
    """A session evacuated mid-decode and resumed on a second pool
    emits the tokens an undisturbed run emits: the windows and the
    key/value rows round-trip exactly."""
    p = _prompts([7], seed=6)[0]
    want = _gen(TOY, 1).generate(p[None], 24)[0]
    d1 = _gen(TOY, 2).serving_decoder()
    d2 = _gen(TOY, 2).serving_decoder()
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


def test_speculation_refuses_a_window_as_it_does_a_scan_state():
    gen = _gen(_toy((CONV, ATTN)), 2)
    assert gen._has_ssm
    with pytest.raises(ValueError, match="speculative"):
        gen.serving_decoder(draft=_gen(_toy((CONV, ATTN)), 2))


# -- the other four families' programs ---------------------------------------------

# sha256 of the StableHLO text, first 16 digits, computed on the parent
# commit 8112ee3 (`cd <its checkout> && PYTHONPATH=. python
# <this tree>/tests/_toy_programs.py`, jax 0.9.0 on the CPU): what this
# PR adds must leave these programs as they were
PARENT = {
    "opt.generator_step": "6e8cb9a964074d8b",
    "opt.decode_step": "27395e8a0e2b21e9",
    "granite.generator_step": "f9ab301df17462bc",
    "granite.decode_step": "087dca3522e08343",
    "nemotron.generator_step": "0eb20743b7af830c",
    "nemotron.decode_step": "4668afad534e8762",
    "sdar.block_step": "0698336f5a5d55c1",
}


@pytest.fixture(scope="module")
def hashes():
    return _toy_programs.hashes()


@pytest.mark.parametrize("program", sorted(PARENT))
def test_the_other_families_programs_hash_as_on_the_parent(hashes,
                                                           program):
    assert hashes[program] == PARENT[program]
