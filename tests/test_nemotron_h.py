"""What a Nemotron-H stack forces (ISSUE 34), at toy widths in float32
on the CPU: Mamba-2 with B, C and the gated norm in groups; a router
with sigmoid scores, a bias that chooses and does not weigh, and a
scaling factor; squared-ReLU experts in a latent width beside a shared
expert; THE CHIP'S SHARE (an expert layer that routes over all experts
and computes the pairs of those it holds); layers that are one
sublayer each, some of which hold no decode state; and all of it
through Generator -> ContinuousDecoder against the benchmark's plain
reference on logits."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cellbench.models import nemotron_h as model
from cellbench.models.opt import served_logits
from cellbench.reference import nemotron_h as ref
from mxnet_tpu.generation import Generator
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import mamba2
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel.moe import route_topk, routed_experts

pytestmark = pytest.mark.serve

# -- Mamba-2 in groups --------------------------------------------------------

B, H, P, N, K = 2, 8, 4, 6, 4


def _mamba_inputs(T, G, seed=0):
    rng = np.random.default_rng(seed)
    C = H * P + 2 * G * N
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    return dict(
        xbc=f(B, T, C), dt=f(B, T, H),
        conv_weight=0.5 * f(C, K), conv_bias=0.5 * f(C),
        dt_bias=(-4.5 + 1.3 * rng.uniform(-1, 1, H)).astype(np.float32),
        a_log=rng.uniform(0.0, 2.77, H).astype(np.float32),
        d_skip=(1 + 0.1 * f(H)))


def _by_token(p, G):
    """The equations, one token at a time, in float64 numpy: head h
    reads B and C of group h // (H / G)."""
    xbc = p["xbc"].astype(np.float64)
    T, C = xbc.shape[1:]
    win = np.zeros((B, K - 1, C))
    S = np.zeros((B, H, P, N))
    A = -np.exp(p["a_log"].astype(np.float64))
    of = np.arange(H) // (H // G)
    ys = []
    for t in range(T):
        full = np.concatenate([win, xbc[:, t:t + 1]], axis=1)
        act = p["conv_bias"] + np.einsum("bkc,ck->bc", full,
                                         p["conv_weight"])
        act = act / (1 + np.exp(-act))
        win = full[:, 1:]
        x = act[:, :H * P].reshape(B, H, P)
        Bm = act[:, H * P:H * P + G * N].reshape(B, G, N)[:, of]
        Cm = act[:, H * P + G * N:].reshape(B, G, N)[:, of]
        step = np.log1p(np.exp(p["dt"][:, t] + p["dt_bias"]))
        S = np.exp(step * A)[..., None, None] * S + \
            (step[..., None] * x)[..., None] * Bm[:, :, None, :]
        ys.append((S * Cm[:, :, None, :]).sum(-1) +
                  p["d_skip"][:, None] * x)
    return np.stack(ys, 1).reshape(B, T, H * P), win, S


_MIX = jax.jit(mamba2.mamba2_mix, static_argnames=(
    "num_heads", "head_dim", "d_state", "chunk", "n_groups"))


def _mix(p, conv, scan, G, chunk=8, lo=0, hi=None):
    return _MIX(p["xbc"][:, lo:hi], p["dt"][:, lo:hi], p["conv_weight"],
                p["conv_bias"], p["dt_bias"], p["a_log"], p["d_skip"],
                conv, scan, num_heads=H, head_dim=P, d_state=N,
                chunk=chunk, n_groups=G)


def _zeros(G):
    return (jnp.zeros((B, K - 1, H * P + 2 * G * N), jnp.float32),
            jnp.zeros((B, H, P, N), jnp.float32))


@pytest.mark.parametrize("chunk", [3, 8, 64])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_grouped_chunk_scan_equals_the_equations(G, chunk):
    p = _mamba_inputs(13, G, seed=G)
    y, win, S = _mix(p, *_zeros(G), G, chunk)
    want = _by_token(p, G)
    for got, exp in zip((y, win, S), want):
        np.testing.assert_allclose(got, exp, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G", [1, 2, 8])
def test_grouped_steps_continue_the_chunked_scan(G):
    """A prefill of 9 tokens, then 4 one-token steps over the carried
    states, against one scan of all 13."""
    p = _mamba_inputs(13, G, seed=10 + G)
    y0, win, S = _mix(p, *_zeros(G), G, 4, 0, 9)
    ys = [y0]
    for t in range(9, 13):
        y1, win, S = _mix(p, win, S, G, 4, t, t + 1)
        ys.append(y1)
    want = _by_token(p, G)
    for got, exp in zip((jnp.concatenate(ys, 1), win, S), want):
        np.testing.assert_allclose(got, exp, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("G", [1, 2, 8])
def test_the_gated_norm_by_group_against_a_loop(G):
    rng = np.random.default_rng(G)
    d = 48
    y, z, gamma = (rng.standard_normal(s).astype(np.float32)
                   for s in ((3, 5, d), (3, 5, d), (d,)))
    kw = {"groups": G} if G > 1 else {}
    got = get_op("_contrib_GatedRMSNorm").fn(y, z, gamma, eps=1e-5, **kw)
    x = y.astype(np.float64) * (z / (1 + np.exp(-z.astype(np.float64))))
    want = np.empty_like(x)
    for g in range(G):
        part = slice(g * d // G, (g + 1) * d // G)
        want[..., part] = x[..., part] / np.sqrt(
            (x[..., part] ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * gamma, rtol=2e-5, atol=2e-5)
    if G > 1:
        plain = get_op("_contrib_GatedRMSNorm").fn(y, z, gamma, eps=1e-5)
        assert float(np.abs(plain - got).max()) > 1e-2


def test_one_group_is_the_op_it_was():
    """n_groups=1 said aloud lowers to the program that saying nothing
    lowers to, text for text, and a one-group symbol names no group."""
    p = _mamba_inputs(13, 1)
    args = (p["xbc"], p["dt"], p["conv_weight"], p["conv_bias"],
            p["dt_bias"], p["a_log"], p["d_skip"]) + _zeros(1)
    text = [jax.jit(lambda *a, kw=kw: mamba2.mamba2_mix(
        *a, num_heads=H, head_dim=P, d_state=N, chunk=4, **kw))
        .lower(*args).as_text() for kw in ({}, {"n_groups": 1})]
    assert text[0] == text[1]
    sym = transformer.get_decode_symbol(
        97, 32, num_layers=1, num_heads=4, dim=32, block_type="mamba2",
        mamba2=dict(num_heads=8, head_dim=8, d_state=16))
    assert "n_groups" not in sym.tojson()
    assert '"groups"' not in sym.tojson()


@pytest.mark.parametrize("sizes", [
    dict(num_heads=8, head_dim=4, d_state=6, n_groups=3),
    dict(num_heads=8, head_dim=4, d_state=6, n_groups=4)])
def test_group_sizes_that_disagree_are_refused(sizes):
    """Heads that do not divide over the groups; a convolution width
    that is another group count's."""
    p = _mamba_inputs(5, 2)
    with pytest.raises(ValueError, match="n_groups"):
        mamba2.mamba2_mix(
            p["xbc"], p["dt"], p["conv_weight"], p["conv_bias"],
            p["dt_bias"], p["a_log"], p["d_skip"], *_zeros(2), **sizes)
    with pytest.raises(ValueError, match="n_groups"):
        transformer._canon_mamba2(dict(num_heads=8, head_dim=4,
                                       d_state=6, n_groups=3),
                                  ("mamba2",))


# -- the router ---------------------------------------------------------------

def _router(E=16, D=12, n=9, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((D, E)), jnp.float32),
            jnp.asarray(0.3 * rng.standard_normal(E), jnp.float32))


@pytest.mark.parametrize("k", [1, 2, 22])
def test_sigmoid_routing_against_numpy(k):
    x, g, b = _router(E=32, seed=k)
    w, e = route_topk(x, g, k, True, "sigmoid", b, 5.0)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(g)))
    order = np.argsort(-(s + np.asarray(b)), axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.sort(e, 1), np.sort(order, 1))
    want = np.take_along_axis(s, np.asarray(e), 1)
    want = 5.0 * want / (want.sum(1, keepdims=True) + 1e-20)
    np.testing.assert_allclose(w, want, rtol=1e-5)
    np.testing.assert_allclose(w.sum(1), 5.0, rtol=1e-5)


def test_the_bias_chooses_and_does_not_weigh():
    x, g, b = _router()
    w0, e0 = route_topk(x, g, 3, False, "sigmoid", jnp.zeros_like(b))
    # lift the expert ranked last for every token past all others
    last = int(np.bincount(np.asarray(e0).ravel(), minlength=16).argmin())
    w1, e1 = route_topk(x, g, 3, False, "sigmoid",
                        jnp.zeros_like(b).at[last].set(10.0))
    assert (np.asarray(e1)[:, 0] == last).all()        # chosen first
    s = jax.nn.sigmoid(x @ g)
    # ... at the weight of its own score, the bias nowhere in it
    np.testing.assert_allclose(w1[:, 0], s[:, last], rtol=1e-6)
    assert float(w1.max()) <= 1.0
    assert not np.array_equal(np.asarray(e0), np.asarray(e1))


def test_the_scaling_factor_multiplies_the_weights():
    x, g, b = _router()
    w1, e1 = route_topk(x, g, 4, True, "sigmoid", b, 1.0)
    w5, e5 = route_topk(x, g, 4, True, "sigmoid", b, 5.0)
    np.testing.assert_array_equal(e1, e5)
    np.testing.assert_allclose(w5, 5.0 * w1, rtol=1e-6)
    ws, _ = route_topk(x, g, 4, True, scale=2.0)        # softmax too
    np.testing.assert_allclose(ws.sum(1), 2.0, rtol=1e-6)


def test_a_tie_goes_to_the_lower_expert_with_sigmoid_scores():
    x, g, b = _router()
    g = g.at[:, 11].set(g[:, 4])
    b = b.at[11].set(b[4])                     # 4 and 11 tie everywhere
    _, e = route_topk(x, g, 16, False, "sigmoid", b)
    e = np.asarray(e)
    for row in e:
        assert list(row).index(4) + 1 == list(row).index(11)
    with pytest.raises(ValueError, match="scoring"):
        route_topk(x, g, 2, False, "tanh", b)


# -- the expert layer: latent, relu2, shared, and the share --------------------

D, E, Z, F, HS = 24, 16, 8, 12, 20


def _layer(seed=0, n=13):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(0.5 * rng.standard_normal(s), jnp.float32)
    return dict(x=f(n, D), gate_weight=f(D, E), gate_score_bias=f(E),
                latent_down_weight=f(D, Z), latent_up_weight=f(Z, D),
                experts_w1_weight=f(E, Z, F), experts_w2_weight=f(E, F, Z),
                shared_w1_weight=f(D, HS), shared_w2_weight=f(HS, D))


def _run(p, k, first=0, held=E, shared=True, bias=None, **kw):
    hold = slice(first, first + held)
    with jax.default_matmul_precision("highest"):
        return routed_experts(
            p["x"], p["gate_weight"], p["experts_w1_weight"][hold],
            p["experts_w2_weight"][hold], top_k=k, act="relu2",
            renormalize=True, scoring="sigmoid",
            score_bias=p["gate_score_bias"] if bias is None else bias,
            scale=2.5, first_expert=first,
            latent=(p["latent_down_weight"], p["latent_up_weight"]),
            shared=(p["shared_w1_weight"], p["shared_w2_weight"])
            if shared else None, **kw)


def _uncut(p, k, shared=HS):
    """The benchmark's plain reference for the WHOLE layer."""
    s = dict(top_k=k, renorm=True, scale=2.5, first=0, held=E,
             shared=shared)
    with jax.default_matmul_precision("highest"):
        return ref._experts(p["x"][None], p, s)[0]


@pytest.mark.parametrize("k", [1, 5])
def test_latent_relu2_experts_against_the_reference_s_loop(k):
    p = _layer(seed=k)
    y, stats = _run(p, k)
    np.testing.assert_allclose(y, _uncut(p, k), rtol=2e-5, atol=2e-5)
    pairs, hit, largest = np.asarray(stats)     # all held: three counts
    assert pairs == 13 * k and 1 <= hit <= E and largest >= 1


def test_the_shared_expert_is_added_whole():
    p = _layer(seed=3)
    with_, _ = _run(p, 3)
    without, _ = _run(p, 3, shared=False)
    a = np.asarray(p["x"], np.float64)
    shared = np.maximum(a @ np.asarray(p["shared_w1_weight"]), 0) ** 2 \
        @ np.asarray(p["shared_w2_weight"])
    np.testing.assert_allclose(with_ - without, shared, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(without, _uncut(p, 3, shared=0),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_four_shares_and_the_shared_expert_once_are_the_whole_layer(seed):
    """What ties the share to the model: the routed parts that the four
    chips of a layer compute, with what every chip computes alike (the
    shared expert) counted once, add up to the uncut reference's layer;
    and the four chips' counts add up to the layer's."""
    p = _layer(seed=seed)
    k = 5
    total, here, hit = 0.0, 0, 0
    for first in range(0, E, 4):
        y, stats = _run(p, k, first, 4, shared=first == 0)
        routed, n_hit, _largest, n_here = np.asarray(stats)
        assert routed == 13 * k
        total, here, hit = total + y, here + n_here, hit + n_hit
    assert here == 13 * k
    np.testing.assert_allclose(total, _uncut(p, k), rtol=3e-5, atol=3e-5)
    _, whole = _run(p, k)
    assert hit == np.asarray(whole)[1]


def test_a_share_that_receives_no_pair_adds_nothing():
    p = _layer(seed=4)
    away = jnp.where(jnp.arange(E) < 4, -100.0, 0.0)
    y, stats = _run(p, 5, 0, 4, shared=False, bias=away)
    assert np.asarray(stats).tolist() == [65, 0, 0, 0]
    assert float(jnp.abs(y).max()) == 0.0


def test_every_pair_of_a_step_to_one_held_expert():
    """k = 1 and a bias that sends every token to expert 6, which the
    share 4-7 holds: one ragged batch of 13 rows, nothing dropped."""
    p = _layer(seed=5)
    to6 = jnp.where(jnp.arange(E) == 6, 100.0, 0.0)
    y, stats = _run(p, 1, 4, 4, shared=False, bias=to6)
    assert np.asarray(stats).tolist() == [13, 1, 13, 13]
    u = p["x"] @ p["latent_down_weight"]
    want = 2.5 * (jnp.square(jax.nn.relu(u @ p["experts_w1_weight"][6]))
                  @ p["experts_w2_weight"][6]) @ p["latent_up_weight"]
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_experts_held_must_lie_inside_the_router_s_outputs():
    p = _layer()
    with pytest.raises(ValueError, match="held"):
        routed_experts(p["x"], p["gate_weight"],
                       p["experts_w1_weight"][:4],
                       p["experts_w2_weight"][:4], top_k=2, act="relu2",
                       first_expert=14)


# -- one sublayer a layer, through Generator and the slot pool -----------------

V, T, SEED = 97, 48, 11
TOY = {"family": "nemotron_h", "hidden_size": 32,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "intermediate_size": 48, "vocab_size": V, "num_hidden_layers": 5,
       "hybrid_override_pattern": "EM*-E", "max_position_embeddings": 64,
       "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
       "n_groups": 4, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
       "n_routed_experts": 4, "routed_experts_first": 4,
       "router_outputs": 16, "num_experts_per_tok": 5,
       "moe_intermediate_size": 24, "moe_latent_size": 16,
       "n_shared_experts": 1, "moe_shared_expert_intermediate_size": 40,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5,
       "n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2",
       "mamba_hidden_act": "silu", "use_bias": False,
       "attention_bias": False, "use_conv_bias": True,
       "tie_word_embeddings": False, "layer_norm_epsilon": 1e-5,
       "initializer_range": 0.2, "compute_dtype": "float32"}
# float32 program against float32 reference: rounding of sums of a few
# dozen terms, through five layers
TOL = 2e-4


def _toy(pattern):
    return dict(TOY, hybrid_override_pattern=pattern,
                num_hidden_layers=len(pattern))


def _gen(cfg, batch_size, dtype=None):
    params = ref.make_params(cfg, SEED, dtype or "float32")
    return Generator(params, V, T, batch_size=batch_size, dtype=dtype,
                     **model.generator_args(cfg))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, n, dtype=np.int64) for n in lengths]


def _error(got, want):
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got) - want).max() / want.std())


@pytest.mark.parametrize("pattern", ["MM", "**", "EE", "--", "EM*-E"])
def test_prefill_then_steps_match_one_full_forward(pattern):
    """A stack of each kind of single-sublayer layer alone, and the
    mixed one: a 12-token prefill then one step a token through the
    cache, against the reference's one forward over all 20."""
    cfg = _toy(pattern)
    gen = _gen(cfg, 2)
    tokens = np.stack(_prompts([20, 20], seed=3))
    aux = gen._fresh_aux()
    logits, aux = gen._forward(aux, tokens[:, :12], 0)
    outs = [np.asarray(logits[:, -1], np.float32)]
    for i in range(12, 19):
        logits, aux = gen._forward(aux, tokens[:, i:i + 1], i)
        outs.append(np.asarray(logits[:, -1], np.float32))
    where = np.tile(np.arange(11, 19), (2, 1))
    want = ref.logits_at(cfg, SEED, tokens, where, "float32")
    assert _error(np.stack(outs, 1), want) < TOL
    # a layer of experts or of an MLP holds no state; a mixer holds its
    # own kinds
    kinds = {"M": {"scan_state", "conv_window"}, "*": {"kv_rows"}}
    assert set(gen.state_bytes_by_kind()) == set().union(
        *(kinds.get(c, set()) for c in pattern))


@pytest.fixture(scope="module")
def pool():
    """Five requests of four prompt lengths through a pool of two
    slots (so rows are admitted while others are mid-flight and the
    pool's states are reused), with the logits behind every token."""
    prompts = _prompts([5, 13, 8, 5, 21])
    with _gen(TOY, 2).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 9)
        return prompts, rows, logits, dec.stats(), dec.describe()


def test_logits_through_the_slot_pool_match_the_reference(pool):
    prompts, rows, logits, _stats, _text = pool
    want = ref.served_logits(TOY, SEED, [(len(p), r) for p, r in
                                         zip(prompts, rows)], "float32")
    for got, exp in zip(logits, want):
        assert got.shape == exp.shape == (9, V)
        assert _error(got, exp) < TOL


def test_decoder_rows_equal_the_one_shot_rows(pool):
    prompts, rows, _logits, stats, _text = pool
    assert stats["admit_rounds"] >= 3           # admitted mid-flight
    one = _gen(TOY, 1)
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(
            row, np.asarray(one.generate(p[None], 9))[0])


def test_stats_count_the_experts_work_by_hand(pool):
    """Two expert layers; every step runs both of the pool's rows
    (an idle row's pairs are computed too): 2 x 5 pairs a layer and
    step are routed, and those of experts 4-7 are computed here."""
    _prompts_, _rows, _logits, stats, text = pool
    assert stats["moe_assignments"] == stats["steps"] * 2 * 2 * 5
    assert 0 < stats["moe_pairs_here"] < stats["moe_assignments"]
    assert 0 < stats["moe_experts_hit"] <= stats["steps"] * 2 * 4
    assert stats["moe_experts_hit"] <= stats["moe_pairs_here"]
    assert stats["moe_max_load"] >= 1.0
    assert stats["bytes_per_slot"] == {
        "scan_state": 8 * 8 * 16 * 4, "conv_window": 3 * (64 + 128) * 4,
        "kv_rows": 2 * T * 32 * 4}
    assert "5 layer(s) (3 hold no decode state)" in text


def test_the_score_bias_stays_float32_in_a_bfloat16_model():
    gen = _gen(_toy("E"), 1, dtype="bfloat16")
    dtypes = {n: a.dtype for n, a in gen._params.items()}
    assert dtypes.pop("layer0_gate_score_bias") == jnp.float32
    assert set(dtypes.values()) == {jnp.dtype(jnp.bfloat16)}


def test_the_old_spelling_builds_the_symbol_it_built():
    """block_type + an FFN in every layer: no argument of this PR shows
    in the symbol unless it is given."""
    dense = transformer.get_decode_symbol(97, 32, num_layers=2,
                                          num_heads=4, dim=32)
    experts = transformer.get_decode_symbol(
        97, 32, num_layers=2, num_heads=4, dim=32, num_experts=8,
        experts_per_token=2, expert_hidden=16, ffn="gated_silu",
        moe_stats=True, per_row_pos=True)
    for sym in (dense, experts):
        text = sym.tojson()
        for word in ("scoring", "first_expert", "latent", "shared",
                     "n_groups"):
            assert word not in text, word
        assert "layer1_ln2_gamma" in sym.list_arguments()
    one = transformer.get_decode_symbol(
        97, 32, num_heads=4, dim=32, num_layers=2,
        layer_kinds=("attention", "mlp"))
    assert "layer0_ln2_gamma" not in one.list_arguments()
    assert one.list_auxiliary_states() == ["layer0_attn_k_cache",
                                           "layer0_attn_v_cache"]


@pytest.mark.parametrize("bad,match", [
    (dict(layer_kinds=("attention", "mlp"), block_type="ssm"),
     "two spellings"),
    (dict(layer_kinds=("attention", "experts")), "go together"),
    (dict(layer_kinds=("attention", "mlp"), num_experts=4),
     "go together"),
    (dict(layer_kinds=("attention", "moe")), "layer_kinds entries"),
    (dict(layer_kinds=("attention",)), "names each layer"),
    (dict(num_experts=8, experts_held=(6, 4)), "experts_held")])
def test_spellings_that_disagree_are_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        transformer.get_decode_symbol(97, 32, num_layers=2, num_heads=4,
                                      dim=32, **bad)


def test_a_draft_of_the_first_sublayers_is_refused():
    with pytest.raises(ValueError, match="layer_kinds"):
        _gen(_toy("*-"), 1).truncated_draft(1)
