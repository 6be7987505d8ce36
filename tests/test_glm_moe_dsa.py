"""What a GLM-MoE-DSA stack forces (ISSUE 46), at toy widths on the CPU:
multi-head latent attention whose keys a learned indexer selects, as
one mixer kind ("mla") sized by one dict; a third kind of rows in the
slot pool, latent rows and index-key rows with widths of their own,
filled by chunks and stepped at one depth a row; the rotation on a
slice of a head; and all of it through Generator -> ContinuousDecoder
against the benchmark's plain reference on logits. Toy stack: three
layers (one dense, two with experts), 4 heads of 12 + 4 | 8, latents of
24 and 16, 3 index heads of 8, the 8 best keys kept, chunks of 8."""
import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_programs
from cellbench.models import glm_moe_dsa as model
from cellbench.models.opt import served_logits
from cellbench.ops import glm_moe_dsa as ops
from cellbench.reference import glm_moe_dsa as ref
from mxnet_tpu import config
from mxnet_tpu.generation import Generator
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import mla
from mxnet_tpu.parallel.moe import routed_experts
from mxnet_tpu.serve import PrefillEngine, SessionEvacuated
from mxnet_tpu.serve.decode import _merge_program

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, SEED, TOPK, CHUNK = 97, 64, 11, 8, 8
with open(os.path.join(ROOT, "cellbench", "configs", "glm-5.json")) as _f:
    PUBLISHED = json.load(_f)
TOY = dict(PUBLISHED)
TOY.update(hidden_size=32, num_attention_heads=4, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
           qk_head_dim=16, v_head_dim=8, index_n_heads=3,
           index_head_dim=8, index_topk=TOPK, intermediate_size=48,
           moe_intermediate_size=16, n_routed_experts=4,
           router_outputs=8, routed_experts_first=2,
           num_experts_per_tok=3, num_hidden_layers=3,
           first_k_dense_replace=1, vocab_size=V,
           max_position_embeddings=128, initializer_range=0.3,
           compute_dtype="float32")
KINDS = {"latent_rows", "index_rows"}
# float32 program against float32 reference: rounding of sums of a few
# dozen terms through three layers (sound runs read 1e-6 to 3e-5 of
# the logits' spread; the two forms of the attention, in the latent
# space and expanded a head, order their sums differently). A program
# that computed in bfloat16 reads 1e-2 and more, and one that attended
# another key than the reference selected a tenth and more
TOL = 2e-4


@pytest.fixture(autouse=True)
def _chunked():
    config.set_override("MXNET_PREFILL_CHUNK", CHUNK)
    yield
    config.set_override("MXNET_PREFILL_CHUNK", None)


def _gen(cfg, batch, dtype="float32", seed=SEED, max_len=T, **over):
    s = ref.sizes(cfg)
    args = dict(model.generator_args(cfg), **over)
    return Generator(ref.make_params(cfg, seed, dtype), s["vocab"],
                     max_len, batch_size=batch, dtype=dtype, **args)


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, n, dtype=np.int64) for n in lengths]


def _error(got, want):
    """Largest difference over the spread of the reference's logits."""
    return float(np.abs(got - want).max() / want.std())


# -- (a) chunks, then steps through the pool, against one forward -------------

@pytest.fixture(scope="module")
def pool():
    """Three prompts several times longer than the 8 keys kept through
    a pool of two slots by chunks of 8 (the last chunk ragged): rows at
    unequal depths, the third admitted into a slot another has left."""
    config.set_override("MXNET_PREFILL_CHUNK", CHUNK)
    prompts = _prompts([41, 27, 50])
    with _gen(TOY, 2).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 7)
        stats, text = dec.stats(), dec.describe()
    config.set_override("MXNET_PREFILL_CHUNK", None)
    return prompts, rows, logits, stats, text


def test_chunks_then_steps_through_the_pool_match_one_forward(pool):
    prompts, rows, logits, stats, _ = pool
    want = list(ref.served_logits(
        TOY, SEED, [(len(p), r) for p, r in zip(prompts, rows)],
        "float32"))
    for got, ref_logits in zip(logits, want):
        assert got.shape == ref_logits.shape == (7, V)
        assert _error(got, ref_logits) < TOL
    # every prompt went in by chunks of 8, one row a chunk
    assert stats["chunks"] == stats["chunk_rows"] == 6 + 4 + 7
    assert stats["prefills"] == 3


def test_stats_and_the_sizing_report_by_kind(pool):
    *_, stats, text = pool
    # 1 408 B a token and layer at the published widths: here (16 + 4)
    # latent and rotary-key numbers and 8 index-key numbers, float32
    assert stats["bytes_per_slot"] == {"latent_rows": 3 * T * 20 * 4,
                                       "index_rows": 3 * T * 8 * 4}
    # a step's queries attend 8 keys each of the 27 to 57 they see
    # (idle rows' one key of one counted: they are computed)
    assert 0 < stats["dsa_keys_selected"] < stats["dsa_keys_visible"] / 3
    assert stats["dsa_keys_selected"] % 3 == 0        # three mixers
    assert stats["moe_assignments"] > 0
    assert "latent rows 64x20" in text and "index-key rows 64x8" in text
    assert "(3 hold no decode state)" in text


@pytest.mark.parametrize("topk", [TOPK, T])
def test_one_prefill_and_its_chunks_equal_the_reference(topk):
    """(d) too: with index_topk >= max_len every visible key is
    attended, and the layer is plain causal latent attention (which is
    what the reference computes when told to keep as many)."""
    cfg = dict(TOY, index_topk=topk)
    gen = _gen(cfg, 2)
    toks = np.stack(_prompts([40, 40], seed=5))
    want = np.asarray(ref.logits_at(
        cfg, SEED, toks, np.tile(np.arange(40), (2, 1)), "float32"))
    whole, _ = gen._forward(gen._fresh_aux(), toks, 0)
    assert _error(np.asarray(whole), want) < TOL
    aux, parts = gen._fresh_aux(), []
    for lo in range(0, 40, CHUNK):
        part, aux = gen._forward(aux, toks[:, lo:lo + CHUNK], lo)
        parts.append(np.asarray(part))
    assert _error(np.concatenate(parts, 1), want) < TOL


def test_keeping_every_key_differs_from_keeping_eight():
    """The selection does something at these lengths: the same weights
    with every key kept give other logits past the first 8 positions
    and the same ones up to there."""
    toks = np.stack(_prompts([40], seed=5))
    kept, _ = _gen(TOY, 1)._forward(_gen(TOY, 1)._fresh_aux(), toks, 0)
    dense = dict(TOY, index_topk=T)
    every, _ = _gen(dense, 1)._forward(_gen(dense, 1)._fresh_aux(),
                                       toks, 0)
    kept, every = np.asarray(kept), np.asarray(every)
    assert _error(kept[:, :TOPK], every[:, :TOPK]) < TOL
    assert _error(kept[:, TOPK:], every[:, TOPK:]) > 100 * TOL


def test_each_row_of_the_pool_equals_its_lone_run(pool):
    prompts, rows, *_ = pool
    with _gen(TOY, 1).serving_decoder() as dec:
        for p, row in zip(prompts, rows):
            np.testing.assert_array_equal(
                dec.submit(p, 7).result(120.0), row)


def test_bfloat16_in_float32_s_place_fails():
    prompts = _prompts([41])
    with _gen(TOY, 1, dtype="bfloat16").serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 7)
    want = list(ref.served_logits(TOY, SEED, [(41, rows[0])], "float32"))
    assert _error(logits[0], want[0]) > 50 * TOL


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """What lets a 16 512-token row fit: the reference's queries by
    blocks, each against the keys up to its own end at the nearest of
    four key counts, and its experts over the tokens routed to them,
    change no number of it (here 50 positions in blocks of 16, padded
    to 64; an expert over its 8, 32 or all 100 tokens, whichever holds
    those routed to it)."""
    toks = np.stack(_prompts([50, 50], seed=9))
    where = np.tile(np.arange(50), (2, 1))
    whole = np.asarray(ref.logits_at(TOY, SEED, toks, where, "float32"))
    monkeypatch.setattr(ref, "_QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "_EXPERT_TOKENS", (8, 32))
    ref._programs.cache_clear()
    assert ref._key_counts(4, 16) == [16, 32, 48, 64]
    assert ref._key_counts(17, 1024) == [5120, 9216, 13312, 17408]
    try:
        blocked = np.asarray(ref.logits_at(TOY, SEED, toks, where,
                                           "float32"))
    finally:
        ref._programs.cache_clear()
    assert _error(blocked, whole) < 1e-5


# -- (b) the selection alone ------------------------------------------------------

def _scores(seed, b=2, t=24, j=3, di=8):
    rng = np.random.default_rng(seed)
    qi = rng.standard_normal((b, t, j, di)).astype(np.float32)
    ki = rng.standard_normal((b, t, di)).astype(np.float32)
    w = rng.standard_normal((b, t, j)).astype(np.float32)
    return qi, ki, w


def _program_sets(qi, ki, w, pos, k):
    """{(row, query): frozenset of selected positions} as the program
    selects them for the new rows at depth `pos` of a cache holding
    `ki`."""
    b, t = ki.shape[:2]
    tn = t - pos
    scores = mla.index_scores(jnp.asarray(qi[:, pos:]),
                              jnp.asarray(w[:, pos:]), jnp.asarray(ki))
    sel = np.asarray(mla.select_keys(scores, jnp.int32(pos), k))
    return {(r, pos + q): frozenset(np.flatnonzero(sel[r, q]).tolist())
            for r in range(b) for q in range(tn)}


@pytest.mark.parametrize("pos", [0, 8, 23])
def test_the_program_selects_the_reference_s_sets(pos):
    """Random scores have no near-ties: the program's chosen sets (a
    whole prompt, a chunk at depth 8, one token at depth 23) are the
    reference's, every visible key while fewer than 8 are."""
    qi, ki, w = _scores(pos)
    with jax.default_matmul_precision("highest"):
        sel = np.asarray(ref.selected(jnp.asarray(qi), jnp.asarray(ki),
                                      jnp.asarray(w), TOPK))[0]
    got = _program_sets(qi, ki, w, pos, TOPK)
    for (r, t), chosen in got.items():
        assert chosen == frozenset(np.flatnonzero(sel[r, t]).tolist())
        assert len(chosen) == min(t + 1, TOPK)
        if t < TOPK:
            assert chosen == frozenset(range(t + 1))


def test_equal_scores_go_to_the_lower_position():
    """Ties, in program and reference: all scores equal (a zero head
    weight), the 8 kept are positions 0-7 however deep the query; two
    equal keys at the edge of the 8, the lower is kept."""
    qi, ki, w = _scores(1)
    got = _program_sets(qi, ki, np.zeros_like(w), 0, TOPK)
    sel = np.asarray(ref.selected(jnp.asarray(qi), jnp.asarray(ki),
                                  jnp.zeros(w.shape), TOPK))[0]
    for (r, t), chosen in got.items():
        assert chosen == frozenset(range(min(t + 1, TOPK)))
        assert frozenset(np.flatnonzero(sel[r, t]).tolist()) == chosen
    # position 20 repeats position 3's key: they tie wherever they rank
    ki[:, 20] = ki[:, 3]
    got = _program_sets(qi, ki, w, 0, TOPK)
    with jax.default_matmul_precision("highest"):
        sel = np.asarray(ref.selected(jnp.asarray(qi), jnp.asarray(ki),
                                      jnp.asarray(w), TOPK))[0]
    for (r, t), chosen in got.items():
        assert chosen == frozenset(np.flatnonzero(sel[r, t]).tolist())
        assert t < 20 or 3 in chosen or 20 not in chosen
    # the tie on the edge of the 8, by hand: seven larger scores, two
    # equal ones at 3 and 20, the rest smaller
    row = np.full((1, 1, 24), -1.0, np.float32)
    row[0, 0, 10:17] = np.arange(7) + 2.0
    row[0, 0, [3, 20]] = 1.0
    sel = mla.select_keys(jnp.asarray(row), jnp.int32(23), TOPK)
    assert np.flatnonzero(np.asarray(sel)[0, 0]).tolist() == \
        [3] + list(range(10, 17))
    # -0.0 ties with +0.0, as a comparison of floats has it
    row[0, 0, 3], row[0, 0, 20] = 0.0, -0.0
    row[0, 0, 10:17], row[0, 0, 21:] = np.arange(7) + 1.0, -0.5
    sel = mla.select_keys(jnp.asarray(row), jnp.int32(23), TOPK)
    assert np.flatnonzero(np.asarray(sel)[0, 0]).tolist() == \
        [3] + list(range(10, 17))


# -- the part over cached rows, a block of columns at a time -------------------

_OP = dict(num_heads=4, qk_nope_head_dim=12, qk_rope_head_dim=4,
           v_head_dim=8, index_heads=3)

def _mixer(width, depth, topk=TOPK, rows=2, seed=0):
    """The op alone on a chunk of 8 new positions at `depth` of a
    buffer `width` columns long, every cached row random (those past
    the depth too: nothing may read them): (out, stats)."""
    rng = np.random.default_rng(seed)
    layer = ref._program_layer(ref.base_key(seed), 0, "mla",
                               ref.sizes(TOY), jnp.float32)
    w = {k[4:]: v for k, v in layer.items() if k.startswith("mla_")}
    x = jnp.asarray(rng.standard_normal((rows, CHUNK, 32)), jnp.float32)
    latent = rng.standard_normal((rows, 256, 20)).astype(np.float32)
    index = rng.standard_normal((rows, 256, 8)).astype(np.float32)
    out, stats, _, _ = jax.jit(
        lambda *a: mla.latent_select_attention(*a, index_topk=topk, **_OP))(
        x, depth + jnp.arange(CHUNK, dtype=jnp.float32), w,
        jnp.asarray(latent[:, :width]), jnp.asarray(index[:, :width]),
        jnp.full((1,), depth, jnp.int32))
    return np.asarray(out), np.asarray(stats).tolist()


@pytest.mark.parametrize("depth", [0, 8, 24, 40, 56])
def test_a_chunk_computes_the_columns_to_its_own_depth(depth, monkeypatch):
    """Blocks of 16 columns (a quarter of the short buffer; in the
    long one the budget holds a row's 4 x 8 float32 scores of as
    many): a chunk that ends at `depth + 8` runs the blocks up to
    there and no others, and the columns past them change nothing,
    bit for bit: a buffer four times as long gives the same output
    and counts."""
    monkeypatch.setattr(mla, "_SCORE_BYTES", 4 * CHUNK * 16 * 4)
    assert mla._block_width(CHUNK, 4, 64) == \
        mla._block_width(CHUNK, 4, 256) == 16
    out, (visible, selected, computed) = _mixer(64, depth)
    assert computed == 2 * CHUNK * -(-(depth + CHUNK) // 16) * 16
    assert visible == 2 * sum(depth + r + 1 for r in range(CHUNK))
    assert selected == 2 * sum(min(depth + r + 1, TOPK)
                               for r in range(CHUNK))
    longer, counts = _mixer(256, depth)
    np.testing.assert_array_equal(longer, out)
    assert counts == [visible, selected, computed]


def test_a_forward_no_deeper_than_the_keys_kept_selects_nothing():
    """With `deepest <= index_topk` every visible row is kept by
    definition: the forward gives what the configuration that keeps
    every key (`index_topk = max_len`: no indexer at all) gives, bit
    for bit; one position deeper it does not. (The branch it takes
    holds no indexer and no selection: the lowered programs' test.)"""
    out, (visible, selected, _) = _mixer(64, 8, topk=16)
    assert selected == visible
    every, counts = _mixer(64, 8, topk=64)
    np.testing.assert_array_equal(out, every)
    assert counts[:2] == [visible, visible]
    out, (visible, selected, _) = _mixer(64, 9, topk=16)
    assert selected == visible - 2 * 1
    assert np.abs(out - _mixer(64, 9, topk=64)[0]).max() > 1e-3

def test_the_pool_counts_the_keys_its_steps_computed(pool):
    """`dsa_keys_computed`: columns run x queries over the steps' three
    mixers, both rows of the pool: the blocks of 16 (a quarter of the
    64 columns) up to the step's deepest row, 27 to 57 deep here."""
    *_, stats, _ = pool
    queries = stats["steps"] * 2 * 3
    assert mla._block_width(1, 4, T) == 16
    assert stats["dsa_keys_computed"] % (16 * 2 * 3) == 0
    assert stats["dsa_keys_visible"] <= stats["dsa_keys_computed"] < \
        T * queries


# -- (c) the latent-space form, the rotary slice, the loader's permutation --------

def test_the_latent_space_form_equals_keys_and_values_expanded_a_head():
    """attend_selected (the query carried through Wkb's key half, the
    sum of latents through its value half, over every cached row under
    the selection's mask) against a literal gather of the selected
    keys and values expanded a head, in float64."""
    rng = np.random.default_rng(2)
    b, t, c, h, nope, rd, lat, vd, k = 2, 5, 20, 4, 12, 4, 16, 8, 6
    q = rng.standard_normal((b, t, h, nope + rd))
    rows = rng.standard_normal((b, c, lat + rd))
    wkb = rng.standard_normal((h, nope + vd, lat))
    idx = np.stack([[rng.choice(c, k, replace=False) for _ in range(t)]
                    for _ in range(b)])
    ok = rng.random((b, t, k)) < 0.8
    ok[..., 0] = True
    sel = np.zeros((b, t, c), bool)
    np.put_along_axis(sel, idx, ok, axis=2)
    ql = np.einsum("bthn,hnl->bthl", q[..., :nope], wkb[:, :nope])
    got = mla.attend_selected(
        jnp.asarray(np.concatenate([ql, q[..., nope:]], -1), jnp.float32),
        jnp.asarray(rows, jnp.float32), jnp.asarray(sel),
        jnp.asarray(wkb[:, nope:].swapaxes(1, 2), jnp.float32),
        (nope + rd) ** -0.5)
    want = np.zeros((b, t, h, vd))
    for r in range(b):
        for i in range(t):
            for g in range(h):
                picked = rows[r, idx[r, i][ok[r, i]]]
                kv = picked[:, :lat] @ wkb[g].T        # (n, nope + vd)
                keys = np.concatenate([kv[:, :nope], picked[:, lat:]], 1)
                s = keys @ q[r, i, g] / np.sqrt(nope + rd)
                p = np.exp(s - s.max())
                want[r, i, g] = (p / p.sum()) @ kv[:, nope:]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-5)


def test_the_loader_s_permutation_makes_half_split_pairs_interleaved():
    """The program rotates channel i with i + 2 of a 4-channel slice,
    the published block 2i with 2i + 1: on the loader's order (evens,
    odds) the two are one rotation, and only the slice turns."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 9, 3, 16)).astype(np.float32)
    at = np.tile(np.arange(5, 14, dtype=np.float32), (2, 1))
    order = ref._rotary_order(16, 12, 4)
    assert order.tolist() == list(range(12)) + [12, 14, 13, 15]
    theirs = np.asarray(ref._rope_interleaved(
        jnp.asarray(np.pad(x[..., 12:], ((0, 0), (5, 0), (0, 0), (0, 0)))),
        1e6))[:, 5:]
    mine = np.asarray(mla._rotate(jnp.asarray(x[..., order][..., 12:]),
                                  jnp.asarray(at), 1e6))
    np.testing.assert_allclose(mine, theirs[..., [0, 2, 1, 3]],
                               rtol=1e-5, atol=1e-6)
    # an index head's rotary slice is its FIRST channels
    assert ref._rotary_order(8, 0, 4).tolist() == [0, 2, 1, 3, 4, 5, 6, 7]
    s = ref.sizes(TOY)
    p = ref._program_layer(ref.base_key(SEED), 0, "mla", s, jnp.float32)
    t = ref._layer_tensors(ref.base_key(SEED), 0, "mla", s, jnp.float32)
    head = np.asarray(p["mla_q_b_weight"]).reshape(4, 16, 24)
    np.testing.assert_array_equal(
        head, np.asarray(t["mla_q_b_weight"]).reshape(4, 16, 24)[:, order])
    for name in ("mla_index_k_weight", "mla_index_k_norm_gamma",
                 "mla_index_k_norm_beta"):
        np.testing.assert_array_equal(
            np.asarray(p[name]),
            np.asarray(t[name])[ref._rotary_order(8, 0, 4)])
    for name in ("mla_q_a_weight", "mla_kv_b_weight", "mla_o_weight",
                 "mla_index_head_weight", "mla_kv_a_norm_gamma"):
        np.testing.assert_array_equal(np.asarray(p[name]),
                                      np.asarray(t[name]))


# -- (e) the shares add up -----------------------------------------------------------

def test_the_shares_with_what_every_chip_computes_once_are_the_whole_layer():
    """What ties the share to the model: the routed parts that the
    chips of an expert layer compute through the PROGRAM's layer (here
    8 experts over chips of 2, 2, 1, 1 and 2), with what every chip
    computes alike (the shared expert; attention and the dense layer
    are other sublayers, whole on every chip) counted once, add up to
    the uncut reference's layer."""
    uncut = dict(TOY, n_routed_experts=8, routed_experts_first=0)
    s = ref.sizes(uncut)
    key = ref.base_key(SEED)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 12, 32)),
                    jnp.float32)
    p = ref._program_layer(key, 3, "experts", s, jnp.float32)

    def expert(stream, e):
        return ref._expert_tensors(key, 3, stream, e, s, jnp.float32)

    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref._experts(x, p, expert, s) - x, np.float64)
        a = ref._rms(x, p["ln1_gamma"], s["eps"]).reshape(-1, 32)
        total = np.zeros(whole.shape)
        for n, (first, held) in enumerate(
                [(0, 2), (2, 2), (4, 1), (5, 1), (6, 2)]):
            part, _ = routed_experts(
                a, p["gate_weight"],
                p["experts_w1_weight"][first:first + held],
                p["experts_w2_weight"][first:first + held], top_k=3,
                act="gated_silu", renormalize=True, scoring="sigmoid",
                score_bias=p["gate_score_bias"], scale=2.5,
                first_expert=first,
                shared=(p["shared_w1_weight"], p["shared_w2_weight"])
                if n == 0 else None)
            total += np.asarray(part, np.float64).reshape(whole.shape)
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


# -- (f) the new pytree through merge, export, import, evacuation --------------------

def _prefilled(gen, seed, length=29):
    toks = np.stack(_prompts([length] * gen.batch_size, seed=seed))
    aux = gen._fresh_aux()
    for lo in range(0, length, CHUNK):
        _logits, aux = gen._forward(aux, toks[:, lo:lo + CHUNK], lo)
    return aux


def test_fresh_aux_and_cache_merge_carry_two_widths():
    gen = _gen(TOY, 3)
    fresh = gen._fresh_aux()
    assert {gen._aux_kind(n) for n in fresh} == KINDS
    assert sorted(fresh) == sorted(
        "layer%d_mla_%s_cache" % (i, k) for i in (0, 2, 4)
        for k in ("latent", "index"))
    for name, v in fresh.items():
        shape, dtype = gen._aux_spec(name)
        assert v.shape == shape and v.dtype == dtype
        assert not np.asarray(v).any()
    assert fresh["layer0_mla_latent_cache"].shape == (3, T, 20)
    assert fresh["layer4_mla_index_cache"].shape == (3, T, 8)
    assert gen._fresh_aux(1)["layer2_mla_latent_cache"].shape == (1, T, 20)
    assert gen.state_bytes_per_slot() == 3 * T * 28 * 4
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


def test_export_import_bit_preserves_both_kinds_of_rows():
    gen = _gen(TOY, 3)
    aux = _prefilled(gen, 4)
    blob = gen.export_kv_rows(aux, 1, 29)
    rows = blob["rows"]
    # every head shares a row: ONE head on the wire, its own width
    assert rows["layer0_mla_latent_cache"].shape == (1, 29, 20)
    assert rows["layer2_mla_index_cache"].shape == (1, 29, 8)
    with gen.serving_decoder() as dec:
        assert dec.import_kv_rows(2, blob) == 29
        for name, v in aux.items():
            np.testing.assert_array_equal(
                np.asarray(dec._aux[name])[2, :29], np.asarray(v)[1, :29])
        bad = dict(blob, rows=dict(
            rows, layer0_mla_index_cache=rows[
                "layer0_mla_index_cache"][:, :9]))
        with pytest.raises(ValueError, match="layer0_mla_index_cache"):
            dec.import_kv_rows(0, bad)


def test_evacuate_then_resume_continues_bit_for_bit():
    """A session evacuated mid-decode, several times the kept keys
    deep, and resumed on a second pool emits the tokens an undisturbed
    run emits."""
    p = _prompts([30], seed=6)[0]
    d0, d1, d2 = (_gen(TOY, 2).serving_decoder() for _ in range(3))
    try:
        want = d0.submit(p, 20).result(120.0)
        three = threading.Event()

        def hold(req, _row):
            if len(req.emitted) >= 3 and not three.is_set():
                three.set()
                end = time.time() + 60.0
                while not d1._evac_waiters and time.time() < end:
                    time.sleep(0.0005)

        d1.on_logits = hold
        fut = d1.submit(p, 20)
        assert three.wait(120.0), "3 emitted tokens"
        assert d1.evacuate() == 1
        with pytest.raises(SessionEvacuated) as ei:
            fut.result(10.0)
        state = ei.value.state
        assert state["kv_blob"]["pos"] > 30
        assert set(state["kv_blob"]["rows"]) == set(d1._aux)
        got = d2.submit(p, 20, resume=state).result(120.0)
        np.testing.assert_array_equal(got, want)
        assert d2.stats()["resumed"] == 1
        assert d2.stats()["prefills"] == 0
    finally:
        for d in (d0, d1, d2):
            d.close()


def test_a_remote_prefill_is_served_like_a_local_one():
    """Rows prefilled by a `PrefillEngine` (whole, padded to its group),
    exported and admitted as a handoff: the tokens of a local
    admission by chunks, which are the tokens `generate()` gives."""
    pre = PrefillEngine(_gen(TOY, 2))
    single = _gen(TOY, 1)
    with _gen(TOY, 2).serving_decoder() as dec:
        for p in _prompts([26, 37], seed=8):
            want = dec.submit(p, 9).result(120.0)
            got = dec.submit(p, 9, handoff=pre.prefill(p)).result(120.0)
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(
                got, single.generate(p[None], 9)[0])
        assert dec.stats()["imported"] == 2
        assert dec.stats()["prefills"] == 2


# -- what is refused, and what the scopes carry -------------------------------------

@pytest.mark.parametrize("bad, match", [
    (dict(mla=dict(q_lora_rank=24)), "mla=dict"),
    (dict(pos_encoding="none"), "pos_encoding must be 'rope'"),
    (dict(layer_kinds=["attention", "mlp"] * 3), "no layer_kinds entry"),
    (dict(quantize_kv=True), "kv_quantize needs at least one attention"),
    (dict(diffusion=dict(block_length=4, mask_id=0)), "diffusion"),
])
def test_spellings_that_disagree_are_refused(bad, match):
    args = dict(model.generator_args(TOY), **bad)
    if "layer_kinds" in bad:
        args.update(num_experts=0)
        for k in ("experts_per_token", "expert_hidden", "norm_topk_prob",
                  "expert_scoring", "routed_scaling_factor",
                  "shared_expert_hidden", "experts_held"):
            args.pop(k)
    with pytest.raises(ValueError, match=match):
        Generator(ref.make_params(TOY, SEED, "float32"), V, T,
                  batch_size=1, **args)


def test_speculation_over_latent_rows_is_refused():
    gen = _gen(TOY, 2)
    with pytest.raises(ValueError, match="latent"):
        gen.serving_decoder(draft=_gen(TOY, 2))
    with pytest.raises(ValueError, match="layer_kinds"):
        gen.truncated_draft(num_layers=1)


def test_the_lowered_programs_carry_the_four_scopes():
    gen = _gen(TOY, 2)
    args = dict(gen._params, data=jnp.zeros((2, CHUNK), jnp.float32),
                positions=jnp.arange(CHUNK, dtype=jnp.float32),
                cache_pos=jnp.zeros((1,), jnp.float32))
    chunk = gen._step_fn.lower(args, gen._fresh_aux(),
                               jax.random.PRNGKey(0))
    with gen.serving_decoder() as dec:
        args = dict(gen._params, data=jnp.zeros((2, 1), jnp.float32),
                    positions=jnp.zeros((2, 1), jnp.float32),
                    cache_pos=jnp.zeros((2,), jnp.float32))
        step = dec._step_fn.lower(args, dec._aux, dec._rng0)
    for lowered in (chunk, step):
        text = lowered.as_text(debug_info=True)
        for scope in ("/mla.project/", "/mla.keys/dsa.index/",
                      "/mla.keys/mla.attend/", "/dsa.index/",
                      "/dsa.select/", "/mla.attend/", "/moe.experts/"):
            assert scope in text, scope
        # what runs over the cached rows sits in loops over column
        # blocks, each scope still a whole part of the stack
        for loop in ("/mla.keys/cond/branch_0_fun/dsa.index/while/body",
                     "/mla.keys/cond/branch_0_fun/dsa.select/while/body",
                     "/mla.keys/mla.attend/while/body"):
            assert loop in text, loop
        # the branch of a forward no deeper than the keys kept holds
        # no indexer and no selection
        kept_all = [line for line in text.splitlines()
                    if "/mla.keys/cond/branch_1_fun/" in line]
        assert kept_all and not [x for x in kept_all if "dsa." in x]
        # exact: a threshold found bit by bit, never an approximate
        # top-k (the experts' router sorts; the selection does not)
        assert "approx" not in text.lower()
        assert not [line for line in text.splitlines()
                    if "sort" in line.lower() and "dsa." in line]
        # one program: no branch by column count, one body a loop
        assert "branch_2_fun" not in text


def test_the_symbol_binds_one_dict_and_two_states_a_mixer():
    args = model.generator_args(TOY)
    assert set(args["mla"]) == set(mla.MLA_SIZES)
    assert args["layer_kinds"] == ["mla", "mlp", "mla", "experts",
                                   "mla", "experts"]
    sym = transformer.get_decode_symbol(V, T, num_layers=6, **args)
    assert sym.list_auxiliary_states() == [
        "layer%d_mla_%s_cache" % (i, k) for i in (0, 2, 4)
        for k in ("latent", "index")]
    twin = transformer.get_decode_symbol(V, T, num_layers=6,
                                         per_row_pos=True,
                                         moe_stats=True, **args)
    assert twin.list_arguments() == sym.list_arguments()
    assert len(twin.list_outputs()) == 3       # logits, experts, keys
    shapes, _, aux = sym.infer_shape(data=(2, 5), positions=(5,),
                                     cache_pos=(1,))
    by_name = dict(zip(sym.list_arguments(), shapes))
    assert by_name["layer0_mla_kv_b_weight"] == (4 * 20, 16)
    assert by_name["layer2_mla_index_q_weight"] == (3 * 8, 24)
    assert aux[:2] == [(2, T, 20), (2, T, 8)]


# -- (g) the configuration's counts ----------------------------------------------------

def test_the_configuration_file_s_count_and_bytes():
    """The published widths through the same `sizes` the builder
    reads: the issue's arithmetic, to the parameter."""
    s = ref.sizes(PUBLISHED)
    assert (s["dim"], s["heads"], s["nope"], s["rope"], s["v_head"],
            s["q_rank"], s["kv_rank"]) == (6144, 64, 192, 64, 256, 2048,
                                           512)
    assert (s["index_heads"], s["index_head"], s["index_topk"]) == \
        (32, 128, 2048)
    assert s["kinds"] == ("mla", "mlp") + ("mla", "experts") * 5
    mixer = 6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 64 * 448 + \
        16384 * 6144 + 2048 + 512
    indexer = 2048 * 4096 + 6144 * 128 + 32 * 6144 + 256
    assert round(mixer / 1e6, 3) == 165.022
    assert round(indexer / 1e6, 3) == 9.372
    expert = 3 * 6144 * 2048
    dense = mixer + indexer + 2 * 6144 + 3 * 6144 * 12288
    layer = mixer + indexer + 2 * 6144 + 6144 * 256 + 256 + 17 * expert
    assert round(dense / 1e6, 3) == 400.899
    assert round(layer / 1e6, 2) == 817.71
    total = dense + 5 * layer + 2 * 19360 * 6144 + 6144
    assert ops.param_count(PUBLISHED) == total
    assert round(total / 1e6, 2) == 4727.34
    assert round(ops.weight_bytes(PUBLISHED) / 1e9, 3) == 9.455
    per_slot = ops.state_bytes_per_slot(PUBLISHED, {"max_len": 16896})
    assert per_slot == {"latent_rows": 6 * 16896 * 576 * 2,
                        "index_rows": 6 * 16896 * 128 * 2}
    assert sum(per_slot.values()) == 16896 * 6 * 1408
    assert round(sum(per_slot.values()) / 1e6, 1) == 142.7


# -- the other families' programs ------------------------------------------------------

# `tests/_toy_programs.py` on the parent commit (`cd <parent checkout>
# && PYTHONPATH=. python <this tree>/tests/_toy_programs.py`, jax 0.9.0
# on the CPU): what this PR adds must leave the other six families'
# programs as they were
PARENT = {
    "opt.generator_step": "6e8cb9a964074d8b",
    "opt.decode_step": "27395e8a0e2b21e9",
    "granite.generator_step": "f9ab301df17462bc",
    "granite.decode_step": "087dca3522e08343",
    "nemotron.generator_step": "0eb20743b7af830c",
    "nemotron.decode_step": "4668afad534e8762",
    "lfm2.generator_step": "ad325a80fb2081fd",
    "lfm2.decode_step": "b9936d58ffabeddd",
    "cohere2.generator_step": "6da1eedfa32abbed",
    "cohere2.decode_step": "1503175043bdac12",
    "sdar.block_step": "0698336f5a5d55c1",
}


@pytest.fixture(scope="module")
def hashes():
    return _toy_programs.hashes()


@pytest.mark.parametrize("program", sorted(PARENT))
def test_the_other_families_programs_hash_as_on_the_parent(hashes,
                                                           program):
    assert hashes[program] == PARENT[program]
