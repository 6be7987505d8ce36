"""What a Cohere2-MoE stack forces (ISSUE 42), at toy widths on the CPU:
attention that differs by layer (a window, a cache kind and a position
kind each), a sliding layer's CIRCULAR rows beside a full layer's
`max_len` rows in one slot pool at per-row depth, prompts fed by chunks
past the window and past the buffer, the parallel block on one gain-only
LayerNorm, averaged shared experts as one weighted expert, the chip's
share of the routed experts; and all of it through Generator ->
ContinuousDecoder against the benchmark's plain reference on logits.
Toy stack: sliding, sliding, sliding, full; window 8, chunk 4, so a
sliding layer's buffer is 16 rows (8 + 4 - 1, by 8)."""
import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import _toy_programs
from cellbench.models import cohere2_moe as model
from cellbench.models.opt import served_logits
from cellbench.ops import cohere2_moe as ops
from cellbench.reference import cohere2_moe as ref
from mxnet_tpu import config, telemetry
from mxnet_tpu.generation import Generator
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import attention
from mxnet_tpu.parallel.moe import routed_experts
from mxnet_tpu.serve import SessionEvacuated
from mxnet_tpu.serve.decode import _merge_program

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, T, SEED, WINDOW, CHUNK, RING = 97, 48, 11, 8, 4, 16
with open(os.path.join(ROOT, "cellbench", "configs",
                       "command-a-plus-05-2026.json")) as _f:
    PUBLISHED = json.load(_f)
TOY = dict(PUBLISHED)
TOY.update(hidden_size=32, num_attention_heads=8, num_key_value_heads=2,
           head_dim=8, intermediate_size=16, num_experts=4,
           router_outputs=8, routed_experts_first=2,
           num_experts_per_tok=3, num_shared_experts=2, vocab_size=V,
           sliding_window=WINDOW, max_position_embeddings=64,
           initializer_range=0.3, compute_dtype="float32")
KINDS = {"kv_rows", "kv_window"}
# float32 program against float32 reference: rounding of sums of a few
# dozen terms through four layers (sound runs read 1e-6 to 1e-5 of the
# logits' spread). A program that computed in bfloat16 reads 1e-2 and
# more: test_bfloat16_in_float32_s_place_fails
TOL = 2e-4


@pytest.fixture(autouse=True)
def _chunked():
    """The chunk a prompt is fed by is the program's own knob; the
    generator sizes its circular buffers by it."""
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


# -- (b) the circular op at one depth a row ------------------------------------

def _band(B, H, Hkv, D, steps, window, ring, long=40, seed=0):
    """The same projections through `cached_attention` under a band
    mask over `long` rows and through the circular op over `ring`
    rows; `steps`: [(Tnew, pos)] with pos () or (B,)."""
    rng = np.random.default_rng(seed)
    full = [jnp.zeros((B, long, Hkv * D))] * 2
    roll = [jnp.zeros((B, ring, Hkv * D))] * 2
    worst = 0.0
    for tn, pos in steps:
        q, k, v = (jnp.asarray(rng.standard_normal((B, h, tn, D)),
                               jnp.float32) for h in (H, Hkv, Hkv))
        pos = jnp.asarray(pos, jnp.int32)
        a, *full = attention.cached_attention(q, k, v, *full, pos,
                                              window=window)
        b, *roll = attention.rolling_cached_attention(q, k, v, *roll,
                                                      pos, window)
        worst = max(worst, float(jnp.abs(a - b).max()))
    return worst


@pytest.mark.parametrize("chunk", [1, 3, 4])
def test_the_circular_op_is_the_band_mask_at_one_depth_a_row(chunk):
    """Chunks at one shared offset, then one-token steps with every
    row at its own depth (one of them admitted later, into rows that
    have wrapped): what the circular buffer of window + chunk - 1 rows
    gives is what a band mask over the whole context gives."""
    ring = WINDOW + chunk - 1
    steps = [(chunk, [p]) for p in range(0, 6 * chunk, chunk)]
    depth = np.array([6 * chunk, 6 * chunk, 6 * chunk])
    for t in range(14):
        steps.append((1, depth.copy()))
        depth += 1
    assert depth.max() > 2 * ring
    assert _band(3, 4, 2, 8, steps, WINDOW, ring) < 1e-5


def test_rows_at_unequal_depths_step_in_one_call():
    """Depths that differ by more than the buffer, in one step."""
    steps = [(1, np.array([p, p + 5, p + 23])) for p in range(16)]
    # each row's history starts at its own first position: the band
    # over the long cache sees the same (unwritten slots never pass
    # either mask: a slot's position is read from the row's depth)
    rng = np.random.default_rng(1)
    B, H, Hkv, D = 3, 4, 2, 8
    roll = [jnp.zeros((B, WINDOW, Hkv * D))] * 2
    lone = [[jnp.zeros((1, WINDOW, Hkv * D))] * 2 for _ in range(B)]
    for _tn, pos in steps:
        q, k, v = (jnp.asarray(rng.standard_normal((B, h, 1, D)),
                               jnp.float32) for h in (H, Hkv, Hkv))
        out, *roll = attention.rolling_cached_attention(
            q, k, v, *roll, jnp.asarray(pos, jnp.int32), WINDOW)
        for b in range(B):
            one, *lone[b] = attention.rolling_cached_attention(
                q[b:b + 1], k[b:b + 1], v[b:b + 1], *lone[b],
                jnp.asarray([pos[b]], jnp.int32), WINDOW)
            np.testing.assert_allclose(out[b], one[0], rtol=1e-6,
                                       atol=1e-6)


@pytest.mark.parametrize("ring,tn", [(11, 4), (7, 7), (9, 5), (16, 4)])
def test_a_chunk_that_wraps_is_written_where_a_loop_writes_it(ring, tn):
    rng = np.random.default_rng(ring)
    cache = rng.standard_normal((2, ring, 6)).astype(np.float32)
    new = rng.standard_normal((2, tn, 6)).astype(np.float32)
    write = jax.jit(attention._write_ring)
    for pos in list(range(0, 3 * ring)) + [np.array([5, 3 * ring + 2])]:
        want = cache.copy()
        for b, p in enumerate(np.broadcast_to(pos, (2,))):
            for r in range(tn):
                want[b, (p + r) % ring] = new[b, r]
        np.testing.assert_array_equal(
            write(cache, new, jnp.asarray(pos, jnp.int32)), want)
    with pytest.raises(ValueError, match="cannot take"):
        attention._write_ring(jnp.zeros((1, 3, 2)), jnp.zeros((1, 4, 2)),
                              jnp.int32(0))


# -- (c) interleaved rotary pairs by a permutation of the head's rows ------------

def test_the_loader_s_permutation_makes_half_split_pairs_interleaved():
    """The reference rotates channels 2i and 2i + 1 together; the
    program's `rope` rotates i and i + hd/2. With q's and k's channels
    in the order evens, odds the program's scores are the reference's,
    exactly up to the order of one sum."""
    s = ref.sizes(TOY)
    rng = np.random.default_rng(2)
    q, k = (rng.standard_normal((2, 9, 3, s["head"])).astype(np.float32)
            for _ in range(2))
    want = np.einsum(
        "nqhd,nkhd->nhqk", *(np.asarray(ref._rope_interleaved(
            jnp.asarray(a), s["theta"])) for a in (q, k)))
    order = ref._rotary_rows(s)
    assert sorted(order) == list(range(s["head"]))
    pos = jnp.arange(9, dtype=jnp.float32)
    got = np.einsum("nhqd,nhkd->nhqk", *(np.asarray(attention.rope(
        jnp.asarray(a[..., order]).transpose(0, 2, 1, 3), pos,
        base=s["theta"])) for a in (q, k)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and make_params hands the program exactly those rows, in the
    # sliding layers only
    key = ref.base_key(SEED)
    drawn = ref._layer_tensors(key, 0, s, jnp.float32)["qkv_weight"]
    params = ref.make_params(TOY, SEED, "float32")
    rot = (s["heads"] + s["kv_heads"]) * s["head"]
    np.testing.assert_array_equal(
        np.asarray(params["layer0_qkv_weight"])[:rot].reshape(
            -1, s["head"], s["dim"]),
        np.asarray(drawn)[:rot].reshape(-1, s["head"], s["dim"])[:, order])
    np.testing.assert_array_equal(params["layer0_qkv_weight"][rot:],
                                  drawn[rot:])
    np.testing.assert_array_equal(
        params["layer3_qkv_weight"],
        ref._layer_tensors(key, 3, s, jnp.float32)["qkv_weight"])


# -- (d) the parallel block, the gain-only norm, averaged shared experts -----------

def test_averaged_shared_experts_are_one_expert_with_scaled_downs():
    """m experts of width h, their outputs averaged, against ONE gated
    expert of width m h (gates and ups side by side, the downs stacked
    and times 1/m, as the loader lays them out): the identity is exact
    up to the order of one sum."""
    rng = np.random.default_rng(5)
    d, h, m, n = 16, 8, 4, 7
    x = rng.standard_normal((n, d)).astype(np.float32)
    w1 = rng.standard_normal((m, d, 2 * h)).astype(np.float32) * 0.3
    w2 = rng.standard_normal((m, h, d)).astype(np.float32) * 0.3
    want = np.mean([np.asarray(ref._gated(jnp.asarray(x), w1[j], w2[j]))
                    for j in range(m)], axis=0)
    one1 = np.concatenate([w1[j][:, lo:lo + h] for lo in (0, h)
                           for j in range(m)], axis=1)
    one2 = (w2 / m).reshape(m * h, d)
    gate = np.zeros((d, 2), np.float32)         # a router nobody weighs
    routed = np.zeros((2, d, 2 * h), np.float32)
    y, _ = routed_experts(
        jnp.asarray(x), jnp.asarray(gate), jnp.asarray(routed),
        jnp.zeros((2, h, d)), top_k=1, act="gated_silu",
        shared=(jnp.asarray(one1), jnp.asarray(one2)))
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)


def test_the_parallel_block_has_one_gain_only_norm_a_layer():
    gen = _gen(TOY, 1)
    names = set(gen._sym.list_arguments())
    assert "layer0_ln1_gamma" in names and "ln_f_gamma" in names
    assert not [n for n in names if n.endswith("_beta") or "ln2" in n]
    assert "lm_head_weight" not in names           # tied to the table
    assert gen._params["layer0_shared_w1_weight"].shape == (32, 2 * 2 * 16)
    assert gen._params["layer0_experts_w1_weight"].shape == (4, 32, 32)
    with pytest.raises(ValueError, match="parallel_block"):
        transformer.get_decode_symbol(
            V, T, layer_kinds=["attention", "mlp"], num_layers=2,
            parallel_block=True)
    # the gain-only norm is LayerNorm without its beta
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 8)),
                    jnp.float32)
    g = jnp.full((8,), 1.5)
    from mxnet_tpu.ops import nn
    np.testing.assert_array_equal(
        nn._layer_norm(x, g), nn._layer_norm(x, g, jnp.zeros((8,))))
    np.testing.assert_allclose(nn._layer_norm(x, g),
                               ref._ln(x, g, 1e-5), rtol=1e-5, atol=1e-6)


# -- (e) the shares add up -----------------------------------------------------------

def test_eight_shares_with_attention_and_shared_once_are_the_whole_layer():
    """What ties the share to the model: the routed parts that the
    chips of a layer compute (here 4 chips of 2 experts and 2 of 4:
    any partition), with what every chip computes alike (attention,
    the shared experts) counted once, add up to the uncut reference's
    layer."""
    uncut = dict(TOY, num_experts=8, routed_experts_first=0)
    s = ref.sizes(uncut)
    key = ref.base_key(SEED)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 12, 32)),
                    jnp.float32)
    p = ref._layer_tensors(key, 1, s, jnp.float32)

    def expert_of(first):
        def expert(stream, e):
            at = e + (first if stream == ref._ROUTED else 0)
            return ref._expert_tensors(key, 1, stream, at, s, jnp.float32)
        return expert

    with jax.default_matmul_precision("highest"):
        whole = ref._layer(x, p, expert_of(0), True, s)
        a = ref._ln(x, p["ln1_gamma"], s["eps"])
        alike = x + ref._attention(a, p, True, s)
        total = np.asarray(alike, np.float64)
        none = dict(s, shared=0)
        for n, (first, held) in enumerate(
                [(0, 2), (2, 2), (4, 1), (5, 1), (6, 2)]):
            share = dict(s if n == 0 else none, first=first, held=held)
            if n:
                # a share without shared experts: the routed sum alone
                w = ref._chosen(a.reshape(-1, 32), p, share)[
                    :, first:first + held]
                part = sum(w[:, i:i + 1] * ref._gated(
                    a.reshape(-1, 32), *expert_of(first)(ref._ROUTED, i))
                    for i in range(held)).reshape(x.shape)
            else:
                part = ref._ffn(a, p, expert_of(first), share)
            total = total + np.asarray(part, np.float64)
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


def test_the_program_s_share_is_the_reference_s_share():
    """One layer of the program over experts 2-5 of 8 against the
    reference given the same share, and against the uncut layer less
    what the absent experts add."""
    prompts = _prompts([9])
    with _gen(TOY, 1).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 3)
        stats = dec.stats()
    want = list(ref.served_logits(TOY, SEED, [(9, rows[0])], "float32"))
    assert _error(logits[0], want[0]) < TOL
    assert 0 < stats["moe_pairs_here"] < stats["moe_assignments"]
    uncut = dict(TOY, num_experts=8, routed_experts_first=0)
    other = list(ref.served_logits(uncut, SEED, [(9, rows[0])],
                                   "float32"))
    assert _error(logits[0], other[0]) > 100 * TOL


# -- (a) chunks, then steps, through the pool: the reference's one forward ---------

@pytest.fixture(scope="module")
def pool():
    """Six requests through a pool of two slots: prompts longer than
    the chunk (4), than the window (8) and, all but one, than the
    circular buffer (16 rows), and one short enough to prefill whole;
    rows are admitted while others are mid-flight at other depths,
    into slots whose buffers have wrapped, and step past the buffer
    again."""
    config.set_override("MXNET_PREFILL_CHUNK", CHUNK)
    prompts = _prompts([21, 13, 30, 3, 18, 26])
    with _gen(TOY, 2).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 14)
        jit = telemetry.gauge("serve.decode.jit_cache_size").value
        return prompts, rows, logits, dec.stats(), dec.describe(1e9), jit


def test_chunks_then_steps_through_the_pool_match_one_forward(pool):
    prompts, rows, logits, stats, _text, jit = pool
    assert jit == 1
    assert max(len(p) for p in prompts) + 14 > 2 * RING
    want = ref.served_logits(TOY, SEED, [(len(p), r) for p, r in
                                         zip(prompts, rows)], "float32")
    for got, exp in zip(logits, want):
        assert got.shape == exp.shape == (14, V)
        assert _error(got, exp) < TOL
    # five prompts went by chunks, one whole
    assert stats["chunks"] == sum(-(-len(p) // CHUNK) for p in prompts
                                  if len(p) > CHUNK)
    # each at the bottom rung of the pool of two, which is the one row
    # a chunk is; the short prompt whole, alone in its group, likewise
    assert stats["chunk_rows"] == stats["chunks"]
    assert stats["prefill_rows"] == stats["chunks"] + 1
    assert stats["prefills"] == 6


def test_each_row_of_the_pool_equals_its_lone_run(pool):
    """Whatever its slot held before and whatever its neighbour's
    depth, a row is the row its prompt gives alone, prefilled whole
    into a buffer wide enough for that."""
    prompts, rows, _logits, stats, _text, _jit = pool
    assert stats["admit_rounds"] >= 3
    config.set_override("MXNET_PREFILL_CHUNK", 0)
    one = _gen(TOY, 1)
    assert one._rings["layer0_"] == (T, WINDOW)
    for p, row in zip(prompts, rows):
        np.testing.assert_array_equal(
            row, np.asarray(one.generate(p[None], 14))[0])


def test_stats_and_the_sizing_report_by_kind(pool):
    _prompts_, _rows, _logits, stats, text, _jit = pool
    assert stats["bytes_per_slot"] == {
        "kv_rows": 2 * T * 16 * 4, "kv_window": 3 * 2 * RING * 16 * 4}
    assert "rolling KV rows 16x16 of window 8 (float32, circular" in text
    assert "KV rows 48x16 (float32)" in text
    assert telemetry.gauge(
        "serve.decode.kv_window_bytes_per_slot").value == \
        3 * 2 * RING * 16 * 4
    assert stats["moe_assignments"] == stats["steps"] * 4 * 2 * 3


def test_bfloat16_in_float32_s_place_fails():
    """The comparison is tight enough to see a lower precision."""
    prompts = _prompts([21])
    with _gen(TOY, 1, dtype="bfloat16").serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 6)
    want = list(ref.served_logits(TOY, SEED, [(21, rows[0])], "float32"))
    assert _error(logits[0], want[0]) > 20 * TOL


@pytest.mark.parametrize("left_out", ["window", "rotation", "full_rope"])
def test_each_piece_left_out_fails_the_comparison(left_out):
    """A stack with one of its per-layer pieces wrong is another
    model: no window in the sliding layers, no rotation in them, a
    rotation in the full layer."""
    args = model.generator_args(TOY)
    layers = [dict(a) for a in args["attention_layers"]]
    for a in layers:
        if left_out == "window" and a["cache"] == "rolling":
            a.update(window=0, cache="full")
        if left_out == "rotation" and a["cache"] == "rolling":
            a["pos"] = "none"
        if left_out == "full_rope" and a["cache"] == "full":
            a["pos"] = "rope"
    prompts = _prompts([21])
    with _gen(TOY, 1, attention_layers=layers).serving_decoder() as dec:
        rows, logits = served_logits(dec, prompts, 6)
    want = list(ref.served_logits(TOY, SEED, [(21, rows[0])], "float32"))
    assert _error(logits[0], want[0]) > 50 * TOL


# -- (f) the mixed pytree through merge, export, import, evacuation ------------------

def _prefilled(gen, seed, length=21):
    """Every row prefilled by chunks past the buffer: (aux, depth)."""
    toks = np.stack(_prompts([length] * gen.batch_size, seed=seed))
    aux = gen._fresh_aux()
    for lo in range(0, length, CHUNK):
        _logits, aux = gen._forward(aux, toks[:, lo:lo + CHUNK], lo)
    return aux


def test_fresh_aux_and_cache_merge_carry_two_row_counts():
    gen = _gen(TOY, 3)
    fresh = gen._fresh_aux()
    assert {("kv_window" if gen._ring_of(n) else gen._aux_kind(n))
            for n in fresh} == KINDS
    for name, v in fresh.items():
        shape, dtype = gen._aux_spec(name)
        assert v.shape == shape and v.dtype == dtype
        assert not np.asarray(v).any()
    assert fresh["layer0_attn_k_cache"].shape == (3, RING, 16)
    assert fresh["layer3_attn_k_cache"].shape == (3, T, 16)
    assert gen._fresh_aux(1)["layer2_attn_v_cache"].shape == (1, RING, 16)
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


def test_export_import_bit_preserves_a_wrapped_window():
    gen = _gen(TOY, 3)
    aux = _prefilled(gen, 4)
    blob = gen.export_kv_rows(aux, 1, 21)
    rows = blob["rows"]
    # the full layer ships its 21 positions, a sliding layer its 16
    # slots as they lie; before anything has wrapped, the first 9
    assert rows["layer3_attn_k_cache"].shape == (2, 21, 8)
    assert rows["layer0_attn_k_cache"].shape == (2, RING, 8)
    early = gen.export_kv_rows(aux, 1, 9)["rows"]
    assert early["layer0_attn_k_cache"].shape == (2, 9, 8)
    with gen.serving_decoder() as dec:
        dec.import_kv_rows(2, blob)
        for name, arr in rows.items():
            n = arr.shape[1]
            got = np.asarray(dec._aux[name])[2, :n].reshape(
                n, arr.shape[0], -1).swapaxes(0, 1)
            np.testing.assert_array_equal(got, arr)
        bad = dict(blob, rows=dict(
            rows, layer0_attn_k_cache=rows["layer0_attn_k_cache"][:, :9]))
        with pytest.raises(ValueError, match="layer0_attn_k_cache"):
            dec.import_kv_rows(0, bad)


def test_evacuate_then_resume_continues_bit_for_bit_past_the_buffer():
    """A session evacuated mid-decode, its windows wrapped, and
    resumed on a second pool emits the tokens an undisturbed run
    emits; the resumed row steps past the buffer once more."""
    p = _prompts([22], seed=6)[0]
    d0 = _gen(TOY, 2).serving_decoder()
    d1 = _gen(TOY, 2).serving_decoder()
    d2 = _gen(TOY, 2).serving_decoder()
    try:
        want = d0.submit(p, 24).result(120.0)
        # the decode thread waits, three tokens out, until the
        # evacuation is queued: no race with the clock
        three = threading.Event()

        def hold(req, _row):
            if len(req.emitted) >= 3 and not three.is_set():
                three.set()
                end = time.time() + 60.0
                while not d1._evac_waiters and time.time() < end:
                    time.sleep(0.0005)

        d1.on_logits = hold
        fut = d1.submit(p, 24)
        assert three.wait(120.0), "3 emitted tokens"
        assert d1.evacuate() == 1
        with pytest.raises(SessionEvacuated) as ei:
            fut.result(10.0)
        state = ei.value.state
        assert state["kv_blob"]["pos"] > RING
        got = d2.submit(p, 24, resume=state).result(120.0)
        np.testing.assert_array_equal(got, want)
        assert d2.stats()["resumed"] == 1
        assert d2.stats()["prefills"] == 0
    finally:
        for d in (d0, d1, d2):
            d.close()


def test_a_handoff_of_a_wrapped_row_is_served_like_a_local_prefill():
    """Rows prefilled elsewhere (by chunks), exported, and admitted as
    a handoff: the tokens of a local admission."""
    p = _prompts([26], seed=8)[0]
    gen = _gen(TOY, 2)
    toks = np.stack([p, p])
    aux = gen._fresh_aux()
    for lo in range(0, 26, CHUNK):
        logits, aux = gen._forward(aux, toks[:, lo:lo + CHUNK], lo)
    first = int(np.argmax(np.asarray(logits[0, -1], np.float32)))
    blob = gen.export_kv_rows(aux, 0, 26)
    with gen.serving_decoder() as dec:
        want = dec.submit(p, 9).result(120.0)
        got = dec.submit(p, 9, handoff={"first_token": first,
                                        "kv_blob": blob,
                                        "pos": 26}).result(120.0)
        np.testing.assert_array_equal(got, want)
        assert dec.stats()["imported"] == 1


# -- what is refused still, and what the spans and scopes carry ---------------------

def test_what_a_circular_buffer_cannot_take_is_refused_at_the_door():
    gen = _gen(TOY, 2)
    assert gen.ring_feed == RING - WINDOW + 1 and gen._wraps
    with pytest.raises(ValueError, match="speculative"):
        gen.serving_decoder(draft=_gen(TOY, 2))
    with pytest.raises(ValueError, match="rolling"):
        gen.truncated_draft(num_layers=1)
    # a prompt fed whole that would wrap: generate() refuses it, a
    # chunk wider than the buffer was sized for is refused at submit
    with pytest.raises(ValueError, match="circular cache"):
        gen.generate(np.zeros((2, 20), np.int64), 2)
    gen.generate(np.zeros((2, 9), np.int64), 12)       # fits, steps wrap
    with gen.serving_decoder() as dec:
        config.set_override("MXNET_PREFILL_CHUNK", 12)
        with pytest.raises(ValueError, match="MXNET_PREFILL_CHUNK"):
            dec.submit(np.zeros(30, np.int64), 2)
        dec.submit(np.zeros(12, np.int64), 2).result(60.0)   # no wrap
    legacy = Generator(
        {k: v for k, v in gen._params.items()}, V, T, batch_size=1,
        **dict(model.generator_args(TOY), attention_layers=None,
               attention_window=WINDOW, rolling_cache=True))
    with pytest.raises(ValueError, match="attention_layers"):
        legacy.serving_decoder()


@pytest.mark.parametrize("bad,match", [
    (dict(attention_layers=[dict(cache="rolling", window=8, rows=16)]),
     "names each attention layer"),
    (dict(attention_layers=[dict(cache="rolling", window=8, rows=4)] * 4),
     "rows >= window"),
    (dict(attention_layers=[dict(cache="full", rows=4)] * 4), "capacity"),
    (dict(attention_layers=[dict(pos="alibi")] * 4), "attention_layers"),
    (dict(attention_layers=[dict(window=4)] * 4, rolling_cache=True,
          attention_window=4), "no rolling_cache"),
    (dict(attention_layers=[dict(cache="rolling", window=8, rows=8)] * 4,
          kv_quantize=True), "kv_quantize"),
    (dict(attention_layers=[dict(pos="rope")] * 4,
          pos_encoding="none"), "pos_encoding='rope'"),
])
def test_spellings_that_disagree_are_refused(bad, match):
    args = dict(num_layers=4, pos_encoding="rope")
    args.update(bad)
    with pytest.raises(ValueError, match=match):
        transformer.get_decode_symbol(V, T, **args)


def test_rolling_cache_no_longer_refuses_one_depth_a_row():
    sym = transformer.get_decode_symbol(
        V, 16, num_layers=1, pos_encoding="rope", attention_window=8,
        rolling_cache=True, per_row_pos=True)
    assert "layer0_attn_k_cache" in sym.list_auxiliary_states()


def test_the_lowered_programs_carry_the_two_kinds_names():
    gen = _gen(TOY, 2)

    def names(fn, args, aux):
        return fn.lower(args, aux, jax.random.PRNGKey(0)).as_text(
            debug_info=True)

    chunk = names(gen._step_fn, dict(
        gen._params, data=jnp.zeros((2, CHUNK), jnp.float32),
        positions=jnp.arange(CHUNK, dtype=jnp.float32),
        cache_pos=jnp.zeros((1,), jnp.float32)), gen._fresh_aux())
    with gen.serving_decoder() as dec:
        step = names(dec._step_fn, dict(
            gen._params, data=jnp.zeros((2, 1), jnp.float32),
            positions=jnp.zeros((2, 1), jnp.float32),
            cache_pos=jnp.zeros((2,), jnp.float32)), dec._aux)
    for text in (chunk, step):
        assert "layer0_attn/op._contrib_RollingCachedAttention/" \
            "attn.window" in text
        assert "layer3_attn/op._contrib_CachedAttention/attn.full" in text
        assert "layer3_attn/op._contrib_RollingCached" not in text
        assert "moe.shared" in text and "moe.experts" in text


@pytest.mark.parametrize("slots", [3, 4, 8])
def test_the_chunk_span_says_how_many_rows_ran(monkeypatch, slots):
    """A chunked prompt's forwards run the pool's bottom rung (one row
    under a mesh-less pool of any width), on a state of that many rows,
    and the span and the counters say so; the row served is the row the
    prompt gives alone, prefilled whole."""
    from mxnet_tpu import trace
    seen = []
    real = trace.phase

    def phase(name, **kw):
        if name == "serve.decode.prefill_chunk":
            seen.append(kw)
        return real(name, **kw)

    monkeypatch.setattr(trace, "phase", phase)
    prompt = _prompts([10])[0]
    with _gen(TOY, slots).serving_decoder() as dec:
        assert dec._rungs == [1, slots]
        row = dec.submit(prompt, 2).result(60.0)
        st = dec.stats()
        assert dec._gen._step_fn._cache_size() == 2   # (1, 4), (1, 2)
    assert (st["chunks"], st["chunk_rows"], st["prefill_rows"]) == \
        (3, 3, 3)
    assert [(k["lo"], k["hi"], k["run"]) for k in seen] == \
        [(0, 4, 1), (4, 8, 1), (8, 10, 1)]
    config.set_override("MXNET_PREFILL_CHUNK", 0)
    np.testing.assert_array_equal(
        row, np.asarray(_gen(TOY, 1).generate(prompt[None], 2))[0])


# -- (g) the configuration's count, and the other families' programs ----------------

def test_the_configuration_file_s_count_and_bytes():
    """4 733 M parameters, 9.47 GB in bf16, as the issue reckons them
    and as `make_params` makes them (counted on shapes: nothing is
    drawn at the published size here)."""
    s = ref.sizes(PUBLISHED)
    shapes = jax.eval_shape(lambda: ref.make_params(PUBLISHED, 0))
    count = sum(int(np.prod(v.shape)) for n, v in shapes.items()
                if not n.endswith("_score_bias"))
    assert count == ops.param_count(PUBLISHED) == 4733292544
    assert round(ops.weight_bytes(PUBLISHED) / 1e9, 2) == 9.47
    assert "4 733.30 M = 9.47 GB" in PUBLISHED["deployment"] or \
        "4 733" in PUBLISHED["deployment"]
    assert shapes["layer0_experts_w1_weight"].shape == (16, 4096, 8192)
    assert shapes["layer0_shared_w1_weight"].shape == (4096, 32768)
    assert shapes["layer3_gate_weight"].shape == (4096, 128)
    assert shapes["tok_embed_weight"].shape == (32768, 4096)
    assert (s["layers"], s["held"], s["experts"], s["top_k"]) == \
        (4, 16, 128, 8)
    # the pool the cell states: 88 MB a slot, a third of it full rows
    gen_args = model.generator_args(PUBLISHED)
    assert [a["cache"] for a in gen_args["attention_layers"]] == \
        ["rolling"] * 3 + ["full"]
    assert [a["pos"] for a in gen_args["attention_layers"]] == \
        ["rope"] * 3 + ["none"]


# sha256 of the StableHLO text, first 16 digits, computed on the parent
# commit 3c49f8a (`cd <its checkout> && PYTHONPATH=. python
# <this tree>/tests/_toy_programs.py`, jax 0.9.0 on the CPU): what this
# PR adds must leave the other five families' programs as they were
PARENT = {
    "opt.generator_step": "6e8cb9a964074d8b",
    "opt.decode_step": "27395e8a0e2b21e9",
    "granite.generator_step": "f9ab301df17462bc",
    "granite.decode_step": "087dca3522e08343",
    "nemotron.generator_step": "0eb20743b7af830c",
    "nemotron.decode_step": "4668afad534e8762",
    "lfm2.generator_step": "ad325a80fb2081fd",
    "lfm2.decode_step": "b9936d58ffabeddd",
    "sdar.block_step": "0698336f5a5d55c1",
}


@pytest.fixture(scope="module")
def hashes():
    return _toy_programs.hashes()


@pytest.mark.parametrize("program", sorted(PARENT))
def test_the_other_families_programs_hash_as_on_the_parent(hashes,
                                                           program):
    assert hashes[program] == PARENT[program]
