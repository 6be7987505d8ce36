"""`_attend`'s prefill path bounds a map step's float32 scores to
`_SCORE_BYTES` (ISSUE 44): over the budget the map runs over (kv head,
block of rows) — batch rows first, then query heads of a group, then
rows inside a head — and every row still meets every column, so a
row's result is the row's result unsplit. A shape within the budget
lowers to the program it lowered to before. Toy sizes on the CPU, the
budget patched down so that the split engages."""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import attention
from mxnet_tpu.parallel import make_train_step

pytestmark = pytest.mark.serve

HKV, SPARE = 2, 24
# new rows a forward by the query heads that share a kv head: 256 rows
# of scores a kv head at least, so a block of `_MXU_ROWS` is a split
TN = {1: 256, 4: 256, 16: 16}


def _inputs(B, G, D, per_row, seed=0):
    rng = np.random.RandomState(seed)
    Tn = TN[G]
    C = Tn + SPARE
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.float32) for shape in (
        (B, G * HKV, Tn, D), (B, HKV, Tn, D), (B, HKV, Tn, D)))
    caches = [jnp.asarray(rng.randn(B, C, HKV * D), jnp.float32)
              for _ in range(2)]
    pos = rng.randint(1, SPARE, size=(B,) if per_row else (1,))
    return q, k, v, caches, jnp.asarray(pos, jnp.int32), Tn, C


def _forced(mp, rows, C):
    """The budget: `rows` rows of C float32 scores a map step."""
    mp.setattr(attention, "_SCORE_BYTES", rows * C * 4)


def _both(mp, call, rows, C):
    """`call()` unsplit and with the budget at `rows` rows: the two
    results, after checking that only the second one split."""
    before = attention.split_traces()
    whole = call()
    assert attention.split_traces() == before
    _forced(mp, rows, C)
    split = call()
    assert attention.split_traces() > before
    return whole, split


def _close(whole, split):
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(split)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("per_row", [False, True], ids=["shared", "rows"])
@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("G", [1, 4, 16])
def test_causal_rows_are_the_unsplit_rows(monkeypatch, G, D, B, per_row):
    """Blocks of `_MXU_ROWS` rows (of batch rows, of a group's query
    heads and of a head's rows, by the shape) against the whole kv
    head, through `cached_attention`'s causal mask."""
    q, k, v, caches, pos, Tn, C = _inputs(B, G, D, per_row)
    whole, split = _both(
        monkeypatch, lambda: attention.cached_attention(
            q, k, v, *caches, pos), attention._MXU_ROWS, C)
    _close(whole, split)
    Bb, Gb, Tb = attention._score_blocks(B, G, Tn, C)
    assert (Bb, Gb * Tb) == (1, attention._MXU_ROWS)


@pytest.mark.parametrize("mask", ["batch", "window", "block", "ring",
                                  "ring_rows", "int8", "int8_rows"])
def test_every_mask_and_the_int8_scales_are_cut_with_the_rows(
        monkeypatch, mask):
    """The batch split alone; the window and the block mask; the ring
    mask of `rolling_cached_attention` across a wrap, at a shared and
    at per-row depths; int8 caches with their scales."""
    G, D, B = 4, 64, 3
    q, k, v, caches, pos, Tn, C = _inputs(
        B, G, D, per_row=mask.endswith("_rows"), seed=3)
    rows = attention._MXU_ROWS
    if mask == "batch":
        rows = G * Tn                 # a whole kv head of one batch row
        call = lambda: attention.cached_attention(q, k, v, *caches, pos)
    elif mask == "window":
        call = lambda: attention.cached_attention(
            q, k, v, *caches, pos, window=40)
    elif mask == "block":
        call = lambda: attention.cached_attention(
            q, k, v, *caches, pos, block=8)
    elif mask.startswith("ring"):
        # past the buffer's end: the new rows wrap (C = window + Tn - 1
        # rounded up is what a generator sizes; here window 25)
        deep = pos + 3 * C - 7
        call = lambda: attention.rolling_cached_attention(
            q, k, v, *caches, deep, window=SPARE + 1)
    else:
        rng = np.random.RandomState(5)
        q8 = [jnp.asarray(rng.randint(-127, 128, (B, C, HKV * D)),
                          jnp.int8) for _ in range(2)]
        scales = [jnp.asarray(rng.rand(B, C, HKV) * 0.02 + 0.001,
                              jnp.float32) for _ in range(2)]
        call = lambda: attention.cached_attention_q8(
            q, k, v, *q8, *scales, pos)
    whole, split = _both(monkeypatch, call, rows, C)
    _close(whole, split)
    if mask == "batch":
        assert attention._score_blocks(B, G, Tn, C) == (1, G, Tn)


# (B, G, Tn, C): (Bb, Gb, Tb) at the budget the module has, from the
# configurations' heads and the traffic files' pools (ISSUE 44)
CELLS = {
    "command_a_full": ((1, 16, 256, 8448), (1, 8, 256)),
    "command_a_sliding": ((1, 16, 256, 4352), (1, 16, 256)),
    "opt_one_row": ((1, 1, 1024, 1536), (1, 1, 1024)),
    "opt_top_rung": ((8, 1, 1024, 1536), (8, 1, 1024)),
    "granite_top_rung": ((16, 4, 256, 768), (16, 4, 256)),
    "lfm2_top_rung": ((16, 4, 256, 1280), (8, 4, 256)),
    "nemotron_bottom_rung": ((4, 16, 256, 768), (4, 16, 256)),
    "nemotron_top_rung": ((32, 16, 256, 768), (4, 16, 256)),
    "sdar_bottom_rung": ((2, 8, 508, 1024), (2, 8, 508)),
    "sdar_top_rung": ((16, 8, 508, 1024), (4, 8, 508)),
    # one batch row over the budget whatever is cut: the smallest block
    "rows_inside_a_head": ((2, 2, 512, 1 << 18), (1, 1, 128)),
    "under_a_pass": ((1, 1, 96, 1 << 20), (1, 1, 96)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_blocks_are_read_from_the_shapes(cell):
    (B, G, Tn, C), want = CELLS[cell]
    Bb, Gb, Tb = got = attention._score_blocks(B, G, Tn, C)
    assert got == want
    assert B % Bb == 0 and G % Gb == 0 and Tn % Tb == 0
    assert Gb * Tb >= min(attention._MXU_ROWS, G * Tn)
    if got != (B, G, Tn):
        whole = B * G * Tn * C * 4
        assert whole > attention._SCORE_BYTES
        # batch rows go first; rows inside one only at one batch row
        assert Bb == 1 or (Gb, Tb) == (G, Tn)


# sha256 of the jaxpr's text, first 16 digits, on the parent commit
# e9b8a2a (jax 0.9.0 on the CPU): (B, H, Tn, D, C, Hkv, per-row pos)
PARENT = {
    "command_a_sliding": ((1, 128, 256, 128, 4352, 8, False),
                          "a5483c2bfce325c4"),
    "command_a_step": ((4, 128, 1, 128, 8448, 8, True),
                       "7700f24700c28260"),
    "opt_top_rung": ((8, 32, 1024, 64, 1536, 32, False),
                     "625978eb536c5865"),
    "opt_one_row": ((1, 32, 1024, 64, 1536, 32, False),
                    "051041fded1a29fe"),
    "granite_top_rung": ((16, 32, 256, 64, 768, 8, False),
                         "9a86038c27c73e1e"),
    "nemotron_bottom_rung": ((4, 32, 256, 128, 768, 2, False),
                             "03ab6cf406931147"),
    "toy_per_row": ((3, 8, 40, 128, 64, 2, True), "b738df89a8b97b62"),
}


def attend_jaxpr_sha(B, H, Tn, D, C, Hkv, per_row):
    def f(q, k, v, pos):
        return attention._attend(q, k, v,
                                 attention._causal(pos, Tn, C, 0),
                                 D ** -0.5)
    cache = jax.ShapeDtypeStruct((B, C, Hkv * D), jnp.bfloat16)
    text = str(jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((B, H, Tn, D), jnp.bfloat16), cache, cache,
        jax.ShapeDtypeStruct((B,) if per_row else (), jnp.int32)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("shape", sorted(PARENT))
def test_a_shape_within_the_budget_lowers_as_on_the_parent(shape):
    args, sha = PARENT[shape]
    before = attention.split_traces()
    assert attend_jaxpr_sha(*args) == sha
    assert attention.split_traces() == before


def test_the_full_layer_s_shape_splits_in_two():
    """The command-a cell's full layer, a chunk of 256 tokens over
    8 448 columns: two blocks of eight query heads a kv head."""
    B, H, Tn, D, C, Hkv, _ = PARENT["command_a_sliding"][0]
    before = attention.split_traces()
    attend_jaxpr_sha(B, H, Tn, D, 8448, Hkv, False)
    assert attention.split_traces() == before + 1


# -- the counter, through a toy pool ------------------------------------

V, T, PROMPT = 50, 320, 300


@pytest.fixture(scope="module")
def params():
    sym = transformer.get_symbol(V, 12, num_layers=1, num_heads=2, dim=32,
                                 max_len=T)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    return step.init_state(Xavier(), {"data": (2, 12),
                                      "softmax_label": (2, 12)})[0]


def _served(params, prompt):
    gen = Generator(params, V, T, num_layers=1, num_heads=2, dim=32,
                    batch_size=2)
    with gen.serving_decoder() as dec:
        row = dec.submit(prompt, 3).result(120.0)
        return np.asarray(row), dec.stats()


def test_stats_count_the_programs_that_split(monkeypatch, params):
    """A toy pool's programs are within the budget and count nothing;
    with the budget forced under a prompt's rows the prefill's program
    splits, `stats()` says so, and the row served is the same row."""
    prompt = np.random.RandomState(7).randint(1, V, (PROMPT,))
    row, stats = _served(params, prompt)
    assert stats["attend_split_programs"] == 0
    _forced(monkeypatch, PROMPT // 2, T)
    again, stats = _served(params, prompt)
    assert stats["attend_split_programs"] > 0
    np.testing.assert_array_equal(again, row)
