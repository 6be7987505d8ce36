"""Operations from shapes, against counts worked by hand at one small
shape each."""
import importlib

import pytest

from cellbench import run
from cellbench.ops import opt, resnet
from cellbench.reference import resnet as resnet_ref


def test_opt_flops_per_token_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "ffn_dim": 32,
           "num_hidden_layers": 3, "vocab_size": 100,
           "max_position_embeddings": 16}
    # a layer: qkv 3*8*8=192, proj 64, ffn 2*8*32=512 -> 768 MACs;
    # attention with 5 keys in view: scores 8*5 + values 8*5 = 80 MACs
    # head: 8*100 = 800 MACs
    macs = 3 * (768 + 80) + 800
    assert opt.forward_flops_per_token(cfg, 5) == 2 * macs
    # training a sequence of 4: mean context 2.5, three passes
    per_tok = 2 * (3 * (768 + 2 * 8 * 2.5) + 800)
    assert opt.train_flops_per_sample(cfg, {"seq_len": 4}) == \
        3 * 4 * per_tok


def test_resnet50_macs_by_hand():
    cfg = {"num_layers": 50, "image_size": 224, "num_classes": 1000}
    # stem: 64*3*7*7 at 112x112
    stem = 64 * 3 * 49 * 112 * 112
    # stage 1 at 56x56, 64 in (first unit) / 256 in, mid 64, out 256
    u1 = (64 * 64 + 64 * 64 * 9 + 256 * 64 + 256 * 64) * 56 * 56
    u = (64 * 256 + 64 * 64 * 9 + 256 * 64) * 56 * 56
    stage1 = u1 + 2 * u
    # stage 2: first unit reads 56x56 for conv1, the rest at 28x28
    u1 = 128 * 256 * 56 * 56 + (128 * 128 * 9 + 512 * 128 +
                                512 * 256) * 28 * 28
    u = (128 * 512 + 128 * 128 * 9 + 512 * 128) * 28 * 28
    stage2 = u1 + 3 * u
    u1 = 256 * 512 * 28 * 28 + (256 * 256 * 9 + 1024 * 256 +
                                1024 * 512) * 14 * 14
    u = (256 * 1024 + 256 * 256 * 9 + 1024 * 256) * 14 * 14
    stage3 = u1 + 5 * u
    u1 = 512 * 1024 * 14 * 14 + (512 * 512 * 9 + 2048 * 512 +
                                 2048 * 1024) * 7 * 7
    u = (512 * 2048 + 512 * 512 * 9 + 2048 * 512) * 7 * 7
    stage4 = u1 + 2 * u
    want = stem + stage1 + stage2 + stage3 + stage4 + 2048 * 1000
    assert resnet.forward_macs(cfg) == want
    assert resnet.train_flops_per_sample(cfg) == 6 * want
    assert 4.0e9 < want < 4.2e9            # the well-known 4.1 GMACs
    n = sum(int(__import__("numpy").prod(s))
            for s in resnet_ref.param_shapes(cfg).values())
    assert 25.4e6 < n < 25.7e6             # 25.6 M parameters


@pytest.mark.parametrize("family, need, config, traffic, today", [
    # (operations, bytes) of a prefill of the pool's 16 rows of 32 and
    # of 300 tokens, as the cells' rooflines have counted them so far
    ("granite", "mamba2_scan_need", "granite-4.0-h-micro",
     "chat_deck_long_answers",
     {32: (41223979008, 2729705472), 300: (938622320640, 5357666304)}),
    ("lfm2_moe", "shortconv_conv_need", "lfm2-24b-a2b",
     "chat_deck_long_answers_16x1280",
     {32: (120317804544, 266162176), 300: (1127979417600, 512053248)}),
])
def test_a_prefill_s_need_follows_the_rows_it_ran(family, need, config,
                                                  traffic, today):
    """`need(P, rows=r)` grows by the same operations and bytes with
    every row, and with no row count it is the pool's width, which is
    what `_admit_batch` runs and what the metric has read so far."""
    fn = getattr(importlib.import_module("cellbench.ops." + family), need)
    cfg = run.load_json(run.HERE, "configs", config + ".json")
    mix = run.load_json(run.HERE, "traffic", traffic + ".json")
    slots = mix["slots"]
    for prompt, want in today.items():
        assert fn(cfg, mix, prompt) == want
        assert fn(cfg, mix, prompt, rows=slots) == want
        f0, b0 = fn(cfg, mix, prompt, rows=0)   # weights read once
        assert f0 == 0 and 0 <= b0 < want[1]
        f1, b1 = fn(cfg, mix, prompt, rows=1)
        for r in (2, 5, slots, 2 * slots):
            f, b = fn(cfg, mix, prompt, rows=r)
            assert f == r * f1
            assert b - b0 == r * (b1 - b0)
