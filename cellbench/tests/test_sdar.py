"""The SDAR configuration and its files: the configuration keeps every
number of its source but the depth and resolves to its cell; the
operation and byte counts of `cellbench/ops/sdar.py` by hand at a small
size and against the issue's arithmetic at the published one; the
replay's layout by hand; and a run of kind `serve_blocks` at toy size
on the CPU: sound, control, and a token altered where it is produced."""
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.drive import serve_blocks
from cellbench.ops import sdar as ops
from cellbench.reference import sdar as ref

CELL = "sdar-30b-a3b-chat.serve_block_answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _small():
    with open(os.path.join(run.HERE, "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=16,
               vocab_size=97, num_hidden_layers=2,
               max_position_embeddings=64, initializer_range=0.3,
               compute_dtype="float32")
    cfg["assumed"] = dict(cfg["assumed"], mask_token_id=96)
    return cfg


SMALL = _small()
# float32 at toy size: with 32 channels and 2 of 8 experts, bfloat16
# rounding moves a token across a near-tie in the router in most runs,
# and the four numbers then read whatever that one token did. Sound
# float32 runs read 0 gaps, logit_err under 1e-5 and |int8_share| under
# 1e-3; the int8 twin reads logit_err 0.01-0.05 and int8_share 1.
DECK = {"kind": "serve_blocks", "callers": 4, "slots": 2, "max_len": 64,
        "queue_cap": 64, "prompt_lengths": [5, 6, 11, 16],
        "output_lengths": [6, 6, 9, 9], "blocks": 4,
        "denoising_steps": 2, "remasking": "sequential",
        "warm_requests": 16, "window_opens_after_s": 0,
        "check_requests": 4, "probe_tokens": 6,
        "limits": {"gap_widest": 0.01, "gap_mean": 0.001,
                   "logit_err": 0.005, "int8_share": 0.3}}
POOL = {"slots": 3, "prompt_lengths": [4, 12], "output_lengths": [8, 16]}


@pytest.fixture(scope="module")
def published():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    return run.resolve(manifest, CELL)


def test_the_cell_resolves_to_its_files(published):
    cell, entry, cfg, traffic = published
    assert cell["chips"] == 1
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["family"] == "sdar" and entry["source"] == cfg["source"]
    assert traffic["kind"] == "serve_blocks"
    assert (traffic["callers"], traffic["slots"], traffic["max_len"],
            traffic["queue_cap"]) == (24, 16, 1024, 64)
    assert traffic["prompt_lengths"] == [61, 126, 255, 508]
    assert [p % 4 for p in traffic["prompt_lengths"]] == [1, 2, 3, 0]
    assert traffic["output_lengths"] == [256, 256, 512, 512]
    assert (traffic["denoising_steps"], traffic["remasking"]) == \
        (2, "sequential")
    assert (traffic["warm_requests"], traffic["check_requests"],
            traffic["window_opens_after_s"]) == (16, 8, 110)
    # the last block is run whole
    assert -(-(508 + 512) // 4) * 4 <= traffic["max_len"]
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    assert {m["name"] for m in
            run.metrics_for(manifest, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in
            run.metrics_for(manifest, "per_layer", CELL)} == {
        "moe_device_share.serve_blocks",
        "moe_experts_roofline.serve_blocks",
        "block_step_roofline.serve_blocks",
        "block_forwards_per_token.serve_blocks",
        "serve_block_time_p50_ms", "block_step_host_ms.serve_blocks",
        "device_idle_share.serve_blocks", "peak_hbm_gb.serve_blocks",
        "prefill_rows_real_share"}


def test_the_configuration_keeps_every_number_but_the_depth(published):
    _cell, _entry, cfg, _traffic = published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["published"]["num_hidden_layers"] == \
        row["config"]["num_hidden_layers"] == 48
    for key in ("reduced", "published", "deployment", "assumed"):
        assert cfg[key]
    assert cfg["assumed"]["block_length"] == 4
    assert cfg["assumed"]["mask_token_id"] == 151669


def test_counts_at_the_published_size_are_the_issue_s(published):
    _cell, _entry, cfg, traffic = published
    layer = 2048 * 4096 * 2 + 2048 * 512 * 2 + 2 * 128 + 2 * 2048 + \
        2048 * 128 + 128 * 3 * 2048 * 768
    assert ops.weight_bytes(cfg) == 2 * (
        6 * layer + 2 * 151936 * 2048 + 2048)
    assert round(ops.weight_bytes(cfg) / 1e9, 2) == 8.72
    assert round(2 * layer / 1e9, 3) == 1.246
    assert ops.expert_params(cfg) == 3 * 2048 * 768
    assert ops.pairs_per_layer(cfg, traffic) == 16 * 4 * 8 == 512
    # nothing measured: as many experts as the pairs allow
    assert ops.experts_hit(cfg, traffic) == 128
    _flops, nbytes = ops.block_step_need(cfg, traffic)
    _flops, moe = ops.moe_experts_need(cfg, traffic)
    assert 8.0e9 < nbytes < 8.4e9           # the issue's "about 8 GB"
    assert 0.87 < moe / nbytes < 0.91       # 89% of it expert weights
    # a forward that hit half the experts needs half their bytes
    half = dict(traffic, measured={"experts_hit_per_layer_forward": 64})
    assert ops.moe_experts_need(cfg, half)[1] < 0.52 * moe
    assert ops.mean_depth(traffic) == 237.5 + 192


def test_counts_by_hand_at_a_small_size():
    d, hd, f, e, v = 32, 16, 16, 8, 97
    shared = 2 * d + (4 + 2 * 2) * hd * d + 2 * hd + d * 4 * hd + d * e
    layer = shared + e * 3 * d * f
    assert ops.weight_bytes(SMALL) == 2 * (2 * layer + 2 * v * d + d)
    pairs = 3 * 4 * 2
    assert ops.pairs_per_layer(SMALL, POOL) == pairs
    assert ops.experts_hit(SMALL, POOL) == 8
    hit = dict(POOL, measured={"experts_hit_per_layer_forward": 5.5})
    flops, nbytes = ops.moe_experts_need(SMALL, hit)
    assert flops == 2 * 2 * pairs * 3 * d * f
    assert nbytes == 2 * 2 * (5.5 * 3 * d * f +
                              pairs * (2 * d + 5 * f))
    depth = 8 + 0.5 * 12
    flops2, nbytes2 = ops.block_step_need(SMALL, hit)
    top = v * d + d * (1 + 12)
    assert nbytes2 == nbytes + 2 * (
        2 * shared + top + 3 * 2 * (2 * 2 * hd) * depth)
    assert flops2 == flops + 2 * 12 * (2 * shared + top) + \
        2 * 12 * 2 * 2 * 4 * hd * depth


def test_the_replay_of_a_row_by_hand():
    """Prompt of 5 (one whole block, one token over), 6 served tokens
    (positions 5-10), two tokens a forward: the first block needs two
    forwards for its three masks, and so does the second, inside which
    the row ends (its last position, 11, is unmasked and not served)."""
    ids = np.arange(100, 111)
    toks, pos, blk, state, where = ref.plan_row(5, ids, 4, 96, 2)
    M = 96
    assert toks.tolist() == list(ids) + \
        [104, M, M, M] + [104, 105, 106, M] + \
        [M, M, M, M] + [108, 109, M, M]
    assert pos.tolist() == list(range(11)) + [4, 5, 6, 7] * 2 + \
        [8, 9, 10, 11] * 2
    assert state.tolist() == [0] * 11 + [1] * 4 + [2] * 4 + [3] * 4 + \
        [4] * 4
    np.testing.assert_array_equal(blk, pos // 4)
    # served token j (position 5 + j) was predicted at:
    assert where.tolist() == [11 + 1, 11 + 2, 15 + 3, 19 + 0, 19 + 1,
                              23 + 2]
    assert ref.replay_length(5, 6, 4, 2) == len(toks) == 11 + 4 * 4
    # max_new 2 ends the row inside the first block's first forward
    _t, _p, _b, st, wh = ref.plan_row(5, ids[:7], 4, 96, 2)
    assert st.max() == 1 and wh.tolist() == [7 + 1, 7 + 2]


def test_block_times_are_between_first_tokens_of_blocks():
    # prompt 5: served tokens 0-2 finish block 4-7, 3-6 are block 8-11
    ts = [[1.0, 1.0, 2.0, 3.0, 3.0, 4.0, 4.0, 5.0]]
    got = serve_blocks.block_times(ts, [5], 4, 0.0, 10.0)
    assert got == [2.0, 2.0]                  # 1 -> 3 -> 5
    assert serve_blocks.block_times(ts, [5], 4, 3.5, 10.0) == [2.0]


@pytest.mark.parametrize("control", [False, True])
def test_the_cell_at_toy_size_sound_and_control(control):
    """The drive, the program and the reference together: a sound run
    is `correct`; the control (the reference's int8 twin, the experts
    among its int8 weights, read in the program's place) is not."""
    res = run.run_cell(SMALL, dict(DECK), 2 ** 31 + 5, 1.5,
                       control=control)
    by_name = {c["name"]: c for c in res["checks"]}
    assert res["correct"] is (not control)
    assert by_name["malformed_rows"]["value"] == 0
    assert by_name["failed_requests"]["value"] == 0
    assert by_name["int8_share"]["ok"] is (not control)
    assert set(res["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    r = res["readings"]
    assert r["stats.steps"] > 0 and r["client.tokens"] > 0
    assert r["stats.forwards"] >= r["stats.steps"]
    assert r["series"]["block_ms"] and r["series"]["gap_ms"]
    hit = r["traffic"]["measured"]["experts_hit_per_layer_forward"]
    assert 1 <= hit <= 8
    if control:
        assert by_name["int8_share"]["value"] == 1.0


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The block step's picks shifted by one id on their way out of the
    program: the rows are well-formed and every token is wrong."""
    def break_step(decoder):
        sound = decoder._step_fn

        def shifted(args, aux, rng):
            (best, conf, logits, stats), new_aux = sound(args, aux, rng)
            return ((best + 1) % 96, conf, logits, stats), new_aux

        decoder._step_fn = shifted

    bad = run.run_cell(SMALL, dict(DECK), 5, 1.5, program_hook=break_step)
    assert bad["correct"] is False
    failed = {c["name"] for c in bad["checks"] if not c["ok"]}
    assert {"gap_widest", "gap_mean"} <= failed
