"""The LFM2-MoE configuration and its files: the configuration keeps
every number of its source but the depth and resolves to its cell; the
operation and byte counts of `cellbench/ops/lfm2_moe.py` by hand at a
small size and against the issue's arithmetic at the published one;
and a run of kind `serve_stream` at toy size on the CPU: sound, control,
and a token altered where it is produced."""
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.ops import lfm2_moe as ops
from cellbench.reference import lfm2_moe as ref

CELL = "lfm2-24b-a2b.serve_long_answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers"]
METRICS = {
    "shortconv_device_share.serve_conv",
    "shortconv_conv_roofline.serve_conv", "moe_device_share.serve_conv",
    "moe_experts_roofline.serve_conv", "moe_experts_hit_share.serve_conv",
    "decode_program_roofline.serve_conv",
    "decode_steps_per_token.serve_conv",
    "decode_step_host_ms.serve_conv", "device_idle_share.serve_conv",
    "peak_hbm_gb.serve_conv"}


def _small():
    with open(os.path.join(run.HERE, "configs",
                           "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=48,
               num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=16, vocab_size=97,
               num_hidden_layers=5, num_dense_layers=1,
               layer_types=["conv", "full_attention", "conv", "conv",
                            "conv"],
               max_position_embeddings=64, initializer_range=0.2,
               compute_dtype="float32")
    cfg["assumed"] = dict(cfg["assumed"], head_dim=8)
    return cfg


SMALL = _small()
# float32 at toy size: with 32 channels and 2 of 8 experts, bfloat16
# rounding moves a token across a near-tie in the router in most runs,
# and the four numbers then read whatever that one token did. Sound
# float32 runs read gaps of 0, logit_err under 1e-5 and |int8_share|
# under 1e-3; the int8 twin reads logit_err 0.01-0.05 and int8_share 1.
DECK = {"kind": "serve_stream", "callers": 4, "slots": 2, "max_len": 64,
        "queue_cap": 64, "prompt_lengths": [4, 8, 12, 16],
        "output_lengths": [2, 3, 4, 6], "blocks": 4, "warm_requests": 16,
        "window_opens_after_s": 0, "check_requests": 4,
        "limits": {"gap_widest": 0.01, "gap_mean": 0.001,
                   "logit_err": 0.005, "int8_share": 0.3}}
POOL = {"slots": 3, "max_len": 40, "prompt_lengths": [4, 12],
        "output_lengths": [8, 16]}


@pytest.fixture(scope="module")
def published():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    return run.resolve(manifest, CELL)


def test_the_cell_resolves_to_its_files(published):
    cell, entry, cfg, traffic = published
    assert cell["chips"] == 1
    assert cell["traffic"] == "chat_deck_long_answers_16x1280"
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert cfg["family"] == "lfm2_moe"
    assert entry["source"] == cfg["source"]
    assert traffic["kind"] == "serve_stream"
    assert traffic["callers"] in (24, 20)      # the issue's one allowance
    assert (traffic["slots"], traffic["max_len"],
            traffic["queue_cap"]) == (16, 1280, 64)
    assert traffic["prompt_lengths"] == [32, 64, 128, 256]
    assert traffic["output_lengths"] == [256, 512, 768, 1024]
    assert (traffic["blocks"], traffic["warm_requests"],
            traffic["check_requests"],
            traffic["window_opens_after_s"]) == (8, 16, 8, 110)
    assert 256 + 1024 <= traffic["max_len"]
    assert set(traffic["limits"]) == {"gap_widest", "gap_mean",
                                      "logit_err", "int8_share"}
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    e2e = {m["name"] for m in
           run.metrics_for(manifest, "end_to_end", CELL)}
    assert e2e == {"serve_tokens_per_s", "serve_itl_p50_ms", "setup_s"}
    layer = run.metrics_for(manifest, "per_layer", CELL)
    # its own ten, and the three every serve cell shares (PR 40)
    assert {m["name"] for m in layer} == METRICS | {
        "admit_wall_share.serve", "idle_under_admit_share.serve",
        "prefill_rows_real_share"}
    assert all(m["workloads"] == [CELL] for m in layer
               if m["name"] in METRICS)
    # the hit share's scale is 100 over the cell's expert layers x
    # experts: the counter reader has no other way to know them
    spec = run.load_json(run.HERE, "metrics",
                         "moe_experts_hit_share.serve_conv.json")
    s = ref.sizes(cfg)
    assert spec["args"]["scale"] == 100.0 / (
        s["kinds"].count("experts") * s["experts"])


def test_the_configuration_keeps_every_number_but_its_depth(published):
    _cell, _entry, cfg, _traffic = published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    types = row["config"]["layer_types"]
    # the published layers 1-9: the second leading dense layer and two
    # whole periods, attention : conv = 2 : 6 as the published 10 : 30
    assert cfg["layer_types"] == types[1:10] == \
        ["conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv"]
    assert (types.count("conv"), types.count("full_attention")) == \
        (30, 10)
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (9, 1)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["layer_types"]) == (40, 2, types)
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["assumed"]["head_dim"],
            cfg["intermediate_size"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["rope_parameters"]["rope_theta"],
            cfg["vocab_size"]) == (2048, 32, 8, 64, 11776, 64, 1536, 4,
                                   3, 1000000, 65536)
    for key in ("reduced", "published", "deployment", "assumed"):
        assert cfg[key]
    assert "10.36 GB" in cfg["deployment"]
    assert "five pipeline stages" in cfg["deployment"]
    for key in ("head", "head_dim_why", "qk_norm", "in_proj_order",
                "router", "router_bias", "weights"):
        assert cfg["assumed"][key]


def test_counts_at_the_published_size_are_the_issue_s(published):
    _cell, _entry, cfg, traffic = published
    d, f, ffn = 2048, 1536, 11776
    conv = 3 * d * d + d * d + d * 3
    attn = d * 2048 + 2 * d * 512 + 2048 * d + 2 * 64
    router = d * 64
    experts = 64 * 3 * d * f
    assert ops.expert_params(cfg) == 3 * d * f == 9437184
    assert ops.weight_bytes(cfg) == 2 * (
        7 * conv + 2 * attn + 3 * d * ffn + 8 * (router + experts) +
        18 * d + 65536 * d + d) + 8 * 64 * 4        # the biases: float32
    assert round(ops.weight_bytes(cfg) / 1e9, 2) == 10.36
    assert round(2 * (conv + router + experts + 2 * d) / 1e9, 3) == 1.242
    assert round(2 * (attn + router + experts + 2 * d) / 1e9, 3) == 1.229
    assert round(2 * (conv + 3 * d * ffn + 2 * d) / 1e9, 3) == 0.178
    per_slot = ops.state_bytes_per_slot(cfg, traffic)
    assert per_slot == {"conv_window": 7 * 2 * 2048 * 2,       # 56 KB
                        "kv_rows": 2 * 2 * 8 * 64 * 1280 * 2}  # 4 KB a token
    assert per_slot["kv_rows"] // 1280 == 4096
    assert ops.pairs_per_layer(cfg, traffic) == 16 * 4 == 64
    assert ops.experts_hit(cfg, traffic) == 64    # nothing measured yet
    assert ops.mean_depth(traffic) == 120 + 320
    step = dict(traffic, measured={"experts_hit_per_layer_step": 41.0})
    _flops, nbytes = ops.decode_step_need(cfg, step)
    _flops, moe = ops.moe_experts_need(cfg, step)
    _flops, conv_bytes = ops.shortconv_step_need(cfg, step)
    assert 6.6e9 < nbytes < 7.1e9           # the issue's "about 6.8 GB"
    assert 0.88 < moe / nbytes < 0.92       # nine tenths: expert weights
    assert 0.030 < conv_bytes / nbytes < 0.040
    # a prefill of 16 x 32 positions and more is bound by the two
    # projections' operations, not by their weights' bytes
    for prompt in traffic["prompt_lengths"]:
        flops, nbytes = ops.shortconv_conv_need(cfg, traffic, prompt)
        assert flops / 197e12 > nbytes / 819e9
    # a step that hit half as many experts needs half their bytes
    half = dict(traffic, measured={"experts_hit_per_layer_step": 20.5})
    assert ops.moe_experts_need(cfg, half)[1] < 0.51 * moe


def test_counts_by_hand_at_a_small_size():
    d, v, f, ffn, e = 32, 97, 16, 48, 8
    conv = d + 3 * d * d + d * 3 + d * d
    attn = d + (32 + 2 * 16) * d + 2 * 8 + d * 32
    mlp = d + 3 * d * ffn
    outside = d + d * e
    assert ops.weight_bytes(SMALL) == 2 * (
        4 * conv + attn + mlp + 4 * (outside + e * 3 * d * f) +
        v * d + d) + 4 * e * 4
    assert ops.state_bytes_per_slot(SMALL, POOL) == {
        "conv_window": 4 * 2 * d * 2, "kv_rows": 2 * 2 * 8 * 40 * 2}
    assert ops.pairs_per_layer(SMALL, POOL) == 3 * 2
    assert ops.experts_hit(SMALL, POOL) == 6
    hit = dict(POOL, measured={"experts_hit_per_layer_step": 2.5})
    flops, nbytes = ops.moe_experts_need(SMALL, hit)
    assert flops == 4 * 2 * 6 * 3 * d * f
    assert nbytes == 4 * 2 * (2.5 * 3 * d * f + 6 * (2 * d + 5 * f))
    flops_c, bytes_c = ops.shortconv_step_need(SMALL, hit)
    assert flops_c == 4 * 3 * (2 * 4 * d * d + (2 * 3 + 2) * d)
    assert bytes_c == 4 * (2 * (4 * d * d + 3 * d) +
                           3 * (2 * 2 * d * 2 + 2 * d * 2))
    flops_p, bytes_p = ops.shortconv_conv_need(SMALL, hit, 12)
    assert flops_p == 4 * 3 * 12 * (2 * 4 * d * d + (2 * 3 + 2) * d)
    assert bytes_p == 4 * (2 * (4 * d * d + 3 * d) + 3 * 12 * 2 * d * 2 +
                           3 * 2 * 2 * d * 2)
    depth = 8 + 0.5 * 12
    flops_s, total = ops.decode_step_need(SMALL, hit)
    held_out = 2 * (4 * conv + attn + mlp + 4 * outside) + 4 * e * 4
    top = 2 * (v * d + d + 3 * d)
    assert total == held_out + top + nbytes + 3 * (
        2 * 4 * 2 * d * 2 + 2 * 2 * 8 * 2 * (depth + 1))
    assert flops_s == 3 * 2 * (held_out + top) // 2 + flops + \
        3 * 2 * 2 * 4 * 8 * depth


@pytest.mark.parametrize("control", [False, True])
def test_the_cell_at_toy_size_sound_and_control(control):
    """The drive, the program and the reference together: a sound run
    is `correct`; the control (the reference's int8 twin, the experts
    among its int8 weights, read in the program's place) is not, by
    `int8_share`."""
    res = run.run_cell(SMALL, dict(DECK), 2 ** 31 + 5, 1.5,
                       control=control)
    by_name = {c["name"]: c for c in res["checks"]}
    assert res["correct"] is (not control)
    assert by_name["malformed_rows"]["value"] == 0
    assert by_name["failed_requests"]["value"] == 0
    assert by_name["int8_share"]["ok"] is (not control)
    assert set(res["end_to_end"]) == {
        "serve_tokens_per_s", "serve_itl_p50_ms", "serve_itl_p99_ms",
        "setup_s"}
    r = res["readings"]
    assert r["stats.steps"] > 0 and r["client.tokens"] > 0
    # four expert layers, 2 rows x 2 pairs a layer and step, every
    # expert held here
    assert r["stats.moe_assignments"] == r["stats.steps"] * 4 * 2 * 2
    assert r["stats.moe_pairs_here"] == r["stats.moe_assignments"]
    measured = r["traffic"]["measured"]
    assert 1 <= measured["experts_hit_per_layer_step"] <= 4
    assert measured["experts_hit_per_layer_step"] == \
        r["stats.moe_experts_hit"] / (4 * r["stats.steps"])
    if control:
        assert by_name["int8_share"]["value"] == 1.0


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The step's logits rolled by one id on their way out of the
    program: the rows are well-formed and every token is wrong."""
    def break_step(decoder):
        sound = decoder._step_fn

        def rolled(args, aux, rng):
            outs, new_aux = sound(args, aux, rng)
            return (np.roll(np.asarray(outs[0]), 1, axis=-1),) + \
                tuple(outs[1:]), new_aux

        decoder._step_fn = rolled

    bad = run.run_cell(SMALL, dict(DECK), 5, 1.5, program_hook=break_step)
    assert bad["correct"] is False
    failed = {c["name"] for c in bad["checks"] if not c["ok"]}
    assert {"gap_widest", "gap_mean"} <= failed


def test_the_int8_twin_differs_from_the_reference_and_not_by_much():
    toks = np.arange(24, dtype=np.int32).reshape(2, 12) % 97
    where = np.tile(np.arange(4, 10), (2, 1))
    plain = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32"))
    twin = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32",
                                    int8=True))
    assert plain.shape == twin.shape == (2, 6, 97)
    assert 1e-4 * plain.std() < np.abs(plain - twin).max() < \
        0.5 * plain.std()
