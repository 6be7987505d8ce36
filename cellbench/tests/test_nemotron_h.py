"""The Nemotron-H configuration and its files: the configuration keeps
every number of its source but the three it reduces and resolves to
its cell; the operation and byte counts of `cellbench/ops/nemotron_h.py`
by hand at a small size and against the issue's arithmetic at the
published one; and a run of kind
`serve_stream` at toy size on the CPU: sound, control, and a token
altered where it is produced."""
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.ops import nemotron_h as ops
from cellbench.reference import nemotron_h as ref

CELL = "nemotron-3-super-120b-a12b.serve_long_answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _small():
    with open(os.path.join(run.HERE, "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=48,
               mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
               n_groups=4, chunk_size=8, router_outputs=16,
               n_routed_experts=4, routed_experts_first=4,
               num_experts_per_tok=5, moe_intermediate_size=24,
               moe_latent_size=16,
               moe_shared_expert_intermediate_size=40, vocab_size=97,
               num_hidden_layers=5, hybrid_override_pattern="EM*-E",
               max_position_embeddings=64, initializer_range=0.2,
               compute_dtype="float32")
    return cfg


SMALL = _small()
# float32 at toy size: with 32 channels and 5 of 16 experts, bfloat16
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
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert cfg["family"] == "nemotron_h"
    assert entry["source"] == cfg["source"]
    assert traffic["kind"] == "serve_stream"
    assert (traffic["callers"], traffic["slots"], traffic["max_len"],
            traffic["queue_cap"]) == (40, 32, 768, 64)
    assert traffic["prompt_lengths"] == [32, 64, 128, 256]
    assert traffic["output_lengths"] == [128, 256, 384, 512]
    assert (traffic["warm_requests"], traffic["check_requests"],
            traffic["window_opens_after_s"]) == (32, 8, 110)
    assert 256 + 512 <= traffic["max_len"]
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    e2e = {m["name"] for m in
           run.metrics_for(manifest, "end_to_end", CELL)}
    assert {"serve_tokens_per_s", "setup_s"} <= e2e <= {
        "serve_tokens_per_s", "serve_itl_p50_ms", "setup_s"}
    layer = run.metrics_for(manifest, "per_layer", CELL)
    assert {m["name"] for m in layer} >= {
        "admit_wall_share.serve", "idle_under_admit_share.serve",
        "prefill_rows_real_share",
        "moe_device_share.serve_stream",
        "mamba2_device_share.serve_stream",
        "moe_experts_roofline.serve_stream",
        "mamba2_step_roofline.serve_stream",
        "decode_program_roofline.serve_stream",
        "moe_pairs_here_share.serve_stream",
        "decode_steps_per_token.serve_stream",
        "decode_step_host_ms.serve_stream",
        "device_idle_share.serve_stream", "peak_hbm_gb.serve_stream"}
    assert all(m["workloads"] == [CELL] for m in layer
               if m["name"].endswith(".serve_stream"))


def test_the_configuration_keeps_every_number_but_its_three(published):
    _cell, _entry, cfg, _traffic = published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] ==
                   "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cfg["source"] == row["source_url"]
    pattern = row["config"]["hybrid_override_pattern"]
    for key, value in row["config"].items():
        if key not in REDUCED + ["hybrid_override_pattern"]:
            assert cfg[key] == value, key
    # one whole period of the published pattern, in its ratio
    assert cfg["hybrid_override_pattern"] == pattern[26:37] == \
        "EMEMEMEMEM*"
    assert (pattern.count("M"), pattern.count("E"),
            pattern.count("*"), pattern.count("-")) == (40, 40, 8, 0)
    assert cfg["num_hidden_layers"] == 11
    assert (cfg["n_routed_experts"], cfg["routed_experts_first"],
            cfg["router_outputs"]) == (128, 0, 512)
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"], pub["hybrid_override_pattern"]) == \
        (88, 512, 131072, pattern)
    for key in ("reduced", "published", "deployment", "assumed",
                "omitted"):
        assert cfg[key]
    assert "multi_token_prediction" in cfg["omitted"]


def test_counts_at_the_published_size_are_the_issue_s(published):
    _cell, _entry, cfg, traffic = published
    m_layer = 4096 * 18560 + 8192 * 4096 + 10240 * 5 + 3 * 128 + \
        8192 + 4096
    a_layer = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    e_outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    assert ops.expert_params(cfg) == expert == 5505024
    assert ops.weight_bytes(cfg) == 2 * (
        5 * m_layer + a_layer + 5 * (e_outside + 128 * expert) +
        2 * 32768 * 4096 + 4096) + 5 * 512 * 4     # the biases: float32
    assert round(ops.weight_bytes(cfg) / 1e9, 2) == 9.30
    assert round(2 * m_layer / 1e9, 3) == 0.219
    assert round(2 * (e_outside + 128 * expert) / 1e9, 3) == 1.518
    per_slot = ops.state_bytes_per_slot(cfg, traffic)
    assert per_slot == {"scan_state": 5 * 128 * 64 * 128 * 4,
                        "conv_window": 5 * 3 * 10240 * 2,
                        "kv_rows": 2 * 2 * 128 * 768 * 2}
    assert round(32 * sum(per_slot.values()) / 1e9, 2) == 0.71
    assert ops.pairs_per_layer(cfg, traffic) == 32 * 22 == 704
    # nothing measured: the share's mean, every held expert it allows
    assert ops.pairs_here(cfg, traffic) == 176
    assert ops.experts_hit(cfg, traffic) == 128
    step = dict(traffic, measured={"experts_hit_per_layer_step": 96,
                                   "pairs_here_per_layer_step": 176})
    _flops, nbytes = ops.decode_step_need(cfg, step)
    _flops, moe = ops.moe_experts_need(cfg, step)
    _flops, scan = ops.mamba2_step_need(cfg, step)
    assert 8.5e9 < nbytes < 8.8e9           # the issue's "about 8.6 GB"
    assert 0.59 < moe / nbytes < 0.63       # three fifths: expert weights
    assert 0.15 < scan / nbytes < 0.17      # a sixth: state and window
    # a step that hit half as many experts needs half their bytes
    half = dict(traffic, measured={"experts_hit_per_layer_step": 48,
                                   "pairs_here_per_layer_step": 176})
    assert ops.moe_experts_need(cfg, half)[1] < 0.51 * moe
    assert ops.mean_depth(traffic) == 120 + 160


def test_counts_by_hand_at_a_small_size():
    d, v, z, f, hs = 32, 97, 16, 24, 40
    conv = 64 + 2 * 4 * 16
    mamba = d + (64 + conv + 8) * d + conv * 4 + conv + 3 * 8 + 64 + \
        d * 64
    attn = d + (64 + 2 * 32) * d + d * 64
    outside = d + d * 16 + 2 * d * z + 2 * d * hs
    mlp = d + 2 * 48 * d
    assert ops.weight_bytes(SMALL) == 2 * (
        mamba + attn + mlp + 2 * (outside + 4 * 2 * z * f) +
        2 * v * d + d) + 2 * 16 * 4
    assert ops.pairs_per_layer(SMALL, POOL) == 3 * 5
    assert ops.pairs_here(SMALL, POOL) == 15 * 4 / 16
    hit = dict(POOL, measured={"experts_hit_per_layer_step": 2.5,
                               "pairs_here_per_layer_step": 4.0})
    flops, nbytes = ops.moe_experts_need(SMALL, hit)
    assert flops == 2 * 2 * 4.0 * 2 * z * f
    assert nbytes == 2 * 2 * (2.5 * 2 * z * f + 4.0 * 2 * (z + f))
    flops_m, bytes_m = ops.mamba2_step_need(SMALL, hit)
    state = 64 * 16
    assert bytes_m == 3 * (2 * state * 4 + 2 * 3 * conv * 2 +
                           (conv + 8 + 64) * 2)
    assert flops_m == 3 * (6 * state + 2 * 4 * conv)
    depth = 8 + 0.5 * 12
    _flops, total = ops.decode_step_need(SMALL, hit)
    held_out = 2 * (mamba + attn + mlp + 2 * outside) + 2 * 16 * 4
    assert total == held_out + 2 * (v * d + d + 3 * d) + nbytes + 3 * (
        2 * state * 4 + 2 * 3 * conv * 2 + 2 * 2 * 16 * 2 * depth)


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
    assert set(res["end_to_end"]) == {
        "serve_tokens_per_s", "serve_itl_p50_ms", "serve_itl_p99_ms",
        "setup_s"}
    r = res["readings"]
    assert r["stats.steps"] > 0 and r["client.tokens"] > 0
    gaps = r["series"]["gap_ms"]
    assert gaps and gaps == sorted(gaps)
    # two expert layers, 2 rows x 5 pairs a layer and step; the share
    # (experts 4-7 of 16) gets some of them
    assert r["stats.moe_assignments"] == r["stats.steps"] * 2 * 2 * 5
    assert 0 < r["stats.moe_pairs_here"] < r["stats.moe_assignments"]
    measured = r["traffic"]["measured"]
    assert 0 < measured["experts_hit_per_layer_step"] <= 4
    assert measured["pairs_here_per_layer_step"] == \
        r["stats.moe_pairs_here"] / (2 * r["stats.steps"])
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


def test_the_reference_is_given_the_same_share():
    """The reference with experts 4-7 of 16 held differs from the one
    that holds all 16 by what the absent experts add, and the int8 twin
    from both."""
    toks = np.arange(24, dtype=np.int32).reshape(2, 12) % 97
    where = np.tile(np.arange(4, 10), (2, 1))
    part = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32"))
    whole = dict(SMALL, n_routed_experts=16, routed_experts_first=0)
    full = np.asarray(ref.logits_at(whole, 3, toks, where, "float32"))
    twin = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32",
                                    int8=True))
    assert part.shape == full.shape == (2, 6, 97)
    assert np.abs(part - full).max() > 0.05 * part.std()
    assert 1e-4 * part.std() < np.abs(part - twin).max() < \
        0.5 * part.std()
