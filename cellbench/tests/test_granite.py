"""The Granite configuration and its files: the configuration keeps
every number of its source and resolves to its cell; the operation and
byte counts of `cellbench/ops/granite.py` by hand at a small size and
against the issue's arithmetic at the published one; the reference's
equations against a token-by-token numpy; and the cell's run at toy
size on the CPU, sound and control."""
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.ops import granite as ops
from cellbench.reference import granite as ref
from cellbench.tests import toy

CELL = "granite-4.0-h-micro.serve_long_answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

SMALL = {"family": "granite", "hidden_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 2, "shared_intermediate_size": 48,
         "vocab_size": 97, "num_hidden_layers": 4,
         "layer_types": ["mamba", "attention", "mamba", "mamba"],
         "max_position_embeddings": 64, "mamba_n_heads": 8,
         "mamba_d_head": 8, "mamba_d_state": 16, "mamba_d_conv": 4,
         "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 8,
         "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
         "residual_multiplier": 0.22, "attention_multiplier": 0.25,
         "logits_scaling": 8, "initializer_range": 0.2,
         "compute_dtype": "bfloat16"}
POOL = {"slots": 3, "max_len": 40, "prompt_lengths": [4, 12],
        "output_lengths": [8, 16]}


@pytest.fixture(scope="module")
def published():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    return run.resolve(manifest, CELL)


def test_the_cell_resolves_to_its_files(published):
    cell, entry, cfg, traffic = published
    assert cell["chips"] == 1 and entry["reduced"] == []
    assert cfg["family"] == "granite" and cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert traffic["kind"] == "serve"
    assert (traffic["callers"], traffic["slots"], traffic["max_len"]) \
        == (24, 16, 768)
    assert traffic["prompt_lengths"] == [32, 64, 128, 256]
    # the issue's first choice, for whom the cell stands (PERF.md §4)
    assert traffic["output_lengths"] == [128, 256, 384, 512]
    assert traffic["check_requests"] == 8
    assert max(traffic["prompt_lengths"]) + \
        max(traffic["output_lengths"]) <= traffic["max_len"]
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    mine = {m["name"] for m in
            run.metrics_for(manifest, "per_layer", CELL)}
    assert mine == {"mamba2_device_share.serve",
                    "mamba2_step_roofline.serve",
                    "mamba2_scan_roofline.serve",
                    "decode_program_roofline.serve",
                    # what every cell of kind `serve` reports (PR 40)
                    "decode_step_host_ms.serve",
                    "device_idle_share.serve", "peak_hbm_gb.serve",
                    "admit_wall_share.serve",
                    "idle_under_admit_share.serve",
                    "decode_steps_per_token",
                    "compiles_in_window.serve", "decode_slot_fill",
                    "prefills_per_request", "prefill_rows_real_share"}
    assert {m["name"] for m in
            run.metrics_for(manifest, "end_to_end", CELL)} == {
        "serve_tokens_per_s", "serve_itl_p50_ms", "setup_s"}


def test_the_configuration_keeps_every_number_of_its_source(published):
    _cell, _entry, cfg, _traffic = published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    s = ref.sizes(cfg)
    assert s["layers"] == 40 and s["kinds"].count("mamba") == 36
    assert [i for i, k in enumerate(s["kinds"]) if k == "attention"] \
        == [5, 15, 25, 35]


def test_counts_at_the_published_size_are_the_issue_s(published):
    _cell, _entry, cfg, traffic = published
    assert ops.weight_bytes(cfg) == 2 * (
        36 * (2048 * 8512 + 4352 * 5 + 3 * 64 + 4096 + 4096 * 2048) +
        4 * (2048 * 3072 + 2048 * 2048) +
        40 * (2 * 2048 + 2048 * 16384 + 8192 * 2048) +
        100352 * 2048 + 2048)
    assert round(ops.weight_bytes(cfg) / 1e9, 1) == 6.4
    per_slot = ops.state_bytes_per_slot(cfg, traffic)
    assert per_slot == {"scan_state": 36 * 64 * 64 * 128 * 4,
                        "conv_window": 36 * 3 * 4352 * 2,
                        "kv_rows": 4 * 2 * 8 * 64 * 768 * 2}
    _flops, nbytes = ops.decode_step_need(cfg, traffic)
    assert 8.7e9 < nbytes < 8.95e9          # the issue's 8.9 GB
    _flops, step = ops.mamba2_step_need(cfg, traffic)
    assert 0.26 < step / nbytes < 0.29      # the state alone: 27%
    assert ops.mean_depth(traffic) == 120 + 160


def test_counts_by_hand_at_a_small_size():
    d_inner, conv, n, heads = 64, 96, 16, 8
    weights = 3 * (32 + 32 * (64 + 96 + 8) + 96 * 4 + 96 + 3 * 8 + 64 +
                   32 * 64) + (32 + 32 * (32 + 2 * 16) + 32 * 32) + \
        4 * (32 + 32 * 96 + 32 * 48) + 97 * 32 + 32
    assert ops.weight_bytes(SMALL) == 2 * weights
    per_slot = ops.state_bytes_per_slot(SMALL, POOL)
    assert per_slot == {"scan_state": 3 * d_inner * n * 4,
                        "conv_window": 3 * 3 * conv * 2,
                        "kv_rows": 1 * 2 * 2 * 8 * 40 * 2}
    # one decode step of the mixers: per layer and slot the state
    # read and written, the window read and written, xBC + dt in, y out
    flops, nbytes = ops.mamba2_step_need(SMALL, POOL)
    assert nbytes == 3 * 3 * (2 * d_inner * n * 4 + 2 * 3 * conv * 2 +
                              (conv + heads + d_inner) * 2)
    assert flops == 3 * 3 * (6 * d_inner * n + 2 * 4 * conv)
    # one prefill of 12 tokens in chunks of 8: two chunks of 36 pairs
    flops, nbytes = ops.mamba2_scan_need(SMALL, POOL, 12)
    assert flops == 3 * 3 * 2 * (2 * 36 * n + 2 * 36 * d_inner +
                                 4 * 8 * d_inner * n)
    assert nbytes == 3 * 3 * (12 * (conv + heads + d_inner) * 2 +
                              2 * d_inner * n * 4)
    # a prompt shorter than a chunk is one chunk of its own length
    assert ops.mamba2_scan_need(SMALL, POOL, 4)[0] == 3 * 3 * (
        2 * 10 * n + 2 * 10 * d_inner + 4 * 4 * d_inner * n)
    # the whole step: weights once, states twice, rows to mean depth
    depth = 8 + 0.5 * 12
    flops, nbytes = ops.decode_step_need(SMALL, POOL)
    assert flops == 3 * 2 * weights
    assert nbytes == 2 * weights + 3 * (
        2 * per_slot["scan_state"] + 2 * per_slot["conv_window"] +
        per_slot["kv_rows"] * depth / 40)


def test_the_reference_s_mixer_is_the_equations_token_by_token():
    """`reference/granite.py::_mamba` (a scan over time in jax.numpy)
    against the same equations as a Python loop in float64 numpy."""
    import jax.numpy as jnp
    s = ref.sizes(SMALL)
    key = ref.base_key(5)
    p = {k: np.asarray(v, np.float64) for k, v in ref._layer_tensors(
        key, 0, "mamba", s, jnp.float32).items()}
    x = np.random.default_rng(1).standard_normal((2, 9, 32))
    got = np.asarray(ref._mamba(
        jnp.asarray(x, jnp.float32),
        {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}, s))
    silu = lambda v: v / (1 + np.exp(-v))
    zxd = x @ p["in_proj_weight"].T
    z, xbc, dt = zxd[..., :64], zxd[..., 64:160], zxd[..., 160:]
    win = np.zeros((2, 3, 96))
    S = np.zeros((2, 8, 8, 16))
    A = -np.exp(p["mamba_a_log"])
    out = []
    for t in range(9):
        full = np.concatenate([win, xbc[:, t:t + 1]], 1)
        act = silu(p["mamba_conv_bias"] + np.einsum(
            "bkc,ck->bc", full, p["mamba_conv_weight"]))
        win = full[:, 1:]
        xs = act[:, :64].reshape(2, 8, 8)
        step = np.log1p(np.exp(dt[:, t] + p["mamba_dt_bias"]))
        S = np.exp(step * A)[..., None, None] * S + \
            (step[..., None] * xs)[..., None] * act[:, None, None, 64:80]
        y = (S * act[:, None, None, 80:]).sum(-1) + \
            p["mamba_d_skip"][:, None] * xs
        y = y.reshape(2, 64) * silu(z[:, t])
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * \
            p["mnorm_gamma"]
        out.append(y @ p["out_proj_weight"].T)
    np.testing.assert_allclose(got, np.stack(out, 1), rtol=2e-4,
                               atol=2e-5)


def test_drawn_parameters_keep_their_ranges():
    """Step size and decay where training leaves them: A in -16..-1,
    the step size at a zero input in 0.001..0.1."""
    p = ref.make_params(SMALL, 2 ** 31 + 3, "float32")
    a_log = np.asarray(p["layer0_mamba_a_log"])
    dt_bias = np.asarray(p["layer2_mamba_dt_bias"])
    assert 0.0 <= a_log.min() and a_log.max() <= np.log(16.0) + 1e-3
    step = np.log1p(np.exp(dt_bias))
    assert 0.0009 < step.min() and step.max() < 0.11
    assert abs(np.asarray(p["layer0_mamba_d_skip"]).mean() - 1) < 0.3
    assert "layer1_qkv_weight" in p and "layer1_in_proj_weight" not in p


@pytest.mark.parametrize("control", [False, True])
def test_the_cell_at_toy_size_sound_and_control(control):
    """The drive, the program and the reference together: a sound run
    is `correct`; the control (the program's int8 weights, the tied
    table among them, and its int8 cache) is not, by `int8_share`."""
    res = run.run_cell(SMALL, toy.DECK, 2 ** 31 + 5, 1.5,
                       control=control)
    by_name = {c["name"]: c for c in res["checks"]}
    assert res["correct"] is (not control)
    assert by_name["malformed_rows"]["value"] == 0
    assert by_name["int8_share"]["ok"] is (not control)
    if control:
        assert by_name["int8_share"]["value"] > 0.6
