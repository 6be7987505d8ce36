"""The GLM-MoE-DSA configuration and its files: the configuration keeps
every number of its source but its depth and the chip's share, and
resolves to its cell; the operation and byte counts of
`cellbench/ops/glm_moe_dsa.py` against the issue's arithmetic at the
published size and by hand at a small one; the chunk spans with their
depth on plain lists; and a run of kind `serve_sparse` at toy size on
the CPU, every prompt chunked to several times the keys kept: sound,
control, and a token altered where it is produced."""
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.ops import glm_moe_dsa as ops
from cellbench.readers import chunk_depth
from cellbench.reference import glm_moe_dsa as ref
from mxnet_tpu import config

CELL = "glm-5.serve_long_prompts"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "n_routed_experts", "vocab_size"]
METRICS = {name + ".serve_sparse" for name in (
    "mla_attend_device_share", "dsa_index_device_share",
    "dsa_select_device_share", "moe_device_share",
    "dsa_index_chunk_roofline", "dsa_select_chunk_roofline",
    "mla_attend_chunk_roofline", "mla_attend_step_roofline",
    "moe_experts_roofline", "chunk_program_roofline",
    "decode_program_roofline", "dsa_selected_share",
    "moe_pairs_here_share", "prefill_chunks_per_request",
    "chunk_rows_real_share", "decode_steps_per_token",
    "chunk_step_host_ms", "decode_step_host_ms", "device_idle_share",
    "peak_hbm_gb", "compiles_in_window", "serve_itl_p99_ms")}


def _small():
    with open(os.path.join(run.HERE, "configs", "glm-5.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
               qk_head_dim=16, v_head_dim=8, index_n_heads=3,
               index_head_dim=8, index_topk=8, intermediate_size=48,
               moe_intermediate_size=16, n_routed_experts=4,
               router_outputs=8, routed_experts_first=2,
               num_experts_per_tok=3, num_hidden_layers=3,
               first_k_dense_replace=1, vocab_size=97,
               max_position_embeddings=128, initializer_range=0.3,
               compute_dtype="float32")
    return cfg


SMALL = _small()
# float32 at toy size, for the reason cellbench/tests/test_lfm2_moe.py
# gives: bfloat16 moves a token across a near-tie in a toy router (and
# here in a toy selection) in most runs. Every prompt is longer than
# the chunk (8) and 3 to 6 times the 8 keys kept. Sound float32 runs
# read gaps of 0, logit_err under 1e-4 and |int8_share| under 1e-2; the
# int8 twin reads int8_share 1.
DECK = {"kind": "serve_sparse", "callers": 4, "slots": 2, "max_len": 64,
        "queue_cap": 64, "prefill_chunk": 8,
        "prompt_lengths": [24, 32, 40, 48],
        "output_lengths": [2, 3, 4, 6], "blocks": 4, "warm_requests": 8,
        "window_opens_after_s": 0, "check_requests": 4,
        "limits": {"gap_widest": 0.01, "gap_mean": 0.001,
                   "logit_err": 0.005, "int8_share": 0.3}}
POOL = {"slots": 3, "max_len": 40, "prefill_chunk": 8,
        "prompt_lengths": [16, 24], "output_lengths": [4, 8]}


@pytest.fixture(autouse=True)
def _chunk_restored():
    """The family's builder sets the program's own MXNET_PREFILL_CHUNK
    from the traffic file; a test leaves it as it found it."""
    yield
    config.set_override("MXNET_PREFILL_CHUNK", None)


@pytest.fixture(scope="module")
def published():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    return run.resolve(manifest, CELL)


def test_the_cell_resolves_to_its_files(published):
    cell, entry, cfg, traffic = published
    assert cell["chips"] == 1
    assert cell["traffic"] == "rag_deck_long_prompts_4x16896"
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert cfg["family"] == "glm_moe_dsa"
    assert entry["source"] == cfg["source"]
    # kind serve_mixed's run with two more counters read: see
    # cellbench/drive/serve_sparse.py
    assert traffic["kind"] == "serve_sparse"
    assert (traffic["callers"], traffic["slots"], traffic["max_len"],
            traffic["queue_cap"], traffic["prefill_chunk"]) == \
        (6, 4, 16896, 64, 512)
    assert traffic["prompt_lengths"] == [8192, 10240, 12288, 16384]
    assert traffic["output_lengths"] == [32, 64, 64, 128]
    assert (traffic["blocks"], traffic["warm_requests"],
            traffic["check_requests"], traffic["window_opens_after_s"],
            traffic["callers_start_after_s"]) == (8, 8, 4, 110, 75)
    # every prompt is a whole number of chunks and at least four times
    # the keys kept
    assert all(p >= 4 * cfg["index_topk"] and
               p % traffic["prefill_chunk"] == 0
               for p in traffic["prompt_lengths"])
    assert 16384 + 128 <= traffic["max_len"]
    assert set(traffic["limits"]) == {"gap_widest", "gap_mean",
                                      "logit_err", "int8_share"}
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    e2e = {m["name"] for m in
           run.metrics_for(manifest, "end_to_end", CELL)}
    assert {"serve_tokens_per_s", "setup_s"} <= e2e <= {
        "serve_tokens_per_s", "serve_itl_p50_ms", "setup_s"}
    layer = run.metrics_for(manifest, "per_layer", CELL)
    assert METRICS <= {m["name"] for m in layer}
    assert all(m["workloads"] == [CELL] and m["moves"] in e2e
               for m in layer)
    assert len(manifest["workloads"]) == 8
    assert not any(w["chips"] == 4 for w in manifest["workloads"])


def test_the_configuration_keeps_every_number_but_its_cut(published):
    _cell, _entry, cfg, _traffic = published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["router_outputs"],
            cfg["routed_experts_first"], cfg["vocab_size"]) == \
        (6, 1, 16, 256, 0, 19360)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"],
            pub["n_routed_experts"], pub["vocab_size"]) == \
        (78, 3, 256, 154880)
    # every width as published; the floors kept
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"], cfg["index_topk"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == \
        (6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 12288, 2048,
         8, 2.5)
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    for key in ("reduced", "published", "deployment", "assumed",
                "omitted"):
        assert cfg[key]
    assert "sixteen v5e chips" in cfg["deployment"]
    assert "4727.34 M" in cfg["deployment"]
    assert "num_nextn_predict_layers" in cfg["omitted"]
    for key in ("weights", "initializer_range", "indexer_inputs",
                "indexer_key_norm", "indexer_head_weights",
                "rotary_channels", "rotary_layout", "selection_ties",
                "indexer_rotation_and_fp8", "router"):
        assert cfg["assumed"][key]


def test_counts_at_the_published_size_are_the_issue_s(published):
    _cell, _entry, cfg, traffic = published
    assert ops.expert_params(cfg) == 3 * 6144 * 2048 == 37748736
    assert round(ops.param_count(cfg) / 1e6, 2) == 4727.34
    assert round(ops.weight_bytes(cfg) / 1e9, 3) == 9.455
    # a whole expert layer does not fit a chip
    s = ref.sizes(cfg)
    whole = ops._sublayer_params("mla", s) + \
        ops._sublayer_params("experts", s) + 256 * ops.expert_params(cfg)
    assert round(2 * whole / 1e9, 2) == 19.75
    per_slot = ops.state_bytes_per_slot(cfg, traffic)
    assert sum(per_slot.values()) == 6 * 16896 * 1408
    assert round(4 * sum(per_slot.values()) / 1e9, 2) == 0.57
    # whole key and value heads would be 47 times the rows kept
    assert round(64 * 512 * 2 / 1408) == 47
    # a chunk of 512 at a depth of 8 192: its products about 1.6 TFLOP
    # over 9.2 GB of weights, the two bounds near each other
    flops, nbytes = ops.chunk_products_need(cfg, traffic, (8192, 8704), 1)
    assert 1.5e12 < flops < 1.7e12 and 9.0e9 < nbytes < 9.5e9
    assert 0.6 < (flops / 197e12) / (nbytes / 819e9) < 0.9
    index, _ = ops.dsa_index_chunk_need(cfg, traffic, (8192, 8704), 1)
    attend, _ = ops.mla_attend_chunk_need(cfg, traffic, (8192, 8704), 1)
    assert 0.2e12 < index < 0.3e12 and 0.8e12 < attend < 1.0e12
    # the indexer's scores grow with the depth, the attention does not
    deep, _ = ops.dsa_index_chunk_need(cfg, traffic, (15872, 16384), 1)
    far, _ = ops.mla_attend_chunk_need(cfg, traffic, (15872, 16384), 1)
    assert deep > 1.5 * index and far == attend
    # the first chunk sees at most 512 rows a query
    first, _ = ops.mla_attend_chunk_need(cfg, traffic, (0, 512), 1)
    assert first < attend / 4
    # rows run count: four rows are four times one
    four, _ = ops.chunk_forward_need(cfg, traffic, (8192, 8704), 4)
    one, _ = ops.chunk_forward_need(cfg, traffic, (8192, 8704), 1)
    assert four == pytest.approx(4 * one)
    # a step reads about 4 GB: the weights outside the routed experts,
    # the experts hit, every index row and 2 048 latent rows a slot
    step = dict(traffic, measured={"experts_hit_per_layer_step": 2.0,
                                   "pairs_here_per_layer_step": 2.0})
    _f, nbytes = ops.decode_step_need(cfg, step)
    _f, keys = ops.mla_attend_step_need(cfg, step)
    assert 3.0e9 < nbytes < 4.5e9
    assert 0.05 < keys / nbytes < 0.2


def test_counts_by_hand_at_a_small_size():
    d, v = 32, 97
    mixer = 24 * d + 24 + 4 * 16 * 24 + 20 * d + 16 + 4 * 20 * 16 + \
        d * 4 * 8
    indexer = 3 * 8 * 24 + 8 * d + 8 + 8 + 3 * d
    dense = 3 * d * 48
    expert = 3 * d * 16
    assert ops.param_count(SMALL) == 2 * v * d + d + 3 * (
        mixer + indexer + d) + (d + dense) + 2 * (
            d + d * 8 + 8 + expert + 4 * expert)
    assert ops.state_bytes_per_slot(SMALL, POOL) == {
        "latent_rows": 3 * 40 * 20 * 2, "index_rows": 3 * 40 * 8 * 2}
    assert ops.pairs_here(SMALL, POOL) == 3 * 3 * 4 / 8
    # rows seen and rows attended by the queries of a span
    assert ops._visible(0, 4) == 1 + 2 + 3 + 4
    assert ops._visible(10, 12) == 11 + 12
    assert ops._selected(0, 4, 8) == 10
    assert ops._selected(6, 10, 8) == 7 + 8 + 8 + 8
    assert ops._selected(20, 24, 8) == 32
    # one mixer, 2 rows, queries 16 .. 23 of a chunk
    s = ref.sizes(SMALL)
    seen = 2 * sum(range(17, 25))
    flops, nbytes = ops._index(s, 2, 16, 24)
    assert flops == 2 * 16 * indexer + seen * 3 * (2 * 8 + 2)
    assert nbytes == 2 * (indexer + 16 * (d + 24) + 2 * 24 * 8 + 16 * 8) \
        + 4 * seen
    assert ops._select(s, 2, 16, 24) == (0, 4 * (seen + 2 * 8 * 8))
    flops, nbytes = ops._attend(s, 2, 16, 24)
    assert flops == 2 * 16 * 4 * 20 * 16 + 2 * 8 * 8 * 4 * 2 * (20 + 16)
    assert nbytes == 2 * (4 * 20 * 16 + 2 * 24 * 20 + 16 * 20 +
                          16 * 4 * (16 + 8))
    # all three mixers, by the chunk's span
    assert ops.mla_attend_chunk_need(SMALL, POOL, (16, 24), 2) == \
        (3 * flops, 3 * nbytes)


def test_chunk_spans_give_each_forward_its_span_and_rows():
    chunks = [(0.0, 0, 4, 3), (1.0, 4, 8, 3), (2.0, 8, 10, 3)]
    assert chunk_depth.as_prefills(chunks) == [
        (0.0, (0, 4), 3), (1.0, (4, 8), 3), (2.0, (8, 10), 3)]
    # the parent's spans carry no `run`: nothing to read, no error
    assert chunk_depth.as_prefills(
        [(s, lo, hi, None) for s, lo, hi, _run in chunks]) == []
    assert chunk_depth.read({}, "roofline") is None
    assert chunk_depth.read({"trace": {}}, "roofline") is None


@pytest.mark.parametrize("control", [False, True])
def test_the_cell_at_toy_size_sound_and_control(control):
    """The drive, the program and the reference together, every prompt
    fed by chunks to several times the keys kept: a sound run is
    `correct`; the control (the reference's int8 twin read in the
    program's place) is not, by `int8_share`."""
    res = run.run_cell(SMALL, dict(DECK), 2 ** 31 + 5, 1.5,
                       control=control)
    by_name = {c["name"]: c for c in res["checks"]}
    assert res["correct"] is (not control)
    assert by_name["malformed_rows"]["value"] == 0
    assert by_name["failed_requests"]["value"] == 0
    assert by_name["int8_share"]["ok"] is (not control)
    r = res["readings"]
    assert r["stats.steps"] > 0 and r["client.tokens"] > 0
    assert set(res["end_to_end"]) == {
        "serve_tokens_per_s", "serve_itl_p50_ms", "serve_itl_p99_ms",
        "setup_s"}
    assert res["end_to_end"]["serve_tokens_per_s"] > 0
    # two expert layers, 2 rows x 3 pairs a layer and step
    assert r["stats.moe_assignments"] == r["stats.steps"] * 2 * 2 * 3
    assert 0 < r["stats.moe_pairs_here"] < r["stats.moe_assignments"]
    # every prefill of the window went by chunks (3 to 6 of them), one
    # row a chunk
    assert 3 * r["stats.prefills"] <= r["stats.chunks"] <= \
        6 * r["stats.prefills"]
    assert r["stats.chunk_rows"] == r["stats.chunks"]
    # the steps' queries attended 8 keys of the 25 and more they saw
    # (an idle row's one of one among them)
    assert 0 < r["stats.dsa_keys_selected"] < \
        r["stats.dsa_keys_visible"] / 2
    from cellbench.readers import counter
    assert 0 < counter.read(r, "stats.dsa_keys_selected",
                            "stats.dsa_keys_visible", 100.0) < 50
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


def test_the_int8_twin_differs_from_the_reference_and_is_the_same_model():
    """At toy size a rounded weight moves which 8 keys of 40 a query
    keeps, and a row's logits with them: the largest difference reads
    0.8 to 3.7 of the logits' spread over three seeds, the mean 0.09
    to 0.31 (two unrelated models would read 1.1)."""
    toks = np.arange(80, dtype=np.int32).reshape(2, 40) % 97
    where = np.tile(np.arange(30, 36), (2, 1))
    plain = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32"))
    twin = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32",
                                    int8=True))
    assert plain.shape == twin.shape == (2, 6, 97)
    assert np.abs(plain - twin).max() > 1e-4 * plain.std()
    assert np.abs(plain - twin).mean() < 0.5 * plain.std()


def test_a_block_the_reference_does_not_write_down_is_refused():
    assert ref.sizes(SMALL)["kinds"] == (
        "mla", "mlp", "mla", "experts", "mla", "experts")
    for bad in (dict(n_group=2), dict(scoring_func="softmax"),
                dict(rope_interleave=False), dict(n_shared_experts=2),
                dict(tie_word_embeddings=True)):
        with pytest.raises(ValueError, match="assumed"):
            ref.sizes(dict(SMALL, **bad))
