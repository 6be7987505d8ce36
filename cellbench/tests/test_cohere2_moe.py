"""The Cohere2-MoE configuration and its files: the configuration keeps
every number of its source but its depth and the chip's share, and
resolves to its cell; the operation and byte counts of
`cellbench/ops/cohere2_moe.py` against the issue's arithmetic at the
published size and by hand at a small one; the chunk spans on plain
lists; and a run of kind `serve_mixed` at toy size on the CPU, every
prompt chunked past a toy window: sound, control, and a token altered
where it is produced."""
import json
import os

import numpy as np
import pytest

from cellbench import run
from cellbench.ops import cohere2_moe as ops
from cellbench.readers import chunk_spans
from cellbench.reference import cohere2_moe as ref

CELL = "command-a-plus-05-2026.serve_long_prompts"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types", "num_experts",
           "vocab_size"]
METRICS = {name + ".serve_mixed" for name in (
    "attn_window_device_share", "attn_full_device_share",
    "moe_device_share", "attn_window_chunk_roofline",
    "attn_full_chunk_roofline", "chunk_program_roofline",
    "decode_program_roofline", "moe_experts_roofline",
    "moe_pairs_here_share",
    "prefill_chunks_per_request", "chunk_rows_real_share",
    "chunk_step_host_ms", "decode_step_host_ms",
    "decode_steps_per_token", "device_idle_share", "peak_hbm_gb",
    "serve_itl_p99_ms",
    # twins of accepted metrics, whose readers find something to read
    # in a pool whose every prompt is chunked (an accepted metric's
    # own file lists its cells, and is not this PR's to edit)
    "prefill_rows_real_share", "admit_wall_share",
    "idle_under_admit_share", "compiles_in_window",
    "prefills_per_request")}


def _small():
    with open(os.path.join(run.HERE, "configs",
                           "command-a-plus-05-2026.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, num_attention_heads=8,
               num_key_value_heads=2, head_dim=8, intermediate_size=16,
               num_experts=4, router_outputs=8, routed_experts_first=2,
               num_experts_per_tok=3, num_shared_experts=2,
               vocab_size=97, sliding_window=8,
               max_position_embeddings=64, initializer_range=0.3,
               compute_dtype="float32")
    return cfg


SMALL = _small()
# float32 at toy size, for the reason cellbench/tests/test_lfm2_moe.py
# gives: bfloat16 moves a token across a near-tie in a toy router in
# most runs. Every prompt is longer than the chunk (4), than the window
# (8) and all but the first than the circular buffer (8 + 4 - 1 -> 16
# rows). Sound float32 runs read gaps of 0, logit_err under 1e-5 and
# |int8_share| under 1e-3; the int8 twin reads int8_share 1.
DECK = {"kind": "serve_mixed", "callers": 4, "slots": 2, "max_len": 48,
        "queue_cap": 64, "prefill_chunk": 4,
        "prompt_lengths": [12, 20, 24, 36],
        "output_lengths": [2, 3, 4, 6], "blocks": 4, "warm_requests": 8,
        "window_opens_after_s": 0, "check_requests": 4,
        "limits": {"gap_widest": 0.01, "gap_mean": 0.001,
                   "logit_err": 0.005, "int8_share": 0.3}}
POOL = {"slots": 3, "max_len": 40, "prefill_chunk": 4,
        "prompt_lengths": [12, 20], "output_lengths": [4, 8]}


@pytest.fixture(scope="module")
def published():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    return run.resolve(manifest, CELL)


def test_the_cell_resolves_to_its_files(published):
    cell, entry, cfg, traffic = published
    assert cell["chips"] == 1
    assert cell["traffic"] == "rag_deck_long_prompts_4x8448"
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert cfg["family"] == "cohere2_moe"
    assert entry["source"] == cfg["source"]
    assert traffic["kind"] == "serve_mixed"
    assert (traffic["callers"], traffic["slots"], traffic["max_len"],
            traffic["queue_cap"], traffic["prefill_chunk"]) == \
        (6, 4, 8448, 64, 256)
    assert traffic["prompt_lengths"] == [4352, 5120, 6144, 8192]
    assert traffic["output_lengths"] == [32, 64, 64, 128]
    assert (traffic["blocks"], traffic["warm_requests"],
            traffic["window_opens_after_s"]) == (8, 8, 110)
    assert 0 < traffic["callers_start_after_s"] < 110
    assert 4 <= traffic["check_requests"] <= 8
    # every prompt is past the window and a whole number of chunks
    assert all(p > cfg["sliding_window"] and
               p % traffic["prefill_chunk"] == 0
               for p in traffic["prompt_lengths"])
    assert 8192 + 128 <= traffic["max_len"]
    assert set(traffic["limits"]) == {"gap_widest", "gap_mean",
                                      "logit_err", "int8_share"}
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    e2e = {m["name"] for m in
           run.metrics_for(manifest, "end_to_end", CELL)}
    # tokens a second end to end (the kind counts an answer where it
    # begins, between first-token marks: PERF.md §4), and the gap
    assert e2e == {"serve_tokens_per_s", "serve_itl_p50_ms", "setup_s"}
    layer = run.metrics_for(manifest, "per_layer", CELL)
    assert METRICS <= {m["name"] for m in layer}
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].startswith("tokens_per_s")]
    assert all(m["workloads"] == [CELL] and m["moves"] in e2e
               for m in layer if m["name"] in METRICS)
    assert len(manifest["workloads"]) == 7
    assert not any(w["chips"] == 4 for w in manifest["workloads"])


def test_the_configuration_keeps_every_number_but_its_cut(published):
    _cell, _entry, cfg, _traffic = published
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    types = row["config"]["layer_types"]
    # one whole period, in the published order and ratio
    assert cfg["layer_types"] == types[:4] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert types == types[:4] * 8
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_outputs"], cfg["routed_experts_first"],
            cfg["vocab_size"]) == (4, 16, 128, 0, 32768)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (32, 128, 262144)
    # every width as published; the floors kept
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["sliding_window"],
            cfg["rope_theta"]) == (4096, 128, 8, 128, 4096, 8, 4, 4096,
                                   50000)
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    for key in ("reduced", "published", "deployment", "assumed",
                "omitted"):
        assert cfg[key]
    assert "eight v5e chips" in cfg["deployment"]
    assert "9.47 GB" in cfg["deployment"]
    assert "vision_tower" in cfg["omitted"]
    for key in ("weights", "initializer_range", "router",
                "shared_expert_combination_strategy",
                "shared_expert_width", "attention_position",
                "rotary_layout", "shared_layout"):
        assert cfg["assumed"][key]


def test_counts_at_the_published_size_are_the_issue_s(published):
    _cell, _entry, cfg, traffic = published
    d = 4096
    attn = d * 16384 * 2 + d * 1024 * 2
    expert = 3 * d * d
    layer = attn + d + d * 128 + 4 * expert + 16 * expert
    assert ops.expert_params(cfg) == expert == 50331648
    assert round(attn / 1e6, 2) == 142.61
    assert round(layer / 1e6, 1) == 1149.8
    assert ops.param_count(cfg) == 4 * layer + 32768 * d + d
    assert round(ops.param_count(cfg) / 1e6) == 4733
    assert ops.weight_bytes(cfg) == 2 * ops.param_count(cfg) + \
        4 * 128 * 4
    assert round(ops.weight_bytes(cfg) / 1e9, 2) == 9.47
    # the whole layer, and that a second period does not fit
    whole = attn + d + d * 128 + 4 * expert + 128 * expert
    assert round(whole / 1e9, 2) == 6.79
    assert 2 * (8 * layer + 32768 * d) / 1e9 > 16.9
    assert ops.ring_rows(cfg, traffic) == 4352 == 17 * 256
    per_slot = ops.state_bytes_per_slot(cfg, traffic)
    assert per_slot == {"kv_rows": 8448 * 4096,
                        "kv_window": 3 * 4352 * 4096}
    assert round(sum(per_slot.values()) / 1e6) == 88
    assert round(4 * 8448 * 4096 / 1e6) == 138      # four full layers
    # a chunk forward of 4 x 256 tokens: about 5 TFLOP, three tenths
    # of it attention; the window takes a third off four full layers
    flops, _ = ops.chunk_forward_need(cfg, traffic, 256)
    win, _ = ops.attn_window_chunk_need(cfg, traffic, 256)
    full, _ = ops.attn_full_chunk_need(cfg, traffic, 256)
    assert 4.8e12 < flops < 5.1e12
    assert round(full / 1e12, 2) == 0.57 and round(win / 1e12, 2) == 0.88
    assert 0.27 < (win + full) / flops < 0.32
    assert 0.6 < (win + full) / (4 * full) < 0.67
    # one real row of the four: a quarter of the work
    one, _ = ops.chunk_forward_need(cfg, traffic, 256, 1)
    assert one * 4 == pytest.approx(flops)
    # a step reads about 4.9 GB, of which a third the held experts hit
    step = dict(traffic, measured={"experts_hit_per_layer_step": 4.0,
                                   "pairs_here_per_layer_step": 4.0})
    _f, nbytes = ops.decode_step_need(cfg, step)
    _f, moe = ops.moe_experts_need(cfg, step)
    assert 3.9e9 < nbytes < 5.1e9
    assert 0.3 < moe / nbytes < 0.45
    assert ops.pairs_here(cfg, traffic) == 4 * 8 * 16 / 128
    # a step's sliding layers: the window's rows, never the buffer's
    _f, rows = ops.attn_window_step_need(cfg, traffic)
    assert rows < 3 * 4 * 4352 * 4096


def test_counts_by_hand_at_a_small_size():
    d, v, f, e, held = 32, 97, 16, 8, 4
    attn = (64 + 2 * 16) * d + d * 64
    outside = d + attn + d * e + 2 * 3 * d * f
    assert ops.param_count(SMALL) == v * d + d + 4 * (
        outside + held * 3 * d * f)
    assert ops.ring_rows(SMALL, POOL) == 16          # 8 + 4 - 1, by 8
    assert ops.state_bytes_per_slot(SMALL, POOL) == {
        "kv_rows": 2 * 2 * 8 * 2 * 40, "kv_window": 3 * 2 * 2 * 8 * 2 * 16}
    assert ops.pairs_here(SMALL, POOL) == 3 * 3 * 4 / 8
    hit = dict(POOL, measured={"experts_hit_per_layer_step": 2.5,
                               "pairs_here_per_layer_step": 5.0})
    flops, nbytes = ops.moe_experts_need(SMALL, hit)
    assert flops == 4 * 2 * 5 * 3 * d * f
    assert nbytes == 4 * 2 * (2.5 * 3 * d * f + 5 * (2 * d + 5 * f))
    flops_w, bytes_w = ops.attn_window_step_need(SMALL, hit)
    assert flops_w == 3 * 3 * 2 * 2 * 64 * 8
    assert bytes_w == 3 * 3 * ((8 + 1) * 2 * 16 * 2 + 2 * 64 * 2)
    flops_c, bytes_c = ops.attn_window_chunk_need(SMALL, hit, 4, 1)
    assert flops_c == 3 * 4 * 2 * 2 * 64 * 16
    assert bytes_c == 3 * ((16 + 4) * 2 * 16 * 2 + 4 * 2 * 64 * 2)
    flops_f, _ = ops.attn_full_chunk_need(SMALL, hit, 4)
    assert flops_f == 3 * 4 * 2 * 2 * 64 * 40


def test_chunk_spans_give_each_forward_its_tokens_and_rows():
    chunks = [(0.0, 0, 4, 3), (1.0, 4, 8, 3), (2.0, 8, 10, 3)]
    assert chunk_spans.as_prefills(chunks) == [
        (0.0, 4, 3), (1.0, 4, 3), (2.0, 2, 3)]
    # the parent's spans carry no `run`: nothing to read, no error
    assert chunk_spans.as_prefills(
        [(s, lo, hi, None) for s, lo, hi, _run in chunks]) == []
    assert chunk_spans.read({}, "roofline") is None
    assert chunk_spans.read({"trace": {}}, "roofline") is None


# five requests' token arrivals: prompts chunked one after the other, so
# first tokens come in the order sent; answers of 3, 1, 4, 2 tokens and
# one request that has none yet
_ARRIVALS = [[1.0, 1.5, 2.0], [2.0], [3.0, 3.5, 4.0, 9.0], [4.0, 4.5],
             None]


@pytest.mark.parametrize("edges, begun, tokens", [
    ((0.0, 4.0), [0, 1, 2, 3], 10),   # an answer still in flight at the
    ((1.0, 3.0), [1, 2], 5),          # close counts whole; one begun on
    ((2.0, 4.0), [2, 3], 6),          # the opening edge not at all
    ((4.0, 9.0), [], 0)])
def test_an_answer_counts_in_the_window_it_begins_in(edges, begun, tokens):
    from cellbench.drive import serve_mixed
    assert serve_mixed.first_tokens(_ARRIVALS) == {
        0: 1.0, 1: 2.0, 2: 3.0, 3: 4.0}
    assert serve_mixed.begun_in(_ARRIVALS, *edges) == (begun, tokens)


def test_two_windows_side_by_side_share_no_answer_and_lose_none():
    from cellbench.drive import serve_mixed
    whole = serve_mixed.begun_in(_ARRIVALS, 0.0, 4.0)[1]
    assert whole == sum(
        serve_mixed.begun_in(_ARRIVALS, a, b)[1]
        for a, b in [(0.0, 1.0), (1.0, 2.0), (2.0, 4.0)])


@pytest.mark.parametrize("control", [False, True])
def test_the_cell_at_toy_size_sound_and_control(control):
    """The drive, the program and the reference together, every prompt
    fed by chunks into circular rows that wrap: a sound run is
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
    # four layers, 2 rows x 3 pairs a layer and step, of which the
    # held half of the experts takes about half
    assert r["stats.moe_assignments"] == r["stats.steps"] * 4 * 2 * 3
    assert 0 < r["stats.moe_pairs_here"] < r["stats.moe_assignments"]
    # every prefill of the window went by chunks (3 to 9 of them) at
    # the pool's width of two rows
    assert 3 * r["stats.prefills"] <= r["stats.chunks"] <= \
        9 * r["stats.prefills"]
    assert r["stats.chunk_rows"] == 2 * r["stats.chunks"] == \
        r["stats.prefill_rows"]
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
    toks = np.arange(48, dtype=np.int32).reshape(2, 24) % 97
    where = np.tile(np.arange(10, 16), (2, 1))
    plain = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32"))
    twin = np.asarray(ref.logits_at(SMALL, 3, toks, where, "float32",
                                    int8=True))
    assert plain.shape == twin.shape == (2, 6, 97)
    assert 1e-4 * plain.std() < np.abs(plain - twin).max() < \
        0.5 * plain.std()


def test_a_number_of_shared_experts_the_loader_cannot_fold_is_refused():
    """`make_params` folds 1/m into the shared experts' downs, which
    is exact only where m is a power of two (4 as published)."""
    assert ref.sizes(dict(SMALL, num_shared_experts=4))["shared"] == 4
    with pytest.raises(ValueError, match="power of two"):
        ref.sizes(dict(SMALL, num_shared_experts=3))
