"""Compiles for a described v5e chip, with no chip attached: what the
TPU's own compiler makes of a program, which a CPU run cannot show.
Nothing runs, so these say nothing about results or times.

All such tests live in THIS file and describe the topology inside a
fixture: the TPU's library can be held by one process at a time, so
only the worker that is given this file may load it, and only once a
test of it has started."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.generation import Generator

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip("no v5e:2x2 topology can be described here: %s"
                    % (exc,))
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compile_cache_off():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def no_compile_cache():
    with _compile_cache_off():
        yield


def _entry_results(compiled):
    """(op, dtype, dims, minor_to_major) of every instruction of the
    compiled module's ENTRY computation whose result is an array."""
    import re
    text = compiled.as_text()
    out = []
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s+(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]"
                     r"(?:\{([\d,]*)[^}]*\})? ([\w-]+)\(", line)
        if m and m.group(2):
            out.append((m.group(4), m.group(1),
                        tuple(int(d) for d in m.group(2).split(",")),
                        tuple(int(d) for d in (m.group(3) or "").split(",")
                              if d)))
    return out


@pytest.mark.parametrize("slots,vocab", [(8, 50272), (32, 32768)],
                         ids=["opt", "nemotron"])
def test_next_tokens_is_one_small_program_on_v5e(one_chip,
                                                 no_compile_cache, slots,
                                                 vocab):
    """The program beside an autoregressive pool's step, at a cell's
    rows and vocabulary in bfloat16: the TPU compiler takes it, and it
    holds the step's logits, their float32 rows and little else."""
    from mxnet_tpu.serve.decode import _next_program

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _next_program(None).lower(
        spec((slots, 1, vocab), jnp.bfloat16),
        spec((slots, 1), jnp.float32), spec((slots,), bool)).compile()
    data, last = compiled.output_shardings   # two results, one chip
    assert data == last == one_chip
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 4 * slots * vocab
    assert mem.temp_size_in_bytes <= 8 * slots * vocab


# the two serve cells' pools: slots, positions, query heads, kv heads
# (x 64), and the longest prompt a prefill takes
POOLS = {"opt-1.3b.serve_saturated": (8, 1536, 32, 32, 1024),
         "granite-4.0-h-micro.attention": (16, 768, 32, 8, 256)}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_serve_programs_keep_a_token_contiguous_on_v5e(
        one_chip, no_compile_cache, pool):
    """The serving programs at a cell's cache shape (bf16, two layers,
    narrow FFN and vocabulary to keep it a few seconds), compiled for
    a v5e. `decode_step`: the donated program aliases every byte of
    the pool; each cache array lies token-contiguous (its minor-most
    axis is the row of Hkv*64 values); the per-row write is not the
    scatter the TPU compiler expands into a `while` that carries, and
    writes back, each whole cache array; and nothing copies or
    transposes a whole cache array on the way to the two products.
    `generator_step` at the longest prompt: no cache-shaped transpose,
    and no temporary the size of the scores of all heads at once."""
    from cellbench.reference import opt as ref
    slots, length, heads, kv_heads, prompt = POOLS[pool]
    row = kv_heads * 64
    cfg = {"hidden_size": 2048, "num_attention_heads": heads,
           "ffn_dim": 2048, "vocab_size": 1024, "num_hidden_layers": 2,
           "max_position_embeddings": length}
    params = {n: a[:2048 + 2 * row] if "_qkv_" in n else a
              for n, a in ref.make_params(cfg, 1, "bfloat16").items()}
    gen = Generator(params, 1024, length, num_layers=2, num_heads=heads,
                    num_kv_heads=kv_heads, dim=2048, ffn_hidden=2048,
                    batch_size=slots, dtype="bfloat16")

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def inputs(data, positions, cache_pos):
        args = {n: spec(a) for n, a in gen._params.items()}
        for name, shape in (("data", data), ("positions", positions),
                            ("cache_pos", cache_pos)):
            args[name] = jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
        return args

    with gen.serving_decoder() as dec:
        aux = {n: spec(a) for n, a in dec._aux.items()}
        step = dec._step_fn.lower(
            inputs((slots, 1), (slots, 1), (slots,)), aux,
            spec(dec._rng0)).compile()
        prefill = gen._step_fn.lower(
            inputs((slots, prompt), (prompt,), (1,)), aux,
            spec(dec._rng0)).compile()
    cache = (slots, length, row)
    assert {a.shape for a in aux.values()} == {cache}
    held = 4 * int(np.prod(cache)) * 2
    assert step.memory_analysis().alias_size_in_bytes == held
    text = step.as_text()
    assert " while(" not in text
    assert text.count(" dynamic-update-slice(") >= 4 * slots
    whole = int(np.prod(cache))
    results = _entry_results(step)
    params_seen = [r for r in results
                   if r[0] == "parameter" and r[2] == cache]
    assert len(params_seen) == 4
    for op, _dtype, dims, minor_to_major in results:
        if dims == cache:
            # a token's row is the lane axis wherever the array goes
            assert minor_to_major[0] == 2, (op, minor_to_major)
        if int(np.prod(dims)) >= whole:
            assert op not in ("copy", "transpose"), (op, dims)
    # nothing cache-sized beside the pool: written where it lies
    assert step.memory_analysis().temp_size_in_bytes < whole * 2
    for op, _dtype, dims, _layout in _entry_results(prefill):
        if int(np.prod(dims)) >= whole:
            assert op != "transpose", (op, dims)
    scores = slots * heads * prompt * length * 4
    assert prefill.memory_analysis().temp_size_in_bytes < scores // 2


# the command-a cell's chunk forward: 256 new rows of 128 query heads
# over 8 kv heads of 128, against a sliding layer's circular buffer and
# the full layer's rows (columns)
CHUNK_ATTENTION = {"sliding": 4352, "full": 8448, "full_unsplit": 8448}


@pytest.mark.parametrize("layer", sorted(CHUNK_ATTENTION))
def test_a_map_step_s_scores_stay_out_of_hbm_on_v5e(
        one_chip, no_compile_cache, monkeypatch, layer):
    """`_attend`'s prefill path at the command-a cell's two shapes,
    compiled for a v5e: a step of the map keeps its float32 scores (a
    sliding layer's 71.3 MB whole, the full layer's 138.4 MB as two
    blocks of 2 048 rows) in fast memory, so the program has no
    temporary in HBM to speak of; with the budget lifted the full
    layer's scores are one 138.4 MB temporary, which is what the
    budget is for."""
    from mxnet_tpu.ops import attention
    C = CHUNK_ATTENTION[layer]
    if layer == "full_unsplit":
        monkeypatch.setattr(attention, "_SCORE_BYTES", 1 << 40)

    def attend(q, k, v, pos):
        return attention._attend(
            q, k, v, attention._causal(pos, 256, C, 0), 128 ** -0.5)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    before = attention.split_traces()
    compiled = jax.jit(attend).lower(
        spec((1, 128, 256, 128)), spec((1, C, 1024)), spec((1, C, 1024)),
        spec((), jnp.int32)).compile()
    assert attention.split_traces() - before == (layer == "full")
    temp = compiled.memory_analysis().temp_size_in_bytes
    if layer == "full_unsplit":
        assert temp >= 4096 * C * 4
    else:
        assert temp < 4 << 20


@pytest.mark.parametrize("tokens", [16 * 4, 16 * 8, 16 * 60])
def test_expert_layer_compiles_for_v5e_without_a_dense_buffer(
        one_chip, no_compile_cache, monkeypatch, tokens):
    """One routed expert layer at the SDAR cell's widths (2048 -> 128
    experts of 768, top 8, bf16), a block's 64 positions, the pool's
    step of two blocks a row (128) and the shortest prefill's 960: Mosaic takes the grouped product's tiles at
    these shapes, the two products are the kernel and not a loop XLA
    wrote, and nothing the size of (experts, tokens, width) exists."""
    from mxnet_tpu.ops import _pallas
    from mxnet_tpu.parallel.moe import routed_experts
    # a compile for a described chip still sees the CPU backend
    monkeypatch.setattr(_pallas, "interpret", lambda: False)
    D, E, H, K = 2048, 128, 768, 8

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    layer = jax.jit(lambda x, g, w1, w2: routed_experts(
        x, g, w1, w2, top_k=K, act="gated_silu", renormalize=True))
    compiled = layer.lower(spec(tokens, D), spec(D, E),
                           spec(E, D, 2 * H), spec(E, H, D)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    # the sorted pairs' rows and outputs, a few times over: far under
    # one row of width D for every (expert, token)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 8 * tokens * K * (D + 2 * H) * 2
    assert temp < E * tokens * D * 2 // 4


@pytest.mark.parametrize("tokens", [32, 32 * 32])
def test_a_share_of_latent_experts_compiles_for_v5e(
        one_chip, no_compile_cache, monkeypatch, tokens):
    """One expert layer at the Nemotron cell's widths (4096 -> a router
    of 512, top 22; 128 of the experts held, 1024 -> 2688 -> 1024 in
    the latent width; a shared expert of 5376), a decode step's 32
    rows and a prefill's 1 024: Mosaic takes the grouped products'
    tiles, the ragged contraction over 2688 among them, and the
    temporaries follow the pairs, not experts x tokens."""
    from mxnet_tpu.ops import _pallas
    from mxnet_tpu.parallel.moe import routed_experts
    monkeypatch.setattr(_pallas, "interpret", lambda: False)
    D, E, HELD, K, Z, H, HS = 4096, 512, 128, 22, 1024, 2688, 5376

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = jax.jit(lambda x, g, w1, w2, b, dn, up, p, q: routed_experts(
        x, g, w1, w2, top_k=K, act="relu2", renormalize=True,
        scoring="sigmoid", score_bias=b, scale=5.0, first_expert=128,
        latent=(dn, up), shared=(p, q)))
    compiled = layer.lower(
        spec(tokens, D), spec(D, E), spec(HELD, Z, H), spec(HELD, H, Z),
        spec(E, dtype=jnp.float32), spec(D, Z), spec(Z, D), spec(D, HS),
        spec(HS, D)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 8 * tokens * K * (Z + H) * 2 + 2 ** 20
    assert temp < HELD * tokens * Z * 2


@pytest.mark.parametrize("tokens", [1, 256])
def test_mamba2_in_eight_groups_compiles_for_v5e(
        one_chip, no_compile_cache, tokens):
    """The Nemotron cell's mixer core (128 heads x 64, state 128, 8
    groups, chunks of 128) over 32 rows: the one-token step and a
    prefill's chunked scan; the step keeps no second copy of the 134
    MB of scan state beside the one it updates."""
    from mxnet_tpu.ops import mamba2
    B, H, P, N, G, K = 32, 128, 64, 128, 8, 4
    conv = H * P + 2 * G * N

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mix = jax.jit(lambda *a: mamba2.mamba2_mix(
        *a, num_heads=H, head_dim=P, d_state=N, chunk=128, n_groups=G),
        donate_argnums=(7, 8))
    compiled = mix.lower(
        spec(B, tokens, conv), spec(B, tokens, H), spec(conv, K),
        spec(conv), spec(H), spec(H), spec(H), spec(B, K - 1, conv),
        spec(B, H, P, N, dtype=jnp.float32)).compile()
    state = B * H * P * N * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (state // 2 if tokens == 1 else 16 * state)


@pytest.mark.parametrize("tokens", [1, 256])
def test_the_gated_short_convolution_compiles_for_v5e(
        one_chip, no_compile_cache, tokens):
    """The LFM2 cell's operator (2 048 channels, 3 taps) over 16 rows:
    the one-token step and the longest prefill; the window is updated
    in place, and a prefill keeps a few (rows, positions, 3 x 2 048)
    activations beside it and nothing larger."""
    from mxnet_tpu.ops import shortconv
    B, D, K = 16, 2048, 3

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=one_chip)

    op = jax.jit(shortconv.short_conv, donate_argnums=4)
    compiled = op.lower(spec(B, tokens, D), spec(3 * D, D), spec(D, K),
                        spec(D, D), spec(B, K - 1, D)).compile()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= B * (K - 1) * D * 2
    assert stats.temp_size_in_bytes < 8 * B * tokens * 3 * D * 2 + 2 ** 20


@pytest.mark.parametrize("rows,tokens", [(1, 512), (4, 1)],
                         ids=["chunk", "step"])
def test_the_latent_mixer_over_column_blocks_compiles_for_v5e(
        one_chip, no_compile_cache, rows, tokens):
    """The GLM-5 cell's mixer (64 heads, latents of 2 048 and 512, 32
    index heads of 128, 2 048 keys kept of a buffer of 16 896) at its
    two shapes: a chunk of 512 queries runs blocks of 512 columns in
    loops, the step blocks of a quarter of the buffer; both update the
    two caches in place, and the chunk's temporaries are the three (1,
    512, 16 896) arrays of the selection (scores, their ordered form,
    the mask) and little else: a block's float32 scores and the 32
    heads' accumulator stay in fast memory; the step keeps no copy of
    its 77.9 MB of latent rows (one block of the whole buffer kept
    one, in another layout, and wrote it back)."""
    from mxnet_tpu.ops import mla, shape_hooks
    D, H, C = 6144, 64, 16896
    sizes = dict(q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
                 qk_rope_head_dim=64, v_head_dim=256, index_heads=32,
                 index_head_dim=128)
    assert mla._block_width(tokens, H, C) == (512 if tokens > 1 else C // 4)
    shapes = shape_hooks._latent_select_shapes(
        [(rows, tokens, D)] + [None] * 16,
        dict(sizes, num_heads=H, max_len=C))

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def mixer(x, weights, latent, index, pos):
        return mla.latent_select_attention(
            x, pos[:, None] + jnp.arange(tokens, dtype=jnp.float32),
            dict(zip(mla._WEIGHTS, weights)), latent, index, pos,
            num_heads=H, index_topk=2048, rope_base=1e6,
            **{k: sizes[k] for k in (
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "index_heads")})

    compiled = jax.jit(mixer, donate_argnums=(2, 3)).lower(
        spec(shapes[0]), [spec(s) for s in shapes[2:14]],
        spec(shapes[14]), spec(shapes[15]),
        spec((rows,), jnp.int32)).compile()
    stats = compiled.memory_analysis()
    caches = rows * C * (576 + 128) * 2
    assert stats.alias_size_in_bytes >= caches
    # read here: 90.0 MB for the chunk (the three arrays are 77.9) and
    # 1.8 MB for the step
    selection = 512 * C * (4 + 4 + 1)
    assert stats.temp_size_in_bytes < (
        selection + (32 << 20) if tokens > 1 else 16 << 20)


@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static",
                                  "low_confidence_dynamic"])
def test_block_step_with_the_rows_state_compiles_for_v5e(
        one_chip, no_compile_cache, monkeypatch, rule):
    """The diffusion pool's step at the SDAR cell's pool (16 slots x
    1 024 positions, blocks of 4, two steps a block; one layer, 16
    experts and a short vocabulary to keep it seconds): the program
    that forms its inputs from the rows' block state, unmasks by the
    rule and advances the state compiles for a v5e, with the grouped
    products the kernel; it updates every byte of the pool and of the
    state it was given in place, and so does the program that writes an
    admitted row's state."""
    import json
    import os
    from cellbench.models import sdar as model
    from cellbench.reference import sdar as ref
    from mxnet_tpu.ops import _pallas
    # a compile for a described chip still sees the CPU backend
    monkeypatch.setattr(_pallas, "interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=1, vocab_size=8192, num_experts=16)
    cfg["assumed"] = dict(cfg["assumed"], mask_token_id=8191)
    gen = Generator(ref.make_params(cfg, 1, "bfloat16"), 8192, 1024,
                    batch_size=16, dtype="bfloat16",
                    **model.generator_args(cfg, {"denoising_steps": 2,
                                                 "remasking": rule}))

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    with gen.serving_decoder() as dec:
        state = {n: spec(a) for n, a in dec._bstate.items()}
        held = ({n: spec(a) for n, a in dec._aux.items()}, state)
        step = dec._step_fn.lower(
            {n: spec(a) for n, a in gen._params.items()}, held,
            spec(dec._rng0)).compile()
        admit = dec._block_admit_fn.lower(
            state, state, jax.ShapeDtypeStruct(
                (16,), bool, sharding=one_chip)).compile()
    nbytes = lambda tree: sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(tree))
    assert step.as_text().count("tpu_custom_call") >= 2
    # the device pads the small arrays: at least their bytes
    assert step.memory_analysis().alias_size_in_bytes >= nbytes(held)
    assert admit.memory_analysis().alias_size_in_bytes >= nbytes(state)
    # nothing pool-sized beside the pool
    assert step.memory_analysis().temp_size_in_bytes < \
        nbytes(held[0]) // 2


@pytest.fixture(scope="module")
def named_train_step(one_chip):
    """[(name stack, whether it holds a convolution)] for every fusion
    of a toy ResNet's `step_with_metric` compiled for a v5e."""
    import re
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.initializer import Uniform
    from mxnet_tpu.parallel import make_train_step
    shapes = {"data": (8, 3, 32, 32), "softmax_label": (8,)}
    step = make_train_step(
        models.get_symbol(network="resnet", num_layers=18,
                          image_shape=(3, 32, 32), num_classes=10),
        optimizer="sgd", optimizer_params={"momentum": 0.9},
        compute_dtype="bfloat16")
    state = step.init_state(Uniform(0.01), shapes)
    metric = mx.metric.CrossEntropy()
    raw, fused = step._metric_fused_step(metric, None)
    placed = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    rng = jax.random.PRNGKey(0)
    mstats = step._zero_metric_stats(raw, metric, state, placed, 0.1, rng)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        (*state, placed, jnp.float32(0.1), rng, mstats))
    with _compile_cache_off():
        text = fused.lower(*args).compile().as_text()
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"\n%?([\w.\-]+) \([^\n]*\{\n(.*?)\n\}", text, re.S)}
    out = []
    for line in text[text.index("\nENTRY"):].splitlines():
        if " fusion(" not in line:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        body = bodies.get(re.search(r"calls=%?([\w.\-]+)",
                                    line).group(1), "")
        out.append((name.group(1) if name else "",
                    " convolution(" in body))
    return out


@pytest.mark.parametrize("wrapper", ["jvp(train.fwd)",
                                     "transpose(jvp(train.fwd))"])
def test_the_tpu_compiler_keeps_the_names_on_its_fusions(
        named_train_step, wrapper):
    """What the device trace will show (`tf_op` is this metadata): each
    fusion of the compiled step carries one name stack, every one of
    them with an `op.` or `train.` part, and a fusion that holds a
    convolution is named after a Convolution or FullyConnected node,
    forward and backward, not after the batch norm, ReLU or sum fused
    into it."""
    fusions = named_train_step
    assert len(fusions) > 100
    kinds = lambda s: [p for p in s.split("/")
                       if p.startswith(("op.", "train."))]
    assert all(kinds(name) for name, _conv in fusions)
    convs = [name for name, conv in fusions
             if conv and ("/%s/" % wrapper) in name]
    assert len(convs) >= 20                   # 21 convolutions a pass
    for name in convs:
        assert kinds(name)[0] in ("op.Convolution",
                                  "op.FullyConnected"), name
    assert any("train.update" in name.split("/")
               for name, _conv in fusions)
