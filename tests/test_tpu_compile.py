"""Compiles for a described v5e chip, with no chip attached: what the
TPU's own compiler makes of a program, which a CPU run cannot show.
Nothing runs, so these say nothing about results or times.

All such tests live in THIS file and describe the topology inside a
fixture: the TPU's library can be held by one process at a time, so
only the worker that is given this file may load it, and only once a
test of it has started."""
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


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_decode_step_writes_the_pool_in_place_on_v5e(one_chip,
                                                     no_compile_cache):
    """The serving step at `opt-1.3b.serve_saturated`'s cache shape (8
    slots x 32 heads x 1 536 positions x 64, bf16; two layers, narrow
    FFN and vocabulary to keep it a few seconds): compiled for a v5e,
    the donated program aliases every byte of the pool, and the
    per-row write is not the scatter the TPU compiler expands into a
    `while` that carries, and writes back, each whole cache array."""
    from cellbench.reference import opt as ref
    cfg = {"hidden_size": 2048, "num_attention_heads": 32,
           "ffn_dim": 2048, "vocab_size": 1024, "num_hidden_layers": 2,
           "max_position_embeddings": 1536}
    slots = 8
    gen = Generator(ref.make_params(cfg, 1, "bfloat16"), 1024, 1536,
                    num_layers=2, num_heads=32, dim=2048,
                    ffn_hidden=2048, batch_size=slots, dtype="bfloat16")

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    with gen.serving_decoder() as dec:
        args = {n: spec(a) for n, a in gen._params.items()}
        args["data"] = args["positions"] = jax.ShapeDtypeStruct(
            (slots, 1), jnp.float32, sharding=one_chip)
        args["cache_pos"] = jax.ShapeDtypeStruct(
            (slots,), jnp.float32, sharding=one_chip)
        aux = {n: spec(a) for n, a in dec._aux.items()}
        compiled = dec._step_fn.lower(args, aux,
                                      spec(dec._rng0)).compile()
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in aux.values())
    assert held == 4 * slots * 32 * 1536 * 64 * 2
    assert compiled.memory_analysis().alias_size_in_bytes == held
    text = compiled.as_text()
    assert " while(" not in text
    assert text.count(" dynamic-update-slice(") >= 4 * slots
