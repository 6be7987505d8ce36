"""The compiled programs of the benchmark's other language-model
families at toy size, by the sha256 of their StableHLO text (no debug
information): OPT, Granite, Nemotron, LFM2 and Cohere2 `generator_step`
and the slot pool's `decode_step`, SDAR's `block_step`. A PR that adds a family and
must leave these programs as they are recomputes them on its parent
(`cd <parent checkout> && PYTHONPATH=. python <this file>`) and holds
its own tree to them in a test. The toy configurations are the ones
`cellbench/tests/` already runs."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np


def _sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _generators():
    from mxnet_tpu.generation import Generator
    from cellbench.models import (cohere2_moe, granite, lfm2_moe,
                                  nemotron_h, sdar)
    from cellbench.reference import cohere2_moe as cohere2_ref
    from cellbench.reference import granite as granite_ref
    from cellbench.reference import lfm2_moe as lfm2_ref
    from cellbench.reference import nemotron_h as nemotron_ref
    from cellbench.reference import opt as opt_ref
    from cellbench.reference import sdar as sdar_ref
    from cellbench.tests import (test_cohere2_moe, test_granite,
                                 test_lfm2_moe, test_nemotron_h,
                                 test_sdar, toy)

    def make(ref, cfg, args):
        cfg = dict(cfg, compute_dtype="float32")
        s = ref.sizes(cfg)
        return Generator(ref.make_params(cfg, 11, "float32"), s["vocab"],
                         48, batch_size=2, **args(cfg))

    def opt_args(cfg):
        s = opt_ref.sizes(cfg)
        return dict(num_layers=s["layers"], num_heads=s["heads"],
                    dim=s["dim"], ffn_hidden=s["ffn"])

    yield "opt", make(opt_ref, toy.OPT, opt_args)
    yield "granite", make(granite_ref, test_granite.SMALL,
                          granite.generator_args)
    yield "nemotron", make(nemotron_ref, test_nemotron_h.SMALL,
                           nemotron_h.generator_args)
    yield "lfm2", make(lfm2_ref, test_lfm2_moe.SMALL,
                       lfm2_moe.generator_args)
    yield "cohere2", make(cohere2_ref, test_cohere2_moe.SMALL,
                          cohere2_moe.generator_args)
    yield "sdar", make(sdar_ref, test_sdar.SMALL,
                       lambda cfg: sdar.generator_args(
                           cfg, test_sdar.DECK))


def hashes():
    """{"<family>.<program>": the first 16 hex digits of the sha256}."""
    out = {}
    rng = jax.random.PRNGKey(0)
    for family, gen in _generators():
        B = gen.batch_size
        if not gen._diffusion:
            args = dict(gen._params, data=jnp.zeros((B, 5), jnp.float32),
                        positions=jnp.arange(5, dtype=jnp.float32),
                        cache_pos=jnp.zeros((1,), jnp.float32))
            out[family + ".generator_step"] = _sha(
                gen._step_fn.lower(args, gen._fresh_aux(), rng))
        with gen.serving_decoder() as dec:
            if gen._diffusion:
                out[family + ".block_step"] = _sha(dec._step_fn.lower(
                    gen._params, (dec._aux, dec._bstate), dec._rng0))
                continue
            args = dict(gen._params,
                        data=jnp.asarray(np.zeros((B, 1), np.float32)),
                        positions=jnp.zeros((B, 1), jnp.float32),
                        cache_pos=jnp.zeros((B,), jnp.float32))
            out[family + ".decode_step"] = _sha(
                dec._step_fn.lower(args, dec._aux, dec._rng0))
    return out


if __name__ == "__main__":
    print(json.dumps(hashes(), indent=1))
