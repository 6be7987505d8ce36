"""Embedding-gradient formulation microbench: scatter-add (autodiff
default) vs sort+segment-sum (`MXNET_EMBED_GRAD=segsum`) vs one-hot
matmul, at the flagship LM's shape (vocab 32k, dim 2048, 16k tokens).

Why: the round-5 transformer trace (bench_out/trace_tlm_summary.txt)
measured the fused embedding scatter-grad + Adam update ~8x off its
pure-bandwidth roofline — the one flagged unexplained inefficiency in
the 59.2%-MFU step. The segsum experiment is staged in
ops/indexing.py; THIS bench decides it, on the chip (not yet run
there).

    python benchmark/bench_embgrad.py

One JSON line with all three timings, naming the device. With
BENCH_EMBGRAD_MODEL=1 a whole-step A/B comes first (bench.py twice,
~5 extra minutes): each child must own the chip, so they run before
this process initialises its own backend.
"""
import json
import os
import sys

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from _bench_util import chain_time, require_accelerator  # noqa: E402

V = int(os.environ.get("BENCH_EMBGRAD_VOCAB", "32768"))
D = int(os.environ.get("BENCH_EMBGRAD_DIM", "2048"))
N = int(os.environ.get("BENCH_EMBGRAD_TOKENS", "16384"))
if os.environ.get("BENCH_EMBGRAD_SMOKE") == "1":
    V, D, N = 64, 16, 128
ITERS = int(os.environ.get("BENCH_ITERS", "20"))


def grad_scatter(ids, dy):
    return jnp.zeros((V, D), jnp.float32).at[ids].add(
        dy.astype(jnp.float32))


def grad_segsum(ids, dy):
    order = jnp.argsort(ids, stable=True)
    return jax.ops.segment_sum(
        jnp.take(dy, order, axis=0).astype(jnp.float32),
        jnp.take(ids, order), num_segments=V,
        indices_are_sorted=True)


def grad_onehot_mm(ids, dy):
    oh = jax.nn.one_hot(ids, V, dtype=dy.dtype)
    return jnp.einsum("nv,nd->vd", oh, dy,
                      preferred_element_type=jnp.float32)


def timed(fn):
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)
    dy0 = jnp.asarray(rng.randn(N, D), jnp.bfloat16)

    def step(dy):
        dw = fn(ids, dy)
        # feed the next iteration (data dependence) without keeping
        # the (V, D) grad alive: gather back the rows that fed it
        return jnp.take(dw, ids, axis=0).astype(dy.dtype)

    return chain_time(step, dy0, ITERS)


def model_ab():
    """bench.py with and without MXNET_EMBED_GRAD=segsum, one child at
    a time; nothing in this process has touched the backend yet."""
    import subprocess
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for tag, env in (("default", {}),
                     ("segsum", {"MXNET_EMBED_GRAD": "segsum"})):
        r = subprocess.run(
            [sys.executable, "bench.py", "--network", "transformer_lm"],
            capture_output=True, text=True, cwd=here,
            env=dict(os.environ, **env))
        line = r.stdout.strip().splitlines()[-1] if r.stdout \
            else r.stderr[-200:]
        print('{"model_ab": "%s", "result": %s}'
              % (tag, line if line.startswith("{") else
                 json.dumps(line)))


def main():
    if os.environ.get("BENCH_EMBGRAD_MODEL") == "1":
        model_ab()
    rec = {"metric": "embedding_grad_formulation",
           "vocab": V, "dim": D, "tokens": N,
           **require_accelerator("bench_embgrad.py")}
    for name, fn in (("scatter", grad_scatter),
                     ("segsum", grad_segsum),
                     ("onehot_mm", grad_onehot_mm)):
        rec["%s_ms" % name] = round(timed(fn) * 1e3, 3)
    rec["segsum_speedup"] = round(
        rec["scatter_ms"] / rec["segsum_ms"], 3)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
