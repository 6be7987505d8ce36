"""Shared microbench discipline (one copy, so every benchmark/ script
means the same thing by a millisecond): take the platform JAX was
given, refuse the host CPU, name the device in the result, and time
one chain of dependent iterations closed by block_until_ready.
"""
import os
import sys
import time

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench_common import require_accelerator  # noqa: E402,F401 (re-export)


def chain_time(step, x0, iters):
    """Time `step` (array -> same-shape array) chained `iters` times.

    Chains the iterations on device inside one jitted fori_loop, warms
    it (compile + first run), then times ONE chain. Returns seconds per
    iteration. `step` must make iteration i+1 data-depend on i (feed
    its output forward) or the loop could overlap in ways a training
    step would not.
    """
    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, iters, lambda i, x_: step(x_), x)

    jax.block_until_ready(chain(x0))                   # compile+warm
    t0 = time.time()
    jax.block_until_ready(chain(x0))
    return (time.time() - t0) / iters
