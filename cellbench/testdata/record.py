"""How `small.xplane.pb` was made (on the chip, once): a few jitted
programs with host pauses between them, traced with the settings
`cellbench/run.py` uses. Run from the root of the repo:

    python3 cellbench/testdata/record.py <output directory>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: (x * 2).astype(jnp.bfloat16))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((f(x), g(x)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(4):
        with jax.profiler.TraceAnnotation("cellbench.pause"):
            time.sleep(0.002)
        jax.block_until_ready(f(x))
        jax.block_until_ready(g(x + i))     # eager add: a PjitFunction
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(found[0], os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
