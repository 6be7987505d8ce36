"""Tensors from a seed, the same in any compilation: a key that takes
any whole-number seed (the driver's are above 2**31), and a uniform
draw made of integers times a constant, which no fusion can round
differently."""
import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def uniform(key, shape, std, mean=0.0):
    """Float32 uniform around `mean` with deviation `std`: integers in
    [-32767, 32767] (more levels than int8 can hold) times a constant."""
    ints = jax.random.randint(key, shape, -32767, 32768, jnp.int32)
    return ints.astype(jnp.float32) * np.float32(
        std * 3 ** 0.5 / 32767.0) + np.float32(mean)
