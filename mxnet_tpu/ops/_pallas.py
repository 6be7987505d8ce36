"""Where every Pallas kernel in the op library learns how it runs.

On the TPU a kernel compiles through Mosaic. On the CPU — the test
host — the same kernel body runs in Pallas interpret mode, so the
numerics tests pin are the kernel's own. No other backend has a
lowering for these kernels; asking for one is an error, not a silent
interpret run at a thousandth of the speed.
"""
from __future__ import annotations

import jax


def interpret():
    """False on the TPU (compiled kernel), True on the CPU (interpret
    mode); raises on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "Pallas kernels compile for the TPU and interpret on the CPU; "
        "the default backend is %r" % (backend,))
