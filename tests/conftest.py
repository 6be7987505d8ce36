"""Test env: run everything on a virtual 8-device CPU mesh so sharding
semantics (kvstore/parallel tests) are exercised without TPU hardware
(SURVEY.md §4: multi-process-on-one-host is the reference's distributed-test
pattern; virtual devices are the JAX analogue).

Set MXNET_TEST_ON_TPU=1 to run the suite against the real chip instead.

A pytest-registered plugin may import jax BEFORE this conftest, so setting
os.environ here is not enough on its own: jax.config.update works post-import
(as long as no backend has been initialized yet, which is true until the
first test runs).
"""
import os

if not os.environ.get("MXNET_TEST_ON_TPU"):
    # for child processes / late importers
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # authoritative override even if jax was already imported
    import jax
    jax.config.update("jax_platforms", "cpu")
