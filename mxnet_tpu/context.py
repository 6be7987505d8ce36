"""Device context — TPU-native analogue of mxnet.context.

The reference models devices as ``Context(device_type, device_id)`` with a
thread-local "current context" scope (reference: ``python/mxnet/context.py``).
Here a Context maps onto a concrete ``jax.Device``:

* ``cpu(i)``  -> i-th JAX CPU (host) device
* ``tpu(i)``  -> i-th JAX accelerator device
* ``gpu(i)``  -> alias of ``tpu(i)`` so reference scripts written against
  ``mx.gpu()`` run unmodified on TPU
* ``cpu_pinned(i)`` -> alias of ``cpu(i)`` (pinned host memory is a CUDA
  concept; on TPU the host staging buffer is managed by the runtime)

Placement is realised with ``jax.device_put``; everything under ``jit``
runs on the default backend regardless, which is the TPU-idiomatic model:
context picks where *array storage* lives, XLA owns execution.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus"]


class Context:
    """A device context (reference: python/mxnet/context.py:28-140)."""

    # Keep the reference's numeric type codes for serialization compat.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    # -- mapping onto jax devices -------------------------------------------------
    def jax_device(self):
        """The concrete jax.Device backing this context."""
        if self.device_type in ("cpu", "cpu_pinned"):
            # cpu ids are logical, as in the reference (any mx.cpu(k)
            # names host memory): they wrap over the host devices
            devs = jax.devices("cpu") if _has_platform("cpu") \
                else jax.devices()
            return devs[self.device_id % len(devs)]
        # gpu is an alias for the accelerator on this image (TPU); an
        # accelerator id names one chip, so it never wraps or falls
        # back to the host
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                "%r: this process sees %d accelerator device(s) "
                "(default backend %r)"
                % (self, len(devs), jax.default_backend()))
        return devs[self.device_id]

    # -- equality / hashing -------------------------------------------------------
    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- scope --------------------------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """Reference frees the GPU memory pool; XLA owns the TPU pool. No-op."""


def _has_platform(name):
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


Context._default_ctx.value = Context("cpu", 0)


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias for the accelerator so `mx.gpu()` scripts work on TPU."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len([d for d in jax.devices() if d.platform != "cpu"])


num_tpus = num_gpus


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
