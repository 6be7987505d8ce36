"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet (~0.11, NNVM era), re-architected for JAX/XLA/Pallas/pjit.

Blueprint: SURVEY.md at the repo root. Mapping of the reference's layers:
  ThreadedEngine/GraphExecutor/PlanMemory  -> jax.jit + XLA (async, fused)
  mshadow/CUDA kernels                     -> jnp/lax (+ Pallas hot ops)
  KVStore comm trees + ps-lite             -> XLA collectives over the mesh
  Module/Gluon/NDArray/Symbol user surface -> preserved API, same semantics
"""
__version__ = "0.1.0"

import jax as _jax

# MXNet semantics: float32 arrays mean float32 math. JAX's DEFAULT matmul
# precision lowers f32 matmuls to bf16 passes on TPU; we keep reference
# numerics for f32 and get MXU speed by using bf16 *dtypes* on the perf path
# (the reference's multi-precision story, mp_sgd_*, maps to this).
# Override with MXNET_MATMUL_PRECISION=default|high|highest.
from . import config as _config
_prec = _config.get("MXNET_MATMUL_PRECISION")
if _prec != "default":
    _jax.config.update("jax_default_matmul_precision",
                       {"high": "bfloat16_3x", "highest": "float32"}.get(
                           _prec, _prec))

# Persistent XLA compilation cache, so a second process on the same
# machine skips the per-shape compiles. The directory is part of the
# cache key, so it must not move between runs: where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing here
# touches it; otherwise the cache sits at one fixed path in the checkout.
# This is the only place the directory is set.
import os as _os
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

from . import telemetry

from . import base
from .base import MXNetError

from . import context
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context

from . import ops  # populates the operator registry

from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray

from . import random
from . import random as rnd

from . import autograd

from . import name
from . import attribute
from .attribute import AttrScope

from . import symbol
from . import symbol as sym
from .symbol import Symbol

from . import executor
from .executor import Executor

from . import registry
from . import io
from . import initializer
from . import initializer as init
from . import lr_scheduler
from . import optimizer
from . import optimizer as opt
from .optimizer import Optimizer
from . import metric
from . import kvstore
from . import kvstore as kv
from . import callback
from . import monitor
from .monitor import Monitor
from . import model
from .model import FeedForward
from . import module
from . import module as mod
from .module import Module

from . import rnn
from . import operator
from . import kvstore_server as _kvstore_server
# server/scheduler-role processes park here (reference: mxnet/__init__
# starts the server loop at import when DMLC_ROLE=server)
_kvstore_server._init_kvstore_server_module()
from . import guardrail
from . import profiler
from . import predictor
from .predictor import Predictor
from . import generation
from .generation import Generator
from . import serve
from . import rtc
from . import visualization
from . import visualization as viz

from . import recordio
from . import image
from . import image as img
from . import gluon
from . import models
from . import parallel
