"""Profiler — per-op host timeline + XLA device traces (``mx.profiler``).

Reference: src/engine/profiler.{h,cc} (engine-integrated op stats, Chrome
trace-event JSON dump, profiler.h:122-127) and python/mxnet/profiler.py
(profiler_set_config / profiler_set_state / dump_profile).

TPU-native mapping, two layers:
- **Host timeline** (this module): eager dispatch and executor runs are
  timed around their dispatch sites and dumped as Chrome trace-event JSON
  — open in chrome://tracing or Perfetto, like the reference's dump.
  Durations are host-side dispatch+sync costs; JAX dispatch is async, so
  a step's device time shows up on the op that blocks (the analogue of
  the reference's WaitToRead attribution).
- **Device traces**: when a trace dir is configured (``xplane_dir`` or
  MXNET_PROFILER_XPLANE), start/stop also drive ``jax.profiler`` which
  records XLA/TPU activity as TensorBoard xplane + trace.json.gz — the
  ground-truth per-kernel timeline. Every device operation on it names
  the graph node and operator it was lowered from
  (``<node>/op.<Operator>``; ``train.*`` in a train step), the
  reference profiler's per-operator record
  (docs/observability.md, "Reading the device's seconds by node and
  operator").

Env parity (docs/how_to/env_var.md:97-108): MXNET_PROFILER_AUTOSTART,
MXNET_PROFILER_MODE (0 => symbolic-only, 1 => all ops).
"""
from __future__ import annotations

import json
import os
import threading
import time

from . import telemetry as _telemetry
from . import trace as _trace

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "set_config", "set_state", "dump", "State", "record_event",
           "scope", "is_running", "mode", "step_scope", "count_host_sync",
           "host_sync_count", "reset_host_sync_count",
           "sample_device_memory"]


class _ProfilerState:
    def __init__(self):
        self.mode = "symbolic"            # 'symbolic' | 'all'
        self.filename = "profile.json"
        self.xplane_dir = None
        self.running = False
        self.events = []
        self.lock = threading.Lock()
        self._tracing = False


_P = _ProfilerState()


class State:
    stop = "stop"
    run = "run"


def profiler_set_config(mode="symbolic", filename="profile.json",
                        xplane_dir=None, **_kwargs):
    """Configure the profiler (reference profiler.py:profiler_set_config;
    modes 'symbolic' = executor runs only, 'all' = every eager op too)."""
    if mode not in ("symbolic", "all"):
        raise ValueError("mode must be 'symbolic' or 'all'")
    _P.mode = mode
    _P.filename = filename
    from . import config as _config
    _P.xplane_dir = xplane_dir or \
        _config.get("MXNET_PROFILER_XPLANE") or None


def profiler_set_state(state="stop"):
    """Start/stop collection (reference profiler_set_state)."""
    if state not in (State.stop, State.run):
        raise ValueError("state must be 'run' or 'stop'")
    was = _P.running
    _P.running = state == State.run
    if _P.running and not was:
        with _P.lock:
            _P.events = []
        if _P.xplane_dir:
            import jax
            jax.profiler.start_trace(_P.xplane_dir)
            _P._tracing = True
    elif was and not _P.running and _P._tracing:
        import jax
        jax.profiler.stop_trace()
        _P._tracing = False


def is_running():
    return _P.running


def mode():
    return _P.mode


def record_event(name, category, start_us, dur_us, tid=0, args=None):
    """Append one complete ('X') trace event; called by the dispatch
    sites (ops/registry.py, executor.py)."""
    if not _P.running:
        return
    ev = {"name": name, "cat": category, "ph": "X",
          "ts": start_us, "dur": dur_us, "pid": 0, "tid": tid}
    if args:
        ev["args"] = args
    with _P.lock:
        _P.events.append(ev)


# -- blocking-host-sync accounting ------------------------------------------
# The pipelining claim ("no per-step blocking host syncs in the fit hot
# loop") is asserted by tests against this counter, so it is ALWAYS on
# (one locked int increment — noise next to the transfer it counts).
# Counted sites: NDArray.asnumpy / wait_to_read / wait_to_write, the
# metric device-accumulator read in EvalMetric.get, and the fit loops'
# bounded-dispatch-window waits. The count lives in the telemetry
# registry (ISSUE 8) — same always-on semantics, but it now also rides
# the Prometheus export and the dump_profile snapshot; this API is the
# stable surface the tests keep using.

_HOST_SYNCS = _telemetry.counter("host_syncs")


def count_host_sync(kind="sync"):
    """Count one blocking host synchronization (a D2H transfer or a
    block-until-ready wait); records a timeline event when running."""
    _HOST_SYNCS.inc()
    if _P.running:
        record_event("host_sync:" + kind, "sync",
                     time.perf_counter_ns() // 1000, 1)


def host_sync_count():
    """Monotonic count of blocking host syncs since import (tests take
    deltas around the region under scrutiny)."""
    return _HOST_SYNCS.value


def reset_host_sync_count():
    _HOST_SYNCS.reset()


def sample_device_memory(site="boundary"):
    """HBM watermark sample into the ``mem.hbm_bytes_in_use`` /
    ``mem.hbm_peak_bytes`` gauges, from
    ``jax.local_devices()[0].memory_stats()`` when the backend provides
    it (TPU/GPU runtimes do; CPU usually returns nothing). Called at
    EPOCH boundaries and serve ``warmup()`` only — never per step: the
    stats read is a runtime API call, cheap but not free, and the
    watermark is a boundary-scale signal anyway. A host-side API read
    — no device sync, no transfer. Returns the raw stats dict (None
    when the backend has none)."""
    try:
        import jax
        devices = jax.local_devices()
        if not devices:
            return None
        stats = getattr(devices[0], "memory_stats", None)
        stats = stats() if callable(stats) else None
    except Exception:    # noqa: BLE001 — absent API/backend = no sample
        return None
    if not stats:
        return None
    in_use = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    if in_use is not None:
        _telemetry.gauge("mem.hbm_bytes_in_use").set(in_use)
    if peak is not None:
        _telemetry.gauge("mem.hbm_peak_bytes").set(peak)
    if in_use is not None or peak is not None:
        _telemetry.journal_event("mem.sample", site=site,
                                 bytes_in_use=in_use, peak_bytes=peak)
    return stats


class scope:
    """Context manager timing one region into the profile and, as the
    host annotation ``mxnet.<name>`` (``trace.annotate``), into the
    xplane timeline of whatever device trace is live — this module's
    own or one started by anyone else."""

    def __init__(self, name, category="op"):
        self.name = name
        self.category = category

    def __enter__(self):
        self._start = time.perf_counter_ns()
        self._ann = _trace.annotate(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        end = time.perf_counter_ns()
        record_event(self.name, self.category, self._start // 1000,
                     max((end - self._start) // 1000, 1))
        return False


class step_scope:
    """Step marker for training hot loops: wraps one step in a
    ``jax.profiler.StepTraceAnnotation`` — the xplane/TensorBoard
    step-grouping annotation, which makes per-step device time and the
    input-pipeline/compute overlap visible in the trace viewer — plus a
    host timeline event when the host profiler is running."""

    def __init__(self, step_num, name="train_step"):
        self.name = name
        self.step_num = int(step_num)
        self._jax_ctx = None
        self._start = None

    def __enter__(self):
        self._start = time.perf_counter_ns()
        import jax
        self._jax_ctx = jax.profiler.StepTraceAnnotation(
            self.name, step_num=self.step_num)
        self._jax_ctx.__enter__()
        return self

    def __exit__(self, *exc):
        self._jax_ctx.__exit__(*exc)
        end = time.perf_counter_ns()
        record_event("%s#%d" % (self.name, self.step_num), "step",
                     self._start // 1000,
                     max((end - self._start) // 1000, 1))
        return False


def dump_profile(filename=None):
    """Write the collected events as Chrome trace-event JSON (reference
    profiler.h:122-127 DumpProfile)."""
    path = filename or _P.filename
    with _P.lock:
        events = list(_P.events)
    # the telemetry registry snapshot rides the dump as metadata, so a
    # trace capture carries the run's counters/quantiles with it
    payload = {"traceEvents": events, "displayTimeUnit": "ms",
               "telemetry": _telemetry.snapshot()}
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


# modern-surface aliases (later-reference profiler.py names)
set_config = profiler_set_config
set_state = profiler_set_state
dump = dump_profile


from . import config as _cfg_mod

if _cfg_mod.get("MXNET_PROFILER_AUTOSTART"):
    profiler_set_config(
        mode="all" if _cfg_mod.get("MXNET_PROFILER_MODE") else "symbolic")
    profiler_set_state(State.run)
