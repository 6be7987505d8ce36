"""Pipelined training hot loop: device-resident metrics, prefetch
placement, bounded async dispatch, and the blocking-host-sync budget.

The load-bearing assertions (ISSUE 4 acceptance):
- device metric accumulation equals the host metric within 1e-5;
- an instrumented fit epoch performs at most ONE blocking host sync
  per step (asserted on the CPU backend via the profiler's
  always-on counter);
- metrics without a device impl fall back to the host path unchanged.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io, metric, profiler
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.parallel import make_train_step


def _mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=32)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=2)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy(n=96, d=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# device-metric parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,kwargs", [
    ("acc", {}),
    ("ce", {}),
    ("mse", {}),
    ("mae", {}),
    ("rmse", {}),
    ("nll_loss", {}),
    ("top_k_accuracy", {"top_k": 3}),
    ("perplexity", {"ignore_label": 1}),
])
def test_device_metric_parity(name, kwargs):
    """Device accumulation equals the host metric within 1e-5 over
    several batches (acceptance gate names acc/ce/mse; the rest ride
    the same contract)."""
    rng = np.random.RandomState(7)
    host = metric.create(name, **kwargs)
    dev = metric.create(name, **kwargs)
    assert dev.supports_device_update
    for _ in range(5):
        if name in ("mse", "mae", "rmse"):
            label = rng.randn(16).astype(np.float32)
            pred = rng.randn(16).astype(np.float32)
        else:
            pred = rng.rand(16, 10).astype(np.float32) + 1e-3
            pred /= pred.sum(1, keepdims=True)
            label = rng.randint(0, 10, 16).astype(np.float32)
        host.update([mx.nd.array(label)], [mx.nd.array(pred)])
        dev.update_device([mx.nd.array(label)], [mx.nd.array(pred)])
    hv, dv = host.get()[1], dev.get()[1]
    assert abs(hv - dv) <= 1e-5 * max(1.0, abs(hv)), (name, hv, dv)


@pytest.mark.parametrize("name,kwargs", [
    ("ce", {}), ("perplexity", {"ignore_label": None})])
def test_device_metric_reduces_bf16_in_f32(name, kwargs):
    """A bf16-compute step hands the fused metric bf16 outputs. The
    batch reduction must run in float32: a bf16 sum over 4096 rows
    resolves 1 part in 256 (found on the chip as a loss moving in
    steps of 1/16 nat at 16k tokens a batch)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    pred = rng.rand(4096, 16).astype(np.float32) + 1e-3
    pred = jnp.asarray(pred / pred.sum(1, keepdims=True), jnp.bfloat16)
    label = jnp.asarray(rng.randint(0, 16, 4096), jnp.float32)
    host = metric.create(name, **kwargs)
    dev = metric.create(name, **kwargs)
    host.update([np.asarray(label)], [np.asarray(pred, np.float32)])
    dev.update_device([label], [pred])
    hv, dv = host.get()[1], dev.get()[1]
    assert abs(hv - dv) <= 1e-4 * abs(hv), (name, hv, dv)


def test_device_metric_composite_and_fallback():
    """Composite fans out per child; a metric without a device impl
    (F1) transparently falls back to the host path — update_device is
    always safe to call."""
    pred = mx.nd.array([[0.2, 0.8], [0.8, 0.2], [0.3, 0.7]])
    label = mx.nd.array([1, 0, 0])

    f1h, f1d = metric.create("f1"), metric.create("f1")
    assert not f1d.supports_device_update
    f1h.update([label], [pred])
    f1d.update_device([label], [pred])        # falls back, same value
    assert f1h.get()[1] == f1d.get()[1]

    comp = metric.create(["acc", "ce"])
    assert comp.supports_device_update
    comp.update_device([label], [pred])
    names, values = comp.get()
    assert names == ["accuracy", "cross-entropy"]
    assert abs(values[0] - 2.0 / 3) < 1e-6

    mixed = metric.create(["acc", "f1"])      # one child host-only
    assert not mixed.supports_device_update
    mixed.update_device([label], [pred])      # still accumulates both
    assert abs(mixed.get()[1][0] - 2.0 / 3) < 1e-6


def test_device_metric_single_host_read():
    """update_device never blocks on the host; get() is the single
    blocking read (profiler's always-on sync counter)."""
    m = metric.create("acc")
    pred = mx.nd.array(np.random.RandomState(0).rand(8, 4))
    label = mx.nd.array(np.zeros(8))
    base = profiler.host_sync_count()
    for _ in range(10):
        m.update_device([label], [pred])
    assert profiler.host_sync_count() == base   # no per-update sync
    m.get()
    assert profiler.host_sync_count() == base + 1


# ---------------------------------------------------------------------------
# pipelined TrainStep.fit
# ---------------------------------------------------------------------------

def test_trainstep_fit_sync_budget_per_step():
    """One instrumented epoch of TrainStep.fit performs at most one
    blocking host sync per step: the bounded-dispatch-window wait.
    (+1 for the epoch-end metric read.)"""
    X, y = _toy()
    step = make_train_step(_mlp(), optimizer="sgd",
                           optimizer_params={"rescale_grad": 1.0 / 32})
    train = io.NDArrayIter(X, y, batch_size=32)   # 3 steps/epoch
    # warm epoch: compiles + init (not the measured regime)
    state, _ = step.fit(train, num_epoch=1, initializer=Xavier(), lr=0.1)
    n_steps = 3
    base = profiler.host_sync_count()
    state, acc = step.fit(train, num_epoch=1, initializer=Xavier(),
                          lr=0.1, state=state)
    syncs = profiler.host_sync_count() - base
    assert syncs <= n_steps + 1, \
        "pipelined epoch did %d blocking syncs for %d steps" \
        % (syncs, n_steps)


def test_trainstep_fit_fused_metric_matches_host_path():
    """Same data, same seeds: the fused on-device metric reports the
    same value as the host metric path within 1e-5."""
    X, y = _toy()

    def run(fuse):
        mx.random.seed(11)
        np.random.seed(11)
        step = make_train_step(
            _mlp(), optimizer="sgd",
            optimizer_params={"momentum": 0.9, "rescale_grad": 1.0 / 32})
        train = io.NDArrayIter(X, y, batch_size=32)
        _, acc = step.fit(train, num_epoch=4, initializer=Xavier(),
                          lr=0.5, seed=3, fuse_metric=fuse)
        return acc

    fused, host = run(True), run(False)
    assert abs(fused - host) <= 1e-5, (fused, host)
    assert fused > 0.9


def test_trainstep_fit_composite_fused_and_callbacks():
    """Composite metrics fuse too, and mid-epoch get() (Speedometer
    pattern) sees live values."""
    X, y = _toy()
    step = make_train_step(_mlp(), optimizer="sgd",
                           optimizer_params={"rescale_grad": 1.0 / 32})
    train = io.NDArrayIter(X, y, batch_size=32)
    seen = []

    def cb(param):
        names, values = param.eval_metric.get()
        seen.append((param.nbatch, names, values))

    step.fit(train, num_epoch=2, initializer=Xavier(), lr=0.5,
             eval_metric=["acc", "ce"], batch_end_callback=cb)
    assert len(seen) == 6
    assert seen[-1][1] == ["accuracy", "cross-entropy"]
    assert all(np.isfinite(v) for v in seen[-1][2])


def test_prefetching_iter_place_fn_stage():
    """PrefetchingIter's device-prefetch stage: batches arrive with
    .placed feeds (assembled off the hot loop) and fit consumes them."""
    X, y = _toy()
    step = make_train_step(_mlp(), optimizer="sgd",
                           optimizer_params={"rescale_grad": 1.0 / 32})
    pf = io.PrefetchingIter(io.NDArrayIter(X, y, batch_size=32),
                            place_fn=step.make_placer())
    batch = next(pf)
    assert set(batch.placed) == {"data", "softmax_label"}
    np.testing.assert_allclose(np.asarray(batch.placed["data"]),
                               batch.data[0].asnumpy())
    pf.reset()
    _, acc = step.fit(pf, num_epoch=6, initializer=Xavier(), lr=0.5)
    assert acc > 0.9


def test_prefetching_iter_worker_error_surfaces():
    """A place_fn failure propagates to the consumer instead of
    starving the queue — including a leaked StopIteration, which must
    NOT be misread as epoch end (silent early truncation)."""
    def boom(_batch):
        raise RuntimeError("placement exploded")

    X, y = _toy(n=32)
    pf = io.PrefetchingIter(io.NDArrayIter(X, y, batch_size=32),
                            place_fn=boom)
    with pytest.raises(RuntimeError, match="placement exploded"):
        next(pf)

    def leaky(_batch):
        raise StopIteration("bug in placement")

    pf2 = io.PrefetchingIter(io.NDArrayIter(X, y, batch_size=32),
                             place_fn=leaky)
    with pytest.raises(StopIteration, match="bug in placement"):
        pf2.iter_next()


def test_trainstep_fit_donate_false_keeps_caller_state():
    """TrainStep(donate=False) must hold for the fused metric step too:
    the state the caller passed in stays readable after fit."""
    X, y = _toy()
    step = make_train_step(_mlp(), optimizer="sgd", donate=False,
                           optimizer_params={"rescale_grad": 1.0 / 32})
    state0 = step.init_state(Xavier(), {"data": X.shape,
                                        "softmax_label": y.shape})
    before = np.asarray(state0[0]["fc1_weight"]).copy()
    train = io.NDArrayIter(X, y, batch_size=32)
    state1, _ = step.fit(train, num_epoch=1, state=state0, lr=0.5)
    # donate=False: the original buffers are intact, not deleted
    np.testing.assert_allclose(np.asarray(state0[0]["fc1_weight"]),
                               before)
    assert not np.allclose(np.asarray(state1[0]["fc1_weight"]), before)


def test_dispatch_ahead_window_is_bounded():
    """dispatch_ahead=1 degenerates to synchronous stepping (one wait
    per step) and still trains; the knob also reads the env default."""
    from mxnet_tpu import config as cfg
    assert cfg.get("MXNET_DISPATCH_AHEAD") == 2
    X, y = _toy()
    step = make_train_step(_mlp(), optimizer="sgd",
                           optimizer_params={"momentum": 0.9,
                                             "rescale_grad": 1.0 / 32})
    train = io.NDArrayIter(X, y, batch_size=32)
    _, acc = step.fit(train, num_epoch=10, initializer=Xavier(), lr=0.5,
                      dispatch_ahead=1)
    assert acc > 0.9


# ---------------------------------------------------------------------------
# pipelined Module.fit
# ---------------------------------------------------------------------------

def test_module_fit_sync_budget_and_staging():
    """Module.fit's hot loop: batch t+1 staged while step t runs, the
    device metric path removes per-batch metric reads — at most one
    blocking sync per step (the window wait), plus the epoch-end
    reads."""
    X, y = _toy()
    train = io.NDArrayIter(X, y, batch_size=32)   # 3 steps/epoch
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    # warm epoch (bind/init/compile)
    mod.fit(train, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9})
    n_steps, budget = 3, 3 + 2    # 1/step window + epoch-end metric/param
    base = profiler.host_sync_count()
    mod._fit_epoch(train, 1, metric.create("acc"), None, None)
    syncs = profiler.host_sync_count() - base
    assert syncs <= budget, \
        "module epoch did %d blocking syncs for %d steps" \
        % (syncs, n_steps)
    # and the full fit (incl. staging via prepare) still converges
    mod.fit(train, num_epoch=8, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            force_init=True, force_rebind=True)
    assert dict(mod.score(train, "acc"))["accuracy"] > 0.9


def test_module_score_device_metric_matches_host():
    """score() routes metrics through the device accumulator; a
    host-only CustomMetric on the same outputs agrees within 1e-5."""
    X, y = _toy()
    train = io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(train, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5})

    def np_acc(label, pred):
        return float((pred.argmax(1) == label.astype(int)).sum()), \
            label.size

    host = dict(mod.score(train, metric.np(np_acc, name="host_acc")))
    dev = dict(mod.score(train, "acc"))
    assert abs(host["host_acc"] - dev["accuracy"]) <= 1e-5


# ---------------------------------------------------------------------------
# profiler plumbing
# ---------------------------------------------------------------------------

def test_profiler_step_markers_and_sync_events(tmp_path):
    """step_scope emits host timeline events (and StepTraceAnnotation
    on device traces); counted syncs appear as events while running."""
    import json
    out = str(tmp_path / "steps.json")
    profiler.profiler_set_config(mode="all", filename=out)
    profiler.profiler_set_state("run")
    try:
        with profiler.step_scope(7):
            mx.nd.ones((4,)).asnumpy()     # a counted blocking read
    finally:
        profiler.profiler_set_state("stop")
    trace = json.load(open(profiler.dump_profile()))
    names = [e["name"] for e in trace["traceEvents"]]
    assert "train_step#7" in names
    assert any(n.startswith("host_sync:") for n in names)
    cats = {e["cat"] for e in trace["traceEvents"]}
    assert "step" in cats and "sync" in cats


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cache_dirs(tmp_path_factory):
    """jax.config.jax_compilation_cache_dir after `import mxnet_tpu` in
    three fresh interpreters (the rule runs at package import), started
    together: one with JAX_COMPILATION_CACHE_DIR set, two without."""
    given = str(tmp_path_factory.mktemp("xla_cache"))
    code = ("import jax, mxnet_tpu; "
            "print('DIR=' + str(jax.config.jax_compilation_cache_dir))")
    procs = []
    for env_dir in (given, None, None):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd="/", text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    seen = []
    for proc in procs:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err
        seen.append(out.strip().splitlines()[-1][len("DIR="):])
    return given, seen


def test_compile_cache_dir_from_environment_is_left_alone(cache_dirs):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the package sets no
    other directory."""
    given, seen = cache_dirs
    assert seen[0] == given


def test_compile_cache_dir_default_is_fixed_in_checkout(cache_dirs):
    """Unset: the cache sits at one path derived from the package
    location — the same in every interpreter, whatever its cwd, because
    the directory is part of the cache key."""
    _, seen = cache_dirs
    assert seen[1] == os.path.join(_REPO, ".jax_cache")
    assert seen[2] == seen[1]
