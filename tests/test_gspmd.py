"""One-jit GSPMD path (ISSUE 11): SpecLayout partition-spec registry
over a forced 8-device ``data × fsdp`` CPU mesh (conftest.py's
xla_force_host_platform_device_count).

The load-bearing acceptance assertions:
- a one-jit GSPMD ``TrainStep.fit`` epoch matches the single-device
  baseline numerically (rtol 2e-4 / atol 1e-5 — the same float
  reduction-order tolerance the plain DP-mesh parity test uses: the
  math is identical, the summation orders are not);
- each device holds a 1/N shard of the optimizer state
  (N = data × fsdp = 8);
- the blocking-host-sync counter stays ≤ 1 per step under GSPMD
  (the test_hotloop.py budget, unchanged by sharding);
- rule precedence / auto rule / describe(), and every layout
  validation failure is a raised ValueError, never an assert.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import io, profiler
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.parallel import make_mesh, make_train_step, SpecLayout
from mxnet_tpu.parallel.sharding import parse_spec

pytestmark = pytest.mark.gspmd


def _mlp(classes=8):
    """All param shapes divisible by 8 so every optimizer-state tensor
    can hold the full 1/N fold (fc1: (32,16)+(32,), fc2: (8,32)+(8,))."""
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=32)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=classes)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy(n=64, d=16, classes=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, classes, n).astype(np.float32)
    return X, y


def _dxf_mesh():
    return make_mesh({"data": 2, "fsdp": 4})


def _layout(mesh=None, **kw):
    kw.setdefault("min_shard_size", 0)   # toy tensors are tiny
    return SpecLayout(mesh or _dxf_mesh(), **kw)


# ---------------------------------------------------------------------------
# make_mesh / layout validation: ValueError, never assert
# ---------------------------------------------------------------------------

def test_make_mesh_mismatch_raises_valueerror():
    with pytest.raises(ValueError) as e:
        make_mesh({"data": 3, "fsdp": 4})
    msg = str(e.value)
    assert "3" in msg and "4" in msg and "8" in msg  # sizes AND count


def test_make_mesh_infers_one_axis_and_validates_inference():
    mesh = make_mesh({"data": 2, "fsdp": -1})
    assert dict(mesh.shape) == {"data": 2, "fsdp": 4}
    with pytest.raises(ValueError, match="does not divide"):
        make_mesh({"data": 3, "fsdp": -1})
    with pytest.raises(ValueError, match="at most one"):
        make_mesh({"data": -1, "fsdp": -1})
    with pytest.raises(ValueError, match="positive"):
        make_mesh({"data": 0, "fsdp": 8})


def test_speclayout_rejects_unknown_axis_and_bad_rules():
    mesh = _dxf_mesh()
    with pytest.raises(ValueError, match="not a mesh axis"):
        SpecLayout(mesh, rules=[("*", P("tp"))])
    with pytest.raises(ValueError, match="more than one dim"):
        SpecLayout(mesh, rules=[("*", P("fsdp", "fsdp"))])
    # an explicit rule that cannot apply fails LOUDLY at placement
    lay = SpecLayout(mesh, rules=[("w", P("fsdp"))], min_shard_size=0)
    with pytest.raises(ValueError, match="not divisible"):
        lay.param_nsharding("w", (6,))
    lay2 = SpecLayout(mesh, rules=[("b", P("fsdp", None))],
                      min_shard_size=0)
    with pytest.raises(ValueError, match="more dims"):
        lay2.param_nsharding("b", (32,))


# ---------------------------------------------------------------------------
# rule precedence / auto rule / describe
# ---------------------------------------------------------------------------

def test_rule_precedence_first_match_wins_and_auto_fallback():
    mesh = _dxf_mesh()
    lay = SpecLayout(mesh, rules=[
        ("fc1_weight", P(None, "fsdp")),      # exact, first
        ("fc1_*", P("fsdp", None)),           # glob, shadowed for fc1_weight
    ], min_shard_size=0)
    parts, label = lay.spec_for("fc1_weight", (32, 16))
    assert parts == (None, "fsdp") and "rule[0]" in label
    parts, label = lay.spec_for("fc1_gamma", (32, 16))
    assert parts == ("fsdp", None) and "rule[1]" in label
    # auto: LARGEST divisible dim over fsdp
    parts, label = lay.spec_for("other_weight", (8, 32))
    assert parts == (None, "fsdp") and label.startswith("auto")
    # nothing divisible -> replicated
    parts, label = lay.spec_for("odd", (6, 3))
    assert parts == (None, None) and "replicated" in label


def test_auto_rule_min_size_replicates_tiny_tensors():
    lay = SpecLayout(_dxf_mesh(), min_shard_size=1024)
    parts, label = lay.spec_for("small_bias", (32,))     # 32 < 1024
    assert parts == (None,) and "replicated" in label
    parts, _ = lay.spec_for("big_weight", (64, 64))      # 4096 >= 1024
    assert parts == ("fsdp", None) or parts == (None, "fsdp")


def test_describe_reports_claims_and_unused_rules():
    lay = SpecLayout(_dxf_mesh(), rules=[
        ("fc1_weight", P("fsdp", None)),
        ("never_matches_*", P("fsdp")),
    ], min_shard_size=0)
    lay.param_nsharding("fc1_weight", (32, 16))
    lay.param_nsharding("fc2_bias", (8,))
    rep = lay.describe()
    assert "fc1_weight" in rep and "rule[0]" in rep
    assert "8x16" in rep                   # per-device shard of (32,16)
    assert "fc2_bias" in rep and "auto" in rep
    assert "rule[1]" in rep and "matched no parameter" in rep


def test_parse_spec_grammar():
    assert parse_spec("fsdp,None") == ("fsdp", None)
    assert parse_spec("data+fsdp,None") == (("data", "fsdp"), None)
    assert parse_spec(P("fsdp", None)) == ("fsdp", None)
    assert parse_spec([("data", "fsdp"), None]) == (("data", "fsdp"),
                                                    None)
    assert parse_spec("None") == (None,)


# ---------------------------------------------------------------------------
# the one-jit step: parity, opt-state shards, sync budget
# ---------------------------------------------------------------------------

def _make_step(layout=None, **kw):
    kw.setdefault("optimizer", "adam")
    kw.setdefault("optimizer_params", {"rescale_grad": 1.0 / 32})
    return make_train_step(_mlp(), layout=layout, **kw)


def test_gspmd_fit_epoch_matches_single_device():
    """Acceptance: a full TrainStep.fit epoch on the data×fsdp layout
    (sharded params, folded optimizer state, activation constraints)
    lands on the same weights as the single-device fit. Tolerance
    rtol=2e-4/atol=1e-5: identical math, different float reduction
    order across the 8 shards."""
    X, y = _toy()

    def run(layout, sharding):
        mx.random.seed(11)
        np.random.seed(11)
        step = _make_step(layout=layout, optimizer_sharding=sharding)
        train = io.NDArrayIter(X, y, batch_size=32)
        state, acc = step.fit(train, num_epoch=3, initializer=Xavier(),
                              lr=0.05, seed=3)
        return {k: np.asarray(v) for k, v in state[0].items()}, acc

    p_single, _ = run(None, None)
    p_gspmd, _ = run(_layout(), "zero1")
    for k in p_single:
        np.testing.assert_allclose(p_gspmd[k], p_single[k], rtol=2e-4,
                                   atol=1e-5, err_msg=k)


def test_gspmd_opt_state_is_one_nth_per_device():
    """Acceptance: every optimizer-state tensor lives 1/N per device
    (N = data × fsdp = 8), and STAYS in that layout across donated
    steps (no GSPMD output-propagation drift, no step-2 recompile)."""
    mesh = _dxf_mesh()
    ndev = mesh.size
    step = _make_step(layout=_layout(mesh), optimizer_sharding="zero1")
    X, y = _toy()
    state = step.init_state(Xavier(), {"data": X.shape,
                                       "softmax_label": y.shape})

    def check(state):
        for name, states in state[1].items():
            for s in states:
                local = s.sharding.shard_shape(s.shape)
                assert np.prod(local) * ndev == np.prod(s.shape), \
                    (name, s.shape, local)

    check(state)
    b = step.place_batch({"data": X, "softmax_label": y})
    rng = jax.random.PRNGKey(0)
    for _ in range(3):
        state, outs = step(state, b, 0.05, rng)
    check(state)   # donated buffers kept their shardings
    # fresh params come back in the PARAM layout (all-gathered off the
    # zero fold), not stuck in the 1/N optimizer slice
    for k, v in state[0].items():
        parts, _ = step._layout.spec_for(k, v.shape)
        got = tuple(v.sharding.spec)
        got += (None,) * (v.ndim - len(got))   # P() drops trailing Nones
        assert got == tuple(parts), (k, v.sharding)


def test_gspmd_batch_and_activations_ride_the_data_axes():
    """The batch shards over data×fsdp (all 8 devices see distinct
    rows — fsdp is data parallelism, not replication) and the step's
    outputs stay batch-sharded (the module-boundary constraints keep
    GSPMD propagation on the data axes)."""
    step = _make_step(layout=_layout(), optimizer_sharding="zero1")
    X, y = _toy()
    b = step.place_batch({"data": X, "softmax_label": y})
    spec = b["data"].sharding.spec
    assert tuple(spec)[0] == ("data", "fsdp"), spec
    state = step.init_state(Xavier(), {"data": X.shape,
                                       "softmax_label": y.shape})
    state, outs = step(state, b, 0.05, jax.random.PRNGKey(0))
    out_spec = tuple(outs[0].sharding.spec)
    assert out_spec and out_spec[0] == ("data", "fsdp"), out_spec


def test_gspmd_fit_sync_budget_per_step():
    """Acceptance: ≤1 blocking host sync per step preserved under
    GSPMD — sharding must not reintroduce per-step device→host reads
    (same budget as test_hotloop.py: the window wait, +1 epoch-end
    metric read)."""
    X, y = _toy()
    step = _make_step(layout=_layout(), optimizer_sharding="zero1")
    train = io.NDArrayIter(X, y, batch_size=32)   # 2 steps/epoch
    # warm epoch: compiles + init (not the measured regime)
    state, _ = step.fit(train, num_epoch=1, initializer=Xavier(),
                        lr=0.05)
    n_steps = 2
    base = profiler.host_sync_count()
    state, _ = step.fit(train, num_epoch=1, state=state, lr=0.05)
    syncs = profiler.host_sync_count() - base
    assert syncs <= n_steps + 1, \
        "GSPMD epoch did %d blocking syncs for %d steps" \
        % (syncs, n_steps)


def test_zero1_requires_replica_axis_on_tp_only_layout():
    mesh = make_mesh({"tp": 8})
    lay = SpecLayout(mesh)
    with pytest.raises(ValueError, match="replica axis"):
        _make_step(layout=lay, optimizer_sharding="zero1")


def test_layout_and_mesh_are_mutually_exclusive():
    with pytest.raises(ValueError, match="not both"):
        make_train_step(_mlp(), mesh=make_mesh({"data": 8}),
                        layout=_layout())


def test_gspmd_checkpoint_roundtrip_across_layouts(tmp_path):
    """A checkpoint written under the data×fsdp zero1 layout restores
    onto a single-device step (and back) and continues the identical
    trajectory — save gathers, load re-places per the loading step's
    own layout."""
    X, y = _toy()
    g = _make_step(layout=_layout(), optimizer_sharding="zero1")
    state = g.init_state(Xavier(), {"data": X.shape,
                                    "softmax_label": y.shape})
    b = g.place_batch({"data": X, "softmax_label": y})
    rng = jax.random.PRNGKey(0)
    for _ in range(2):
        state, _ = g(state, b, 0.05, rng)
    prefix = str(tmp_path / "ck")
    g.save_state(prefix, state)

    ref = g.load_state(prefix)
    ref, ref_outs = g(ref, b, 0.05, rng)

    single = _make_step()
    s_state = single.load_state(prefix)
    bs = single.place_batch({"data": X, "softmax_label": y})
    s_state, s_outs = single(s_state, bs, 0.05, rng)
    np.testing.assert_allclose(np.asarray(s_outs[0]),
                               np.asarray(ref_outs[0]), rtol=2e-5,
                               atol=1e-6)


def test_gspmd_aux_stays_replicated_no_step2_recompile():
    """BN moving stats were placed replicated by init_state but came
    back sharded over fsdp via GSPMD propagation — the drifted layout
    missed the jit cache and every SpecLayout run paid a full step-2
    recompile (caught by review: a GSPMD run's wall time a step stood
    far above its telemetry p50). The step must pin aux back to
    the replicated layout, and the executable must be compiled ONCE."""
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=32)
    net = mx.sym.BatchNorm(net, name="bn", fix_gamma=False)
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=8)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    step = make_train_step(net, optimizer="adam",
                           optimizer_params={"rescale_grad": 1.0 / 64},
                           layout=_layout(), optimizer_sharding="zero1")
    X, y = _toy()
    state = step.init_state(Xavier(), {"data": X.shape,
                                       "softmax_label": y.shape})
    b = step.place_batch({"data": X, "softmax_label": y})
    rng = jax.random.PRNGKey(0)
    for _ in range(3):
        state, _ = step(state, b, 0.05, rng)
        for k, v in state[2].items():
            assert tuple(v.sharding.spec) == (), (k, v.sharding)
    if hasattr(step._jit_step, "_cache_size"):
        assert step._jit_step._cache_size() == 1   # one executable


# ---------------------------------------------------------------------------
# the Module path binds the same layout
# ---------------------------------------------------------------------------

def test_module_accepts_layout_and_shards_params():
    """Module/executor_group bind through the same placement layer:
    params live per the layout's rules, batches shard over data×fsdp,
    and training still converges on the toy problem."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((96, 16)).astype(np.float32)
    y = (X @ rng.standard_normal(16) > 0).astype(np.float32)  # separable
    lay = _layout()
    mod = mx.mod.Module(_mlp(classes=2), context=mx.cpu(), layout=lay)
    train = io.NDArrayIter(X, y, batch_size=32)
    mod.fit(train, num_epoch=8, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9})
    w = mod._exec_group.execs[0].arg_dict["fc1_weight"]._data
    local = w.sharding.shard_shape(w.shape)
    assert np.prod(local) < np.prod(w.shape), w.sharding  # really sharded
    assert dict(mod.score(train, "acc"))["accuracy"] > 0.9


def test_module_layout_batch_must_divide_shards():
    from mxnet_tpu.base import MXNetError
    X, y = _toy(n=30, classes=2)
    mod = mx.mod.Module(_mlp(classes=2), context=mx.cpu(),
                        layout=_layout())
    with pytest.raises(MXNetError, match="divisible"):
        mod.bind([("data", (30, 16))], [("softmax_label", (30,))])


# ---------------------------------------------------------------------------
# telemetry + constraint knob
# ---------------------------------------------------------------------------

def test_layout_bind_telemetry_gauges():
    from mxnet_tpu import telemetry
    step = _make_step(layout=_layout(), optimizer_sharding="zero1")
    X, y = _toy()
    state = step.init_state(Xavier(), {"data": X.shape,
                                       "softmax_label": y.shape})
    assert telemetry.gauge("gspmd.sharded_params").value >= 1
    opt_bytes = telemetry.gauge("gspmd.opt_state_bytes_per_dev").value
    want = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
               * s.dtype.itemsize
               for states in state[1].values() for s in states)
    assert opt_bytes == want


def test_constrain_acts_knob_off_still_trains():
    from mxnet_tpu import config as cfg
    assert cfg.get("MXNET_GSPMD_CONSTRAIN_ACTS") is True
    lay = SpecLayout(_dxf_mesh(), min_shard_size=0,
                     constrain_activations=False)
    assert lay.act_parts(2) is None
    step = _make_step(layout=lay, optimizer_sharding="zero1")
    X, y = _toy()
    state = step.init_state(Xavier(), {"data": X.shape,
                                       "softmax_label": y.shape})
    b = step.place_batch({"data": X, "softmax_label": y})
    state, outs = step(state, b, 0.05, jax.random.PRNGKey(0))
    assert np.isfinite(np.asarray(outs[0])).all()
