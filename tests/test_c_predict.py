"""The non-Python deploy surface, end to end: export a model with
Predictor.export, build the C ABI shim (_native/predict_shim.cc) and
the C host program (examples/c_predict/predict.c), run the C binary in
a clean process, and require its printed outputs to match the
in-process Python forward bit-for-bit-ish (1e-5).

Reference parity: src/c_api/c_predict_api.cc:363 + the predict-cpp
example — a C program loads an exported model and classifies without
any Python source in sight (here: without symbol source or params;
the artifact is one serialized XLA program + a meta json).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import _native
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.predictor import Predictor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_model():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=4)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(2, 8))
    rng = np.random.RandomState(7)
    init = Xavier()
    args = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        arr = mx.nd.zeros(shp)
        init(name, arr)
        args[name] = arr
    return net, args


@pytest.fixture(scope="module")
def shim():
    so = _native.build_predict_shim()
    if so is None:
        pytest.skip("toolchain/Python headers unavailable")
    return so


@pytest.fixture(scope="module")
def c_binary(shim, tmp_path_factory):
    out = tmp_path_factory.mktemp("cbin") / "predict"
    native_dir = os.path.dirname(shim)
    src = os.path.join(REPO, "examples", "c_predict", "predict.c")
    r = subprocess.run(
        ["gcc", src, "-o", str(out), "-L%s" % native_dir,
         "-lpredict_shim", "-Wl,-rpath,%s" % native_dir],
        capture_output=True, text=True, timeout=120)
    if r.returncode != 0:
        pytest.skip("cannot build C host: %s" % r.stderr[-300:])
    return str(out)


def test_c_predict_matches_python(c_binary, tmp_path):
    net, args = _small_model()
    pred = Predictor(net, args, data_names=("data",))
    x = np.random.RandomState(0).standard_normal((2, 8)).astype(
        np.float32)
    want = np.asarray(pred.forward(x)[0].asnumpy(), np.float32)

    prefix = str(tmp_path / "model")
    pred.export(prefix, {"data": (2, 8)})
    assert os.path.exists(prefix + ".stablehlo")

    raw = tmp_path / "input.f32"
    raw.write_bytes(x.tobytes())

    env = dict(os.environ)
    # clean deploy process: only the repo on the path, CPU backend
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [c_binary, prefix, str(raw), str(x.size)],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, "C host failed: %s" % r.stderr[-500:]

    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("output 0 shape")
    shape = tuple(int(v) for v in lines[0].split("shape")[1].split())
    assert shape == want.shape
    got = np.array([float(v) for v in
                    lines[1:1 + want.size]]).reshape(shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_cpp_wrapper_matches_python(shim, tmp_path):
    """mxtpu_cpp.hpp (the predict-only cpp-package analogue, N28):
    the RAII C++ host must match the in-process Python forward."""
    native_dir = os.path.dirname(shim)
    src = os.path.join(REPO, "examples", "c_predict", "predict_cpp.cc")
    binary = str(tmp_path / "predict_cpp")
    r = subprocess.run(
        ["g++", "-std=c++17", src, "-o", binary,
         "-I%s" % os.path.dirname(src), "-L%s" % native_dir,
         "-lpredict_shim", "-Wl,-rpath,%s" % native_dir],
        capture_output=True, text=True, timeout=120)
    if r.returncode != 0:
        pytest.skip("cannot build C++ host: %s" % r.stderr[-300:])

    net, args = _small_model()
    pred = Predictor(net, args, data_names=("data",))
    x = np.random.RandomState(5).standard_normal((2, 8)).astype(
        np.float32)
    want = np.asarray(pred.forward(x)[0].asnumpy(), np.float32)
    prefix = str(tmp_path / "model")
    pred.export(prefix, {"data": (2, 8)})
    raw = tmp_path / "input.f32"
    raw.write_bytes(x.tobytes())

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([binary, prefix, str(raw), str(x.size)],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, "C++ host failed: %s" % r.stderr[-500:]
    lines = r.stdout.strip().splitlines()
    shape = tuple(int(v) for v in lines[0].split("shape")[1].split())
    got = np.array([float(v) for v in
                    lines[1:1 + want.size]]).reshape(shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_amalgamated_bundle(tmp_path):
    """tools/amalgamate.py: the bundle builds and predicts with the
    FRAMEWORK SOURCE ABSENT from PYTHONPATH — the reference
    amalgamation's 'deploy without the framework' property (N29)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import amalgamate
    finally:
        sys.path.pop(0)

    net, args = _small_model()
    pred = Predictor(net, args, data_names=("data",))
    x = np.random.RandomState(3).standard_normal((2, 8)).astype(
        np.float32)
    want = np.asarray(pred.forward(x)[0].asnumpy(), np.float32)
    prefix = str(tmp_path / "export" / "m")
    os.makedirs(os.path.dirname(prefix))
    pred.export(prefix, {"data": (2, 8)})

    bundle = str(tmp_path / "bundle")
    amalgamate.amalgamate(prefix, bundle)
    r = subprocess.run(["sh", os.path.join(bundle, "build.sh")],
                       capture_output=True, text=True, timeout=180)
    if r.returncode != 0:
        pytest.skip("bundle build failed (toolchain): %s"
                    % r.stderr[-300:])

    raw = tmp_path / "input.f32"
    raw.write_bytes(x.tobytes())
    env = dict(os.environ)
    env["PYTHONPATH"] = ""            # NO framework source anywhere
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [os.path.join(bundle, "predict"),
         os.path.join(bundle, "model"), str(raw), str(x.size)],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, "bundle predict failed: %s" % \
        r.stderr[-500:]
    lines = r.stdout.strip().splitlines()
    shape = tuple(int(v) for v in lines[0].split("shape")[1].split())
    got = np.array([float(v) for v in
                    lines[1:1 + want.size]]).reshape(shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_c_train_matches_python(shim, tmp_path):
    """The training ABI (round 5, N17/N28 closure): a C program drives
    N compiled train steps through MXTpuTrain* and must land on
    EXACTLY the same trained parameters as CompiledTrainStep run
    in-process (same exported program, same seed sequence)."""
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.parallel.trainer import CompiledTrainStep

    native_dir = os.path.dirname(shim)
    src = os.path.join(REPO, "examples", "c_predict", "train.c")
    binary = str(tmp_path / "train_host")
    r = subprocess.run(
        ["gcc", src, "-o", binary, "-L%s" % native_dir,
         "-lpredict_shim", "-Wl,-rpath,%s" % native_dir],
        capture_output=True, text=True, timeout=120)
    if r.returncode != 0:
        pytest.skip("cannot build C train host: %s" % r.stderr[-300:])

    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(mx.sym.Activation(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                              name="fc1"), act_type="relu"),
        num_hidden=2, name="fc2"), name="softmax")
    step = make_train_step(net, optimizer="sgd",
                           optimizer_params={"momentum": 0.9,
                                             "rescale_grad": 1.0 / 32})
    state = step.init_state(Xavier(), {"data": (32, 8),
                                       "softmax_label": (32,)})
    rng = np.random.RandomState(0)
    X = rng.standard_normal((32, 8)).astype(np.float32)
    y = (X @ rng.standard_normal(8) > 0).astype(np.float32)
    batch = step.place_batch({"data": X, "softmax_label": y})
    prefix = str(tmp_path / "m")
    step.export(prefix, state, batch)

    n_steps, lr = 25, 0.2
    ref = CompiledTrainStep.load(prefix)
    for _ in range(n_steps):
        outs = ref.step({"data": X, "softmax_label": y}, lr)
    want_out = np.asarray(outs[0], np.float32)
    want_w = np.asarray(ref.get_params()["fc1_weight"], np.float32)

    (tmp_path / "x.f32").write_bytes(X.tobytes())
    (tmp_path / "y.f32").write_bytes(y.tobytes())
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [binary, prefix, str(tmp_path / "x.f32"), str(X.size),
         str(tmp_path / "y.f32"), str(y.size), str(n_steps), str(lr),
         "fc1_weight"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, "C train host failed: %s" % \
        r.stderr[-500:]

    lines = r.stdout.strip().splitlines()
    oshape = tuple(int(v) for v in lines[0].split("shape")[1].split())
    assert oshape == want_out.shape
    got_out = np.array([float(v) for v in
                        lines[1:1 + want_out.size]]).reshape(oshape)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5,
                               atol=1e-6)
    pline = 1 + want_out.size
    assert lines[pline].startswith("param fc1_weight shape")
    pshape = tuple(int(v) for v in
                   lines[pline].split("shape")[1].split())
    assert pshape == want_w.shape
    got_w = np.array([float(v) for v in
                      lines[pline + 1:pline + 1 + want_w.size]]
                     ).reshape(pshape)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5, atol=1e-6)
    # and the C-driven training moved the weights off their initial
    # exported values (the allclose above would also pass for a no-op
    # if the reference run were broken the same way)
    w0 = np.asarray(
        jax.device_get(state[0]["fc1_weight"]), np.float32)
    assert np.abs(got_w - w0).max() > 1e-4


def test_amalgamated_train_bundle(tmp_path):
    """A train-capable amalgamated bundle (TrainStep.export + the
    generated mxtpu_train_min.py) must train from C with the
    FRAMEWORK SOURCE ABSENT from PYTHONPATH and reproduce the
    in-process trajectory exactly."""
    from mxnet_tpu.parallel import make_train_step
    from mxnet_tpu.parallel.trainer import CompiledTrainStep

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import amalgamate
    finally:
        sys.path.pop(0)

    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2, name="fc1"),
        name="softmax")
    step = make_train_step(net, optimizer="sgd",
                           optimizer_params={"momentum": 0.9,
                                             "rescale_grad": 1.0 / 16})
    state = step.init_state(Xavier(), {"data": (16, 8),
                                       "softmax_label": (16,)})
    rng = np.random.RandomState(2)
    X = rng.standard_normal((16, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    batch = step.place_batch({"data": X, "softmax_label": y})
    prefix = str(tmp_path / "export" / "m")
    os.makedirs(os.path.dirname(prefix))
    step.export(prefix, state, batch)

    n_steps, lr = 10, 0.2
    ref = CompiledTrainStep.load(prefix)
    for _ in range(n_steps):
        ref.step({"data": X, "softmax_label": y}, lr)
    want_w = np.asarray(ref.get_params()["fc1_weight"], np.float32)

    bundle = str(tmp_path / "bundle")
    amalgamate.amalgamate(prefix, bundle)
    assert os.path.exists(os.path.join(bundle, "mxtpu_train_min.py"))
    r = subprocess.run(["sh", os.path.join(bundle, "build.sh")],
                       capture_output=True, text=True, timeout=180)
    if r.returncode != 0:
        pytest.skip("bundle build failed (toolchain): %s"
                    % r.stderr[-300:])

    (tmp_path / "x.f32").write_bytes(X.tobytes())
    (tmp_path / "y.f32").write_bytes(y.tobytes())
    env = dict(os.environ)
    env["PYTHONPATH"] = ""            # NO framework source anywhere
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [os.path.join(bundle, "train"), os.path.join(bundle, "model"),
         str(tmp_path / "x.f32"), str(X.size),
         str(tmp_path / "y.f32"), str(y.size), str(n_steps), str(lr),
         "fc1_weight"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, "bundle train failed: %s" % \
        r.stderr[-500:]
    lines = r.stdout.strip().splitlines()
    osize = int(np.prod([int(v) for v in
                         lines[0].split("shape")[1].split()]))
    pline = 1 + osize
    assert lines[pline].startswith("param fc1_weight shape")
    pshape = tuple(int(v) for v in
                   lines[pline].split("shape")[1].split())
    got_w = np.array([float(v) for v in
                      lines[pline + 1:pline + 1 + want_w.size]]
                     ).reshape(pshape)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5, atol=1e-6)


def test_c_predict_error_surface(c_binary, tmp_path):
    """A bad model prefix must fail with a real error message through
    MXTpuGetLastError, not crash."""
    raw = tmp_path / "input.f32"
    raw.write_bytes(np.zeros(4, np.float32).tobytes())
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [c_binary, str(tmp_path / "nope"), str(raw), "4"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 1
    assert "create" in r.stderr
