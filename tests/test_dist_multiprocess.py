"""Multi-process-on-one-host distributed test — the SURVEY §4 pattern
(reference tests/nightly/dist_sync_kvstore.py launched with the `local`
dmlc_tracker): two local processes form a cluster via the DMLC_* env
shim (parallel/dist.py) and run a real cross-process collective.
"""
import os
import socket
import subprocess
import sys

import pytest

_WORKER_SRC = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.parallel import dist
import jax, jax.numpy as jnp

dist.init()
n = int(os.environ["DMLC_NUM_WORKER"])
assert dist.size() == n, dist.size()
rank = dist.rank()

from jax.experimental import multihost_utils
got = multihost_utils.process_allgather(jnp.array([rank + 10.0]))
np.testing.assert_allclose(np.sort(np.asarray(got).ravel()),
                           [10.0 + i for i in range(n)])

# kvstore reports cluster identity through the same plumbing
kv = mx.kv.create("dist_sync")
assert kv.num_workers == n and kv.rank == rank

# dist_sync value semantics (reference tests/nightly/dist_sync_kvstore.py):
# init broadcasts rank 0's value; push sums across workers exactly
init_val = mx.nd.ones((3, 2)) * (100 + rank)   # ranks disagree on purpose
kv.init("w", init_val)
out = mx.nd.zeros((3, 2))
kv.pull("w", out=out)
np.testing.assert_allclose(out.asnumpy(), 100.0)   # rank 0 won

kv.push("w", mx.nd.ones((3, 2)) * (rank + 1))      # sum 1..n
kv.pull("w", out=out)
np.testing.assert_allclose(out.asnumpy(), n * (n + 1) / 2.0)
print("WORKER_OK", rank)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_workers(tmp_path, worker_src, marker, extra_env=None,
                 timeout=180, n=2):
    """Spawn n cluster workers, collect output with a kill-on-timeout
    guard, assert rc=0 + per-rank marker lines."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    script = tmp_path / "worker.py"
    script.write_text(worker_src)

    procs = []
    for wid in range(n):
        env = dict(os.environ)
        env.update({
            "REPO": repo,
            "PYTHONPATH": repo,          # only the repo on the path
            "JAX_PLATFORMS": "cpu",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(n),
            "DMLC_WORKER_ID": str(wid),
            "DMLC_ROLE": "worker",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("worker cluster timed out:\n%s" % "\n".join(outs))
    for wid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, "worker %d failed:\n%s" % (wid, out)
        assert "%s %d" % (marker, wid) in out, out



@pytest.mark.slow
def test_two_process_cluster(tmp_path):
    _run_workers(tmp_path, _WORKER_SRC, "WORKER_OK")


@pytest.mark.slow
def test_four_process_cluster(tmp_path):
    """Same dist_sync contract over a 4-worker cluster — the DCN path
    beyond pairwise (allgather ordering, 4-way push reduction)."""
    _run_workers(tmp_path, _WORKER_SRC, "WORKER_OK", n=4)


def test_launch_py_local_mode(tmp_path):
    """tools/launch.py local mode (dmlc_tracker 'local' analogue): forks
    N workers with the DMLC_* env and they form one jax.distributed
    cluster."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from mxnet_tpu.parallel import dist\n"
        "dist.init()\n"
        "assert dist.size() == 2\n"
        "print('LAUNCHED-OK', dist.rank())\n" % repo)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DMLC_PS_ROOT_URI", None)
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", sys.executable, str(worker)],
        capture_output=True, text=True, timeout=240, env=env)
    assert out.returncode == 0, out.stderr[-800:]
    assert out.stdout.count("LAUNCHED-OK") == 2, out.stdout


_SPMD_WORKER_SRC = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.parallel import dist, make_mesh, make_train_step
import jax

dist.init()
assert jax.process_count() == 2
# 2 processes x 4 local virtual devices = one 8-device global data mesh
devices = jax.devices()
assert len(devices) == 8, devices

def mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=2)
    return mx.sym.SoftmaxOutput(net, name="softmax")

rng = np.random.default_rng(0)          # same data on every process
X = rng.standard_normal((32, 8)).astype(np.float32)
y = (X @ rng.standard_normal(8) > 0).astype(np.float32)

def run(mesh):
    step = make_train_step(mlp(), optimizer="sgd",
                           optimizer_params={"rescale_grad": 1.0 / 32},
                           mesh=mesh)
    mx.random.seed(3); np.random.seed(3)
    state = step.init_state(Xavier(), {"data": X.shape,
                                       "softmax_label": y.shape})
    batch = step.place_batch({"data": X, "softmax_label": y})
    k = jax.random.PRNGKey(0)
    for _ in range(4):
        state, outs = step(state, batch, 0.2, k)
    # gather replicated params to host
    return {n: np.asarray(jax.device_get(v))
            for n, v in state[0].items()}

# the REAL multi-host step: batch + grads span both processes, the
# grad all-reduce rides the cross-process transport
multi = run(make_mesh({"data": 8}, devices=devices))
# reference: same data, same seeds, single process worth of devices
single = run(make_mesh({"data": 4}, devices=jax.local_devices()))
for n in multi:
    np.testing.assert_allclose(multi[n], single[n], rtol=2e-5,
                               atol=1e-6, err_msg=n)
print("SPMD_WORKER_OK", dist.rank())
"""


@pytest.mark.slow
def test_two_process_spmd_train_step(tmp_path):
    """The full compiled train step over a GLOBAL mesh spanning two
    processes: fwd+bwd+update with the grad all-reduce crossing the
    process boundary, numerically identical to a local-mesh run — the
    DCN-path depth check on the SURVEY §4 multi-process pattern."""
    _run_workers(
        tmp_path, _SPMD_WORKER_SRC, "SPMD_WORKER_OK",
        extra_env={"XLA_FLAGS":
                   "--xla_force_host_platform_device_count=4"},
        timeout=300)
