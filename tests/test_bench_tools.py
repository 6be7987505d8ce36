"""Bench tooling guards: the HLO collective-traffic parser and the
workload catalog (every --network choice must build a symbol)."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_collective_bytes_parser():
    from bench_scaling import collective_bytes

    txt = "\n".join([
        "%all-reduce.82 = (f32[16,3,3,3]{3,2,1,0}, f32[10]{0}, "
        "/*index=2*/f32[10,64]{1,0}) all-reduce(%a, %b, %c), channel_id=1",
        "%gte = f32[16]{0} get-tuple-element(%all-reduce.82), index=4",
        "%ar2 = f32[8,8]{1,0} all-reduce(%dot.1), channel_id=2",
        "%s = f32[4]{0} all-reduce-start(%x), channel_id=3",
        "%d = f32[4]{0} all-reduce-done(%s)",
        "%ag = bf16[64,32]{1,0} all-gather(%p), dimensions={0}",
        "%rs = f32[16]{0} reduce-scatter(%q), dimensions={0}",
        "%cp = bf16[2,8]{1,0} collective-permute(%r), "
        "source_target_pairs={{0,1}}",
    ])
    got = collective_bytes(txt)
    assert got == {
        # variadic tuple (16*27 + 10 + 640 floats) + plain (64) + async
        # start (4; the -done half must not double count)
        "all-reduce": (16 * 27 + 10 + 640) * 4 + 64 * 4 + 16,
        "all-gather": 64 * 32 * 2,
        "reduce-scatter": 64,
        "collective-permute": 32,
    }, got
    # operand references and non-collective lines contribute nothing
    assert collective_bytes("%x = f32[8]{0} add(%a, %b)") == {}


def test_collective_bytes_on_real_dp_step():
    """End-to-end: the parser must see the grad all-reduce of a real
    dp-sharded train step, sized like the model's parameters."""
    import jax

    import mxnet_tpu as mx
    from bench_scaling import collective_bytes
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.parallel import data_parallel_mesh, make_train_step

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=32)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mesh = data_parallel_mesh()
    step = make_train_step(net, mesh=mesh)
    state = step.init_state(Xavier(), {"data": (16, 8),
                                       "softmax_label": (16,)})
    batch = step.place_batch(
        {"data": np.zeros((16, 8), np.float32),
         "softmax_label": np.zeros((16,), np.float32)})
    txt = step.lower(state, batch, 0.1,
                     jax.random.PRNGKey(0)).compile().as_text()
    got = collective_bytes(txt)
    # fc1: weight (32,8) + bias (32) = 288 floats = 1152 bytes of grads
    assert got.get("all-reduce", 0) >= 288 * 4, got


def test_bench_network_catalog_builds():
    from bench import _IMAGE_NETS

    from mxnet_tpu import models

    for name, (kw, batch, baseline, gmacs, image) in _IMAGE_NETS.items():
        kwargs = dict(kw)
        kwargs.setdefault("num_classes", 1000)
        if kwargs["network"] == "resnet":
            kwargs["image_shape"] = (3, image, image)
        sym = models.get_symbol(**kwargs)
        assert sym.list_outputs(), name
        assert batch > 0 and baseline > 0 and gmacs > 0
        assert image in (224, 299), name
    # inception-v3's baseline/GMACs are 299px figures
    assert _IMAGE_NETS["inception-v3"][4] == 299


def _load_perf_tables():
    import importlib.util
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "perf_tables", os.path.join(repo, "tools", "perf_tables.py"))
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    return pt


def test_perf_tables_newest_capture_wins(tmp_path):
    """Advisor r4: JSONL captures append chronologically; the rendered
    table must show the LAST record per key, not the first."""
    import json
    pt = _load_perf_tables()
    rec = {"metric": "resnet50_train_throughput", "unit": "img/s",
           "vs_baseline": 1.0, "mfu": 0.2, "step_time_ms": 50.0}
    lines = [dict(rec, value=1000.0), dict(rec, value=2222.0)]
    (tmp_path / "sweep.jsonl").write_text(
        "\n".join(json.dumps(r) for r in lines) + "\n")
    table = pt.training_table(pt.load_records(str(tmp_path)))
    assert "2222" in table and "1000" not in table


def test_perf_tables_renders_from_committed_captures():
    """tools/perf_tables.py turns bench_out/ artifacts into the docs
    tables; must at least render the committed training captures."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pt = _load_perf_tables()
    recs = pt.load_records(os.path.join(repo, "bench_out"))
    assert any(r["metric"] == "resnet50_train_throughput"
               for r in recs)
    table = pt.training_table(recs)
    assert "resnet50" in table and "| workload |" in table


def test_perf_tables_excludes_ab_experiment_rows(tmp_path):
    """A/B rows (tagged ab_config, as bench_out/ab_regression.jsonl's
    are) measure deliberately non-default configs; a newer experiment
    row must never shadow the headline capture."""
    import json
    pt = _load_perf_tables()
    rec = {"metric": "resnet50_train_throughput", "unit": "img/s",
           "vs_baseline": 1.0, "mfu": 0.2, "step_time_ms": 50.0}
    (tmp_path / "resnet50.json").write_text(
        json.dumps(dict(rec, value=2451.0)) + "\n")
    (tmp_path / "ab_regression.jsonl").write_text(
        json.dumps(dict(rec, value=1903.0,
                        ab_config="bn_stats_dot")) + "\n")
    # the jsonl is "newer" on disk
    os.utime(tmp_path / "resnet50.json", (1, 1))
    table = pt.training_table(pt.load_records(str(tmp_path)))
    assert "2451" in table and "1903" not in table


@pytest.mark.gate
def test_bench_killed_mid_run_emits_parseable_stub():
    """ISSUE 12 satellite: a bench killed mid-run BEFORE producing any
    journal/capture must still emit one parseable diagnostic JSON line
    (bench_common.install_death_stub). Deterministic via the
    BENCH_TEST_HANG_AFTER_ARM hook: the bench arms its handlers, tells
    us on stderr, and hangs until we deliver the SIGTERM."""
    import json
    import signal
    import subprocess
    import time

    env = dict(os.environ, BENCH_TEST_HANG_AFTER_ARM="60")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench_serve.py"),
         "--requests", "1"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO)
    try:
        deadline = time.time() + 60
        armed = False
        while time.time() < deadline:
            line = proc.stderr.readline()
            if "BENCH_DEATH_STUB_ARMED" in line:
                armed = True
                break
            if line == "" and proc.poll() is not None:
                break
        assert armed, "bench never armed its death stub"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 1
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    assert lines, "killed bench printed nothing"
    rec = json.loads(lines[-1])          # parseable — the contract
    assert rec["metric"] == "serve_throughput"
    assert rec["value"] is None
    assert "signal" in rec["error"]
    assert rec["signal"] == int(signal.SIGTERM)
