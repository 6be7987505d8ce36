"""The harness end to end at toy size on the CPU: the chip check
refuses, and with the check stepped over the rest of a run prints the
contract's line, refuses device metrics, and calls a broken timed path
not correct."""
import copy
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from cellbench import run
from cellbench.readers import counter
from cellbench.tests import toy

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "cellbench/run.py", "--workload",
         "opt-1.3b.serve_saturated", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=run.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 3
    assert "needs 1 TPU chip" in out.stderr
    assert not [ln for ln in out.stdout.splitlines()
                if ln.startswith("{")]


def _toy_main(monkeypatch, capsys, cfg, traffic, workload, argv_extra=()):
    """`run.main` with the look for a chip stepped over and the cell's
    files swapped for toy sizes."""
    fake = [types.SimpleNamespace(platform="tpu",
                                  device_kind="TPU v5 lite")]
    monkeypatch.setattr(run, "require_chips", lambda chips: fake)
    real = run.resolve

    def resolve(manifest, name):
        cell, entry, _cfg, _traffic = real(manifest, name)
        return cell, entry, cfg, traffic

    monkeypatch.setattr(run, "resolve", resolve)
    run.main(["--workload", workload, "--seed", str(2 ** 31 + 9),
              "--seconds", "1.5", *argv_extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_serve_line_has_exactly_the_contract_s_keys(monkeypatch, capsys):
    workload = "opt-1.3b.serve_saturated"
    line, lines = _toy_main(monkeypatch, capsys, toy.OPT, toy.DECK,
                            workload, ["--trace", "0"])
    assert set(line) == LINE_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    want = {m["name"] for m in
            run.metrics_for(manifest, "end_to_end", workload)}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # every number compared is printed beside its limit
    compared = [ln for ln in lines if "compared" in ln]
    assert any("gap_widest" in ln and "limit" in ln for ln in compared)


def test_a_cpu_trace_gives_no_device_metrics(monkeypatch, capsys):
    with pytest.raises(ValueError, match="no device operation"):
        _toy_main(monkeypatch, capsys, toy.OPT, toy.DECK,
                  "opt-1.3b.serve_saturated", ["--trace", "1"])
    out = capsys.readouterr().out
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_a_token_altered_where_it_is_produced_is_not_correct():
    """The timed path broken underneath: the decode step's logits are
    rolled by one id, so every token after a request's first is the
    neighbour of the one the model puts first."""
    def break_step(decoder):
        sound = decoder._step_fn

        def rolled(args, aux, rng):
            outs, new_aux = sound(args, aux, rng)
            return [np.roll(np.asarray(outs[0], np.float32), 1, -1)
                    ] + list(outs[1:]), new_aux

        decoder._step_fn = rolled

    good = run.run_cell(toy.OPT, toy.DECK, 5, 1.5)
    assert good["correct"] is True
    # what the window hands to the `counter` reader of admission: a
    # prefill runs the pool's 2 rows, whatever the group it holds
    r = good["readings"]
    assert r["stats.prefill_rows"] == 2 * r["stats.prefills"] > 0
    assert 0 < r["stats.admit_rounds"] <= r["stats.prefills"]
    spec = run.load_json(run.HERE, "metrics",
                         "prefill_rows_real_share.json")
    share = counter.read(r, **spec["args"])
    assert share == 100.0 * r["stats.admitted"] / r["stats.prefill_rows"]
    # one or two real rows of 2 (a round in flight at a snapshot may
    # have counted its rows and not yet its requests)
    assert 45.0 < share <= 100.0
    bad = run.run_cell(toy.OPT, toy.DECK, 5, 1.5, program_hook=break_step)
    assert bad["correct"] is False
    failed = [c["name"] for c in bad["checks"] if not c["ok"]]
    assert {"gap_mean", "logit_err"} <= set(failed)
