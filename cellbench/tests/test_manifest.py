"""BENCHMARK.json against the contract's static rules, and every name
in it against the file it points to."""
import json
import os
import re

import pytest

from cellbench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(run.ROOT, "BENCHMARK.json")


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "cellbench/run.py"]
    assert manifest["paths"] == ["cellbench"]
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells fits the driver's day
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    size = os.path.getsize(os.path.join(run.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_lines(manifest):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry and group in ("configs", "workloads",
                                              "per_layer"):
                    text = entry[key]
                    assert 1 <= len(text) <= 200 and "\n" not in text \
                        and "\t" not in text, (entry["name"], key)
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in manifest[g]]
    assert len(names) == len(set(names))
    for g in ("end_to_end", "per_layer"):
        for m in manifest[g]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_cells_resolve_and_report(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    used = set()
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)
    for w in manifest["workloads"]:
        cell, entry, cfg, traffic = run.resolve(manifest, w["name"])
        used.add(entry["name"])
        assert entry["file"].startswith("cellbench/configs/")
        assert w["name"].startswith(w["config"] + ".")
        for key in entry["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
        family = cfg["family"]
        for kind in ("models", "reference", "ops"):
            assert os.path.exists(os.path.join(
                run.HERE, kind, family + ".py")), (kind, family)
        assert os.path.exists(os.path.join(
            run.HERE, "drive", traffic["kind"] + ".py"))
        mine = run.metrics_for(manifest, "end_to_end", w["name"])
        assert len(mine) >= 2          # setup_s and one more
        layer = run.metrics_for(manifest, "per_layer", w["name"])
        assert layer
        reported = {m["name"] for m in mine}
        for m in layer:
            assert m["moves"] in reported, (m["name"], w["name"])
    assert used == {c["name"] for c in manifest["configs"]}
    for g in ("end_to_end", "per_layer"):
        for m in manifest[g]:
            assert set(m.get("workloads", [])) <= cells


def test_every_per_layer_metric_has_its_file(manifest):
    for m in manifest["per_layer"]:
        spec = run.load_json(run.HERE, "metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            run.HERE, "readers", spec["reader"] + ".py"))
        assert spec["unit"] == m["unit"]
        assert spec["layer"] == m["layer"]
        assert spec["moves"] == m["moves"]
        assert spec["cells"] == m["workloads"]


def test_peaks_table_names_its_source():
    table = run.load_json(run.HERE, "peaks.json")
    assert table["source"]
    v5e = table["device_kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    from cellbench.readers import utilization
    with pytest.raises(KeyError):
        utilization.peak("TPU v9", "bf16_flops_per_s")
