"""The journal-backed perf-regression gate (ISSUE 12,
docs/perf_gates.md): fingerprint extraction, the --bless round trip,
and — the load-bearing part — that each class of injected regression
(an extra per-step host sync, a steady-state recompile, a missing
trace span, a vanished counter) FAILS the gate with a diagnostic
naming the PR-won property it protects. The gate counts and does not
time: a fingerprint carries no `times`."""
import copy
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

pytestmark = pytest.mark.gate


def _load_perf_gate():
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(REPO, "tools", "perf_gate.py"))
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)
    return pg


@pytest.fixture(scope="module")
def pg():
    return _load_perf_gate()


# ---------------------------------------------------------------------------
# synthetic journal/trace for the pure-function tests (no subprocess)
# ---------------------------------------------------------------------------

def _synthetic_records():
    journal = [
        {"v": 1, "kind": "run_start", "schema": 1},
        {"v": 1, "kind": "event", "event": "fit.start"},
        {"v": 1, "kind": "event", "event": "compile",
         "fields": {"wall_ms": 100.0}},
        {"v": 1, "kind": "step", "step": 0, "wall_ms": 120.0,
         "samples": 24, "compile": True},
        {"v": 1, "kind": "step", "step": 1, "wall_ms": 10.0,
         "samples": 24},
        {"v": 1, "kind": "step", "step": 2, "wall_ms": 12.0,
         "samples": 24},
        {"v": 1, "kind": "event", "event": "gate.probe",
         "fields": {"max_step_syncs_steady": 1}},
        {"v": 1, "kind": "snapshot", "metrics": {
            "host_syncs": {"type": "counter", "value": 4},
            "ps.retries": {"type": "counter", "value": 2},
            "trainstep.jit_cache_size": {"type": "gauge", "value": 1.0},
            "trainstep.step_ms": {"type": "histogram", "count": 3},
        }},
    ]
    trace = [
        {"v": 1, "kind": "trace_start", "schema": 1},
        {"v": 1, "kind": "span", "name": "train.step", "span": "9.1",
         "parent": None, "trace": "9.0"},
        {"v": 1, "kind": "span", "name": "step.window_wait",
         "span": "9.2", "parent": "9.1", "trace": "9.0"},
        {"v": 1, "kind": "instant", "name": "guardrail.masked_step",
         "parent": "9.1", "trace": "9.0"},
    ]
    return journal, trace


def _fingerprint(pg, scenario="trainstep"):
    journal, trace = _synthetic_records()
    return pg.extract_fingerprint(scenario, journal, trace)


def _baseline(pg, fp):
    return {"scenario": fp["scenario"],
            "fingerprint": copy.deepcopy(fp)}


# ---------------------------------------------------------------------------
# fingerprint extraction round trip
# ---------------------------------------------------------------------------

def test_fingerprint_extraction_and_self_compare(pg):
    fp = _fingerprint(pg)
    assert fp["counts"]["journal_schema"] == 1
    assert fp["counts"]["steps"] == 3
    assert fp["counts"]["compile_events"] == 1
    assert fp["counts"]["compile_steps"] == [0]
    assert fp["counts"]["counters"]["ps.retries"] == 2
    # gauge values normalize to int so baselines read cleanly
    assert fp["counts"]["gauges"]["trainstep.jit_cache_size"] == 1
    assert fp["counts"]["probe"]["max_step_syncs_steady"] == 1
    assert fp["trace"]["spans"] == ["step.window_wait", "train.step"]
    assert fp["trace"]["edges"] == [
        "train.step>guardrail.masked_step",
        "train.step>step.window_wait"]
    assert pg.compare(_baseline(pg, fp), fp) == []
    # json round trip is identity (committed baselines are json)
    again = json.loads(json.dumps(fp))
    assert pg.compare(_baseline(pg, fp), again) == []


def test_fingerprint_deterministic_ordering(pg):
    """Two extractions over the same records serialize identically —
    the run-twice determinism contract, minus the subprocess."""
    a = json.dumps(_fingerprint(pg), sort_keys=True)
    b = json.dumps(_fingerprint(pg), sort_keys=True)
    assert a == b


# ---------------------------------------------------------------------------
# injected regressions are caught, with the right diagnostic
# ---------------------------------------------------------------------------

def _fails_for(pg, mutate, **kw):
    fp = _fingerprint(pg)
    base = _baseline(pg, fp)
    live = copy.deepcopy(fp)
    mutate(live)
    fails = pg.compare(base, live, **kw)
    assert fails, "mutation was not caught"
    return "\n".join(f.format() for f in fails)


def test_extra_host_sync_names_pr2(pg):
    msg = _fails_for(pg, lambda fp: fp["counts"]["probe"].update(
        max_step_syncs_steady=2))
    assert "max_step_syncs_steady" in msg
    assert "ONE blocking host sync" in msg


def test_recompile_names_pr11(pg):
    msg = _fails_for(pg, lambda fp: fp["counts"]["gauges"].update(
        {"trainstep.jit_cache_size": 2}))
    assert "step-2-recompile" in msg or "recompile" in msg
    assert "donated" in msg

    msg = _fails_for(
        pg, lambda fp: fp["counts"].update(compile_steps=[0, 2]))
    assert "compile" in msg


def test_missing_span_names_pr10(pg):
    def cut(fp):
        fp["trace"]["spans"].remove("step.window_wait")
        fp["trace"]["edges"].remove("train.step>step.window_wait")
    msg = _fails_for(pg, cut)
    assert "trace." in msg and "span vocabulary" in msg


def test_missing_counter_names_pr1(pg):
    def cut(fp):
        del fp["counts"]["counters"]["ps.retries"]
    msg = _fails_for(pg, cut)
    assert "ps.retries" in msg and "missing from live run" in msg
    assert "retry" in msg


def test_schema_bump_is_caught(pg):
    msg = _fails_for(pg, lambda fp: fp["counts"].update(
        journal_schema=2))
    assert "journal_schema" in msg and "SCHEMA_VERSION" in msg


def test_new_untracked_field_asks_for_rebless(pg):
    msg = _fails_for(pg, lambda fp: fp["counts"]["counters"].update(
        {"brand.new_counter": 1}))
    assert "re-bless" in msg


# ---------------------------------------------------------------------------
# the gate does not time; a baseline from when it did is refused
# ---------------------------------------------------------------------------

def test_no_times_and_old_schema_baseline_refused(pg):
    fp = _fingerprint(pg)
    assert set(fp) == {"gate_schema", "scenario", "counts", "trace"}
    # the steps' wall_ms, whatever they read, are not in the fingerprint
    journal, trace = _synthetic_records()
    for r in journal:
        if r["kind"] == "step":
            r["wall_ms"] *= 40.0
    assert pg.extract_fingerprint("trainstep", journal, trace) == fp
    # a schema-1 baseline (it carried `times` and a `time_ratio`) is
    # refused whole by the schema check, not compared on what is left
    old = _baseline(pg, fp)
    old["time_ratio"] = 3.0
    old["fingerprint"]["gate_schema"] = 1
    old["fingerprint"]["times"] = {"step_ms_p50": 10.0}
    fails = pg.compare(old, fp)
    assert [f.path for f in fails] == ["gate_schema"]
    assert (fails[0].baseline, fails[0].live) == (1, pg.GATE_SCHEMA)


# ---------------------------------------------------------------------------
# committed baselines stay well-formed
# ---------------------------------------------------------------------------

def test_committed_baselines_parse_and_cover_scenarios(pg):
    bdir = os.path.join(REPO, "perf_baselines")
    files = {f[:-5] for f in os.listdir(bdir) if f.endswith(".json")}
    assert files == set(pg.SCENARIOS), \
        "perf_baselines/ out of sync with SCENARIOS"
    for name in files:
        base = pg.load_baseline(name)
        fp = base["fingerprint"]
        assert fp["gate_schema"] == pg.GATE_SCHEMA
        assert fp["scenario"] == name
        assert set(fp) == {"gate_schema", "scenario", "counts",
                           "trace"}, name
        assert "time_ratio" not in base, name
        assert fp["counts"]["journal_schema"] == 1
        # a baseline must compare clean against itself
        assert pg.compare(base, fp) == []


def test_gate_reports_dead_scenario_cleanly(pg, tmp_path):
    """A scenario child that dies before producing any journal is a
    gate FAILURE with the child's stderr attached — never a traceback
    (every exit of the gate is one parseable verdict). The
    child resolves the scenario name itself, so a name only the parent
    knows makes it die deterministically before opening the journal."""
    fp, err = pg.run_scenario("no_such_scenario_xyz",
                              str(tmp_path / "out"))
    assert fp is None and isinstance(err, str)
    assert "no_such_scenario_xyz" in err and "rc=" in err


# ---------------------------------------------------------------------------
# end-to-end: one real scenario, bless + deterministic re-check
# ---------------------------------------------------------------------------

def test_trainstep_scenario_bless_and_recheck_deterministic(
        pg, tmp_path):
    """Acceptance: run the trainstep scenario twice back-to-back on
    CPU; --bless from run 1, compare run 2 — every count/shape field
    identical."""
    fp1, err = pg.run_scenario("trainstep", str(tmp_path / "r1"))
    assert err is None, err
    path = pg.bless("trainstep", fp1, str(tmp_path / "bl"))
    assert os.path.exists(path)
    base = pg.load_baseline("trainstep", str(tmp_path / "bl"))
    assert pg.compare(base, fp1) == []

    fp2, err = pg.run_scenario("trainstep", str(tmp_path / "r2"))
    assert err is None, err
    fails = pg.compare(base, fp2)
    assert fails == [], "\n".join(f.format() for f in fails)
    assert json.dumps(fp1["counts"], sort_keys=True) \
        == json.dumps(fp2["counts"], sort_keys=True)
    assert json.dumps(fp1["trace"], sort_keys=True) \
        == json.dumps(fp2["trace"], sort_keys=True)
    # the scenario exercises the load-bearing probes
    assert fp1["counts"]["probe"]["max_step_syncs_steady"] <= 1
    assert fp1["counts"]["gauges"]["trainstep.jit_cache_size"] == 1
    assert fp1["counts"]["counters"]["guardrail.masked_steps"] == 1


@pytest.mark.slow
def test_full_gate_all_scenarios_bless_then_pass(pg, tmp_path):
    """Every scenario, blessed then re-checked."""
    rc = pg.main(["--bless", "--baselines", str(tmp_path / "bl"),
                  "--keep", str(tmp_path / "runs1")])
    assert rc == 0
    rc = pg.main(["--baselines", str(tmp_path / "bl"),
                  "--keep", str(tmp_path / "runs2")])
    assert rc == 0


# ---------------------------------------------------------------------------
# tooling glue
# ---------------------------------------------------------------------------

def test_smoke_wrappers_route_through_perf_gate_sh(pg):
    """The CI lint's contract, asserted from pytest too: every
    *_smoke.sh actually DELEGATES to tools/perf_gate.sh (an exec
    line, not a mere mention in a comment)."""
    import re
    tools = os.path.join(REPO, "tools")
    wrappers = [f for f in os.listdir(tools) if f.endswith("_smoke.sh")]
    assert len(wrappers) >= 4
    pat = re.compile(r'^\s*exec .*perf_gate\.sh"? --only', re.M)
    for f in wrappers:
        with open(os.path.join(tools, f)) as fh:
            assert pat.search(fh.read()), f


def test_perf_gate_sh_sections_parse():
    out = subprocess.run(["bash", "-n",
                          os.path.join(REPO, "tools", "perf_gate.sh")],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


def test_telemetry_report_diff(tmp_path):
    """--diff: step-time/throughput deltas, counter deltas and
    event-vocabulary changes between two journals."""
    spec = importlib.util.spec_from_file_location(
        "telemetry_report",
        os.path.join(REPO, "tools", "telemetry_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    def write(path, step_ms, counters, events):
        recs = [{"v": 1, "kind": "run_start", "schema": 1}]
        for ev in events:
            recs.append({"v": 1, "kind": "event", "event": ev})
        for i in range(4):
            recs.append({"v": 1, "kind": "step", "step": i,
                         "wall_ms": step_ms, "samples": 32})
        recs.append({"v": 1, "kind": "snapshot", "metrics": {
            k: {"type": "counter", "value": v}
            for k, v in counters.items()}})
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    old, new = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    write(old, 10.0, {"host_syncs": 4, "ps.retries": 1}, ["fit.start"])
    write(new, 20.0, {"host_syncs": 9}, ["fit.start", "serve.shed"])
    diff = tr.diff_summaries(tr.summarize(tr.load(old)),
                             tr.summarize(tr.load(new)))
    assert diff["step_ms"]["p50"]["pct"] == 100.0
    assert diff["counter_deltas"]["host_syncs"] == {"old": 4, "new": 9}
    assert diff["counter_deltas"]["ps.retries"]["new"] is None
    assert diff["events_added"] == ["serve.shed"]
    assert "step_ms.p50" in diff["suspects"]
    text = tr.format_diff(diff, old, new)
    assert "regression suspects" in text and "host_syncs" in text
    # CLI surface
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "telemetry_report.py"),
         "--diff", old, new],
        capture_output=True, text=True)
    assert out.returncode == 0 and "journal diff" in out.stdout
