"""The `device_scope` reader: its arithmetic on plain lists, and the
file's layout on the trace recorded on the chip and reduced under
`cellbench/testdata/` (`record_scopes.py`)."""
import json
import os

import pytest

from cellbench.readers import device_scope as ds
from cellbench.testdata import record_scopes

HERE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
KIND = "TPU v5 lite"

# two decode steps and one prefill; times in ns
OPS = [("jit(decode_step)/mamba2.step/mul:", 100.0, 10.0),
       ("jit(decode_step)/mamba2.step/reduce_sum:", 110.0, 30.0),
       ("jit(decode_step)/dot_general:", 140.0, 60.0),
       ("jit(generator_step)/mamba2.conv/add:", 300.0, 20.0),
       ("jit(generator_step)/mamba2.scan/while:", 320.0, 100.0),
       ("jit(generator_step)/mamba2.scan/while/body/dot:", 330.0, 40.0),
       ("jit(generator_step)/dot_general:", 420.0, 80.0),
       ("jit(decode_step)/mamba2.step/mul:", 600.0, 10.0),
       ("jit(decode_step)/mamba2.step_not/mul:", 610.0, 5.0),
       ("", 615.0, 5.0)]
MODULES = [("jit_decode_step(17)", 100.0, 100.0),
           ("jit_generator_step(5)", 300.0, 200.0),
           ("jit_decode_step(17)", 600.0, 50.0),
           ("jit_cache_merge(3)", 700.0, 10.0)]
VIEW = {"ops": OPS, "modules": MODULES, "prefills": [(250.0, 64, None)]}


def test_scopes_select_by_part_and_by_prefix():
    assert len(ds.under(OPS, "mamba2.step")) == 3
    assert len(ds.under(OPS, "mamba2.")) == 7     # step_not too
    assert len(ds.under(OPS, "mamba2.scan")) == 2
    assert ds.under(OPS, "decode_step") == []     # jit(...) is no part
    # the loop's body lies inside the loop: counted once
    assert ds.scope_seconds(OPS, "mamba2.scan") == pytest.approx(100e-9)
    assert ds.scope_seconds(OPS, "mamba2.step") == pytest.approx(50e-9)


def test_executions_of_a_program():
    steps = ds.executions(MODULES, "decode_step", OPS, "mamba2.step")
    assert [s for s, _ in steps] == [100.0, 600.0]
    assert [d for _, d in steps] == pytest.approx([40e-9, 10e-9])
    whole = ds.executions(MODULES, "decode_step", OPS)
    assert [d for _, d in whole] == pytest.approx([100e-9, 50e-9])
    assert ds.executions(MODULES, "decode", OPS) == []
    spans = [(250.0, 64, None), (900.0, 32, 2)]
    assert ds.prefill_at(spans, 300.0) == (64, None)
    assert ds.prefill_at(spans, 950.0) == (32, 2)
    assert ds.prefill_at(spans[:1], 200.0) is None


def test_roofline_is_least_over_taken():
    peak_b, peak_f = 819e9, 197e12
    assert ds.least_seconds(197e12, 1.0, KIND) == pytest.approx(1.0)
    assert ds.least_seconds(1.0, 819e9, KIND) == pytest.approx(1.0)
    # 8190 bytes a step: 10 ns each; two steps took 40 + 10 ns
    got = ds.roofline(VIEW, lambda: (0, 8190), KIND, "decode_step",
                      "mamba2.step")
    assert got == pytest.approx(100.0 * 20e-9 / 50e-9)
    # the whole program: 100 + 50 ns
    got = ds.roofline(VIEW, lambda: (0, 8190), KIND, "decode_step")
    assert got == pytest.approx(100.0 * 20e-9 / 150e-9)
    # the prefill takes its prompt length from the span before it
    seen = []
    got = ds.roofline(VIEW, lambda p: seen.append(p) or (peak_f * 50e-9,
                                                         1),
                      KIND, "generator_step", "mamba2.scan",
                      by_prompt=True)
    assert seen == [64] and got == pytest.approx(50.0)
    # nothing to read: a program without the scope, or without spans
    assert ds.roofline(VIEW, lambda: (0, 1), KIND, "cache_merge",
                       "mamba2.step") is None
    assert ds.roofline(dict(VIEW, prefills=[]), lambda p: (0, 1), KIND,
                       "generator_step", "mamba2.scan",
                       by_prompt=True) is None


def test_read_returns_none_where_there_is_nothing():
    assert ds.read({}, "scope_share", scope="mamba2.") is None
    readings = {"trace": {"busy_s": 1.0}, "_device_scope":
                {"ops": OPS[2:3], "modules": MODULES, "prefills": []},
                "device_kind": KIND, "cfg": {"family": "granite"},
                "traffic": {}}
    assert ds.read(readings, "scope_share", scope="mamba2.") is None
    with pytest.raises(ValueError):
        ds.read(readings, "nonsense")


def test_a_prefill_s_need_takes_the_rows_that_ran():
    """Two prefills of 100 ns of scan each: the first under a span
    that says `run=2`, the second under one that says nothing. `need`
    is asked for 2 rows and for no row count (the pool's width, here
    16), never for the span's `rows` (the real prompts)."""
    ops = OPS + [("jit(generator_step)/mamba2.scan/while:", 820.0, 100.0)]
    modules = MODULES + [("jit_generator_step(5)", 800.0, 200.0)]
    view = {"ops": ops, "modules": modules,
            "prefills": [(250.0, 64, 2), (750.0, 32, None)]}
    asked = []

    def need(prompt, rows=None):
        asked.append((prompt, rows))
        # 197 operations a row and token: 1e-12 s of the chip's peak
        return 197.0 * (16 if rows is None else rows) * prompt, 1

    got = ds.roofline(view, need, KIND, "generator_step", "mamba2.scan",
                      by_prompt=True)
    assert asked == [(64, 2), (32, None)]
    least = (2 * 64 + 16 * 32) * 1e-12
    assert got == pytest.approx(100.0 * least / 200e-9)


def _spans_file(path, spans):
    """A trace file with nothing but `mxnet.admit.prefill` spans on a
    host thread, written as `mxnet_tpu.trace.phase` leaves them: the
    attributes as the event's own stats."""
    space = ds._xplane_pb2().XSpace()
    plane = space.planes.add()
    plane.name = ds.HOST_PLANE
    plane.event_metadata[1].id = 1
    plane.event_metadata[1].name = ds.PREFILL_SPAN
    ids = {}
    line = plane.lines.add()
    line.timestamp_ns = 1000
    for offset_ns, attrs in spans:
        ev = line.events.add()
        ev.metadata_id = 1
        ev.offset_ps = offset_ns * 1000
        ev.duration_ps = 5000
        for key, value in attrs.items():
            if key not in ids:
                ids[key] = len(ids) + 1
                plane.stat_metadata[ids[key]].id = ids[key]
                plane.stat_metadata[ids[key]].name = key
            stat = ev.stats.add()
            stat.metadata_id = ids[key]
            stat.int64_value = value
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_load_keeps_a_span_s_run_and_never_its_rows(tmp_path):
    path = str(tmp_path / "spans.xplane.pb")
    _spans_file(path, [(30, {"P": 128, "rows": 3, "run": 4}),
                       (10, {"P": 64, "rows": 1}),
                       (20, {"rows": 2})])          # no P: not a prefill
    assert ds.load(path)["prefills"] == [(1010.0, 64, None),
                                         (1030.0, 128, 4)]


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "scopes.xplane.pb")
    with open(os.path.join(HERE, "scopes.expected.json")) as f:
        return path, json.load(f)


def test_the_recorded_trace_reads_as_it_did(recorded):
    path, want = recorded
    got = record_scopes.expected(path)
    for key, value in got.items():
        if isinstance(value, float):
            assert value == pytest.approx(want[key], rel=1e-9), key
        else:
            assert value == want[key], key


def test_the_recorded_trace_by_other_routes(recorded):
    """What the chip ran: three requests, so three prefills of the
    lengths asked for, each under its own span; a decode step for each
    token after a request's first; operations under all three scopes;
    every share between 0 and 100."""
    path, want = recorded
    v = ds.load(path)
    # recorded before any span carried `run`: the lengths, and no rows
    assert [(p, run) for _s, p, run in v["prefills"]] == \
        [(p, None) for p in want["prefill_lengths"]]
    assert want["scopes"] == ["mamba2.conv", "mamba2.scan", "mamba2.step"]
    assert want["prefill_lengths"] == [p for p, _n in
                                       record_scopes.REQUESTS]
    assert want["lengths_by_execution"] == want["prefill_lengths"]
    assert want["decode_steps"] == sum(n - 1 for _p, n in
                                       record_scopes.REQUESTS)
    assert {"jit_decode_step", "jit_generator_step"} <= set(
        want["modules"])
    parts = want["step_seconds"] + want["scan_seconds"] + \
        want["conv_seconds"]
    assert parts == pytest.approx(want["mamba2_seconds"], rel=1e-6)
    assert 0 < want["mamba2_seconds"] < want["all_seconds"]
    for key in ("step_roofline", "scan_roofline", "decode_roofline"):
        assert 0 < want[key] <= 100, key
    # the scope's seconds are those of the operations that carry it
    by_hand = sum(d for s, _t, d in v["ops"] if "/mamba2.step/" in s)
    assert by_hand * 1e-9 == pytest.approx(want["step_seconds"],
                                           rel=1e-6)
    # with the XLA Ops line alone, the trace reader sees the same ops
    from cellbench.readers import trace
    devices, _host = trace.load(path)
    assert sum(len(ev) for ev in devices.values()) == want["ops"]
