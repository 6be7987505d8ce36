"""The reduction from the program's host spans to self times, shares,
medians and idle time by phase: on hand-made events, and on the trace
with nested `mxnet.*` phases recorded on the chip in
`cellbench/testdata/`."""
import os

import pytest

from cellbench import run
from cellbench.readers import host_spans as hs
from cellbench.readers import trace

MS = 1e6     # nanoseconds


def _thread():
    """One decode thread: an admit round with three children, then two
    steps, the second with no `wait` child."""
    return [("mxnet.serve.decode.admit", 0 * MS, 40 * MS),
            ("mxnet.admit.prefill", 2 * MS, 8 * MS),
            ("mxnet.admit.wait", 10 * MS, 20 * MS),
            ("mxnet.admit.merge", 30 * MS, 9 * MS),
            ("mxnet.serve.decode.step", 50 * MS, 20 * MS),
            ("mxnet.step.inputs", 50 * MS, 2 * MS),
            ("mxnet.step.wait", 55 * MS, 12 * MS),
            ("mxnet.serve.decode.step", 80 * MS, 10 * MS),
            ("mxnet.step.inputs", 81 * MS, 4 * MS)]


def test_parents_are_the_innermost_container():
    ev = _thread()
    parents = hs.nest(ev)
    assert parents[:4] == [None, 0, 0, 0]
    assert parents[4:7] == [None, 4, 4]
    assert parents[7:] == [None, 7]
    # three levels: the grandchild's parent is the child
    deep = [("a", 0, 100), ("b", 10, 50), ("c", 20, 10)]
    assert hs.nest(deep) == [None, 0, 1]


def test_self_time_is_duration_less_nested_children():
    got = {(n, d): s for n, d, s in hs.self_times(_thread())}
    assert got[("mxnet.serve.decode.admit", 40 * MS)] == \
        pytest.approx(3 * MS)              # 40 - (8 + 20 + 9)
    assert got[("mxnet.serve.decode.step", 20 * MS)] == \
        pytest.approx(6 * MS)
    assert got[("mxnet.admit.wait", 20 * MS)] == pytest.approx(20 * MS)
    # grandchildren are the child's to subtract, not the parent's
    deep = [("a", 0, 100), ("b", 10, 50), ("c", 20, 10)]
    assert [s for _n, _d, s in hs.self_times(deep)] == [50, 40, 10]


def test_overlapping_siblings_count_once():
    # two children that overlap one another by 10, one past the end
    parent = ("p", 0, 100)
    kids = [("k", 10, 30), ("k", 30, 30), ("k", 90, 30)]
    assert hs.covered(parent, kids) == 50 + 10
    assert hs.less_child([parent] + kids, "p", "k") == [40]


def test_share_of_the_window_and_median():
    lines = [_thread(), [("mxnet.other", 0, 5 * MS)]]
    assert hs.wall_share(lines, "mxnet.serve.decode.admit", 0.1) == \
        pytest.approx(0.4)
    # 20 - 12 = 8 ms and 10 - 0 = 10 ms: the median of two
    assert hs.median_less_child(
        lines, "mxnet.serve.decode.step", "mxnet.step.wait") == \
        pytest.approx(9 * MS)
    table = hs.phase_table(lines)
    assert table["mxnet.serve.decode.step"] == [
        2, pytest.approx(15.0), pytest.approx(0.030),
        pytest.approx(0.012)]


def test_no_span_of_the_name_reads_none():
    lines = [_thread()]
    assert hs.wall_share(lines, "mxnet.train.step", 0.1) is None
    assert hs.median_less_child(lines, "mxnet.train.step",
                                "mxnet.step.window_wait") is None
    assert hs.idle_share_under([(0, 10)], lines,
                               "mxnet.train.step") is None
    assert hs.wall_share([], "mxnet.serve.decode.admit", 0.1) is None


def test_idle_rolls_up_to_the_innermost_phase():
    dev = [("fusion.1", 0 * MS, 12 * MS), ("fusion.2", 28 * MS, 3 * MS),
           ("fusion.3", 39 * MS, 13 * MS), ("fusion.4", 66 * MS, 10 * MS),
           ("fusion.5", 96 * MS, 4 * MS)]
    gaps = hs.gaps_of(dev)
    assert gaps == [(12 * MS, 28 * MS), (31 * MS, 39 * MS),
                    (52 * MS, 66 * MS), (76 * MS, 96 * MS)]
    by = dict(hs.idle_by_phase(gaps, [_thread()]))
    # middles 20, 35, 59, 86 ms: under admit.wait, admit.merge,
    # step.wait, and the second step itself (no child over 86)
    assert by == {"mxnet.admit.wait": pytest.approx(0.016),
                  "mxnet.admit.merge": pytest.approx(0.008),
                  "mxnet.step.wait": pytest.approx(0.014),
                  "mxnet.serve.decode.step": pytest.approx(0.020)}
    assert sum(by.values()) == pytest.approx(
        sum(e - s for s, e in gaps) * 1e-9)
    # any depth under admit: the first two gaps of the four
    assert hs.idle_share_under(gaps, [_thread()],
                               "mxnet.serve.decode.admit") == \
        pytest.approx(24 / 58)
    none = hs.idle_by_phase(gaps, [])
    assert none == [(hs.NO_PHASE, pytest.approx(0.058))]
    assert hs.uncovered_by_place(gaps, []) is None
    # phases that cover only 31..70 ms: one gap before, one after
    late = [[("mxnet.a", 31 * MS, 10 * MS), ("mxnet.b", 50 * MS, 20 * MS)]]
    assert hs.uncovered_by_place(gaps, late) == {
        "before": pytest.approx(0.016), "between": 0.0,
        "after": pytest.approx(0.020)}
    wide = [[("mxnet.a", 0, 30 * MS), ("mxnet.b", 70 * MS, 30 * MS)]]
    assert hs.uncovered_by_place(gaps, wide) == {
        "before": 0.0, "between": pytest.approx(0.022), "after": 0.0}


def test_only_a_trace_of_this_process_is_taken(tmp_path):
    assert hs.find_trace(str(tmp_path)) is None
    older = tmp_path / "a" / "plugins" / "profile" / "t1"
    newer = tmp_path / "b" / "plugins" / "profile" / "t2"
    for d, age in ((older, 100), (newer, 10)):
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1000 - age, 1000 - age))
    assert hs.find_trace(str(tmp_path)) == \
        str(newer / "host.xplane.pb")
    assert hs.find_trace(str(tmp_path), not_before=900) == \
        str(newer / "host.xplane.pb")
    assert hs.find_trace(str(tmp_path), not_before=995) is None
    assert hs.process_started() <= __import__("time").time()


def test_a_program_without_spans_leaves_the_metrics_out():
    """The parent of the PR that added the phases: a trace with device
    operations and no `mxnet.*` event reads None, and does not raise."""
    path = os.path.join(run.HERE, "testdata", "small.xplane.pb")
    v = hs.view(path, 0.1)
    assert v["lines"] == [] and v["gaps"]
    assert hs.idle_by_phase(v["gaps"], v["lines"])[0][0] == hs.NO_PHASE
    readings = {"trace": {"window_s": 0.1}, "_host_spans": v}
    for what, span, less in (
            ("wall_share", "mxnet.serve.decode.admit", None),
            ("idle_share_under", "mxnet.serve.decode.admit", None),
            ("median_less_child_ms", "mxnet.train.step",
             "mxnet.step.window_wait")):
        assert hs.read(readings, what, span, less) is None
    assert hs.read({}, "wall_share", "mxnet.train.step") is None
    with pytest.raises(ValueError):
        hs.read(readings, "no_such_reading", "mxnet.train.step")


def test_recorded_trace_reads_its_known_numbers():
    path = os.path.join(run.HERE, "testdata", "spans.xplane.pb")
    want = run.load_json(run.HERE, "testdata", "spans.expected.json")
    assert os.path.getsize(path) < 200 * 1024
    v = hs.view(path, want["window_s"])
    lines = v["lines"]
    assert sorted({n for ev in lines for n, _s, _d in ev}) == \
        want["names"]
    edges = sorted({"%s>%s" % (ev[p][0], ev[i][0]) for ev in lines
                    for i, p in enumerate(hs.nest(ev)) if p is not None})
    assert edges == want["edges"]
    for parent, kids in (
            ("mxnet.serve.decode.admit",
             ("fresh_aux", "prefill", "wait", "merge", "emit")),
            ("mxnet.serve.decode.step",
             ("inputs", "dispatch", "wait", "emit")),
            ("mxnet.train.step",
             ("dispatch", "data_wait", "window_wait"))):
        stem = "mxnet.admit." if parent.endswith("admit") \
            else "mxnet.step."
        for kid in kids:
            assert "%s>%s%s" % (parent, stem, kid) in edges
    # every step has its four children, and a tree's self times add up
    # to its root's duration
    for ev in lines:
        parents = hs.nest(ev)
        selfs = hs.self_times(ev)
        for i, (n, _s, d) in enumerate(ev):
            if n != "mxnet.serve.decode.step":
                continue
            kids = [k for k, p in enumerate(parents) if p == i]
            assert sorted(ev[k][0] for k in kids) == [
                "mxnet.step.dispatch", "mxnet.step.emit",
                "mxnet.step.inputs", "mxnet.step.wait"]
            assert selfs[i][2] + sum(selfs[k][2] for k in kids) == \
                pytest.approx(d)
    assert sum(n == "mxnet.serve.decode.step"
               for ev in lines for n, _s, _d in ev) == want["decode_steps"]
    assert sum(n == "mxnet.train.step"
               for ev in lines for n, _s, _d in ev) == want["train_steps"]
    # the gaps are the benchmark's own: the same seconds its summary
    # spreads over `idle_gaps`
    devices, host = trace.load(path)
    summary = trace.reduce_events(devices, host, want["window_s"])
    idle = sum(s for _n, s in summary["idle_gaps"])
    assert idle == pytest.approx(want["idle_s"], rel=1e-6)
    by = hs.idle_by_phase(v["gaps"], lines)
    assert sum(s for _n, s in by) == pytest.approx(idle, rel=1e-6)
    assert by[0][0] == want["idle_by_phase"][0][0]
    assert hs.wall_share(lines, "mxnet.serve.decode.admit",
                         want["window_s"]) == \
        pytest.approx(want["admit_wall_share"], rel=1e-6)
    assert hs.idle_share_under(v["gaps"], lines,
                               "mxnet.serve.decode.admit") == \
        pytest.approx(want["idle_under_admit_share"], rel=1e-6)
    readings = {"trace": {"window_s": want["window_s"]},
                "_host_spans": v}
    assert hs.read(readings, "median_less_child_ms",
                   "mxnet.serve.decode.step", "mxnet.step.wait") == \
        pytest.approx(want["decode_step_host_ms"], rel=1e-6)
    assert hs.read(readings, "median_less_child_ms", "mxnet.train.step",
                   "mxnet.step.window_wait") == \
        pytest.approx(want["fit_step_host_ms"], rel=1e-6)
