"""Live phases (ISSUE 24): one primitive (`trace.phase`), two sinks.

The load-bearing assertions:
- a `jax.profiler` trace that the program did not start holds
  `mxnet.serve.decode.{admit,step}` and `mxnet.train.step` on its host
  plane, with their child phases nested inside them on the trace's own
  clock;
- the same run under `MXNET_TRACE` spills the same names, bare, with
  the same parent>child edges (`xplane name = "mxnet." + spill name`);
- neither sink changes what the program does: `host_sync_count` and
  the decoder's step count are identical with both off, either on,
  both on;
- `profiler.scope` reaches a trace started by anyone;
- the counts at the phase boundaries (`admit_rounds`, `prefill_rows`)
  ride `stats()`.
"""
import glob
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, io, profiler, telemetry, trace
from mxnet_tpu.generation import Generator
from mxnet_tpu.initializer import Xavier
from mxnet_tpu.models import transformer
from mxnet_tpu.parallel import make_train_step

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools import trace_report  # noqa: E402

pytestmark = pytest.mark.trace

V, L, H, DIM, T, B = 50, 2, 2, 32, 24, 3
PREFIX = trace.XPLANE_PREFIX

ADMIT_KIDS = {"admit.fresh_aux", "admit.prefill", "admit.wait",
              "admit.merge", "admit.emit"}
STEP_KIDS = {"step.inputs", "step.dispatch", "step.wait", "step.emit"}
FIT_KIDS = {"step.dispatch", "step.data_wait", "step.window_wait"}


# ---------------------------------------------------------------------------
# toys
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_params():
    sym = transformer.get_symbol(V, 12, num_layers=L, num_heads=H,
                                 dim=DIM, max_len=T)
    step = make_train_step(sym, optimizer="sgd")
    mx.random.seed(0)
    return step.init_state(Xavier(), {"data": (2, 12),
                                      "softmax_label": (2, 12)})[0]


def _serve(params):
    """Five ragged greedy requests through a 3-slot pool; returns the
    rows and the decoder's final stats."""
    pool = Generator(params, V, T, num_layers=L, num_heads=H, dim=DIM,
                     batch_size=B)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, V, (p,)) for p in (4, 6, 4, 5, 7)]
    with pool.serving_decoder() as dec:
        futs = [dec.submit(p, n) for p, n in zip(prompts,
                                                 (6, 3, 8, 5, 4))]
        rows = [f.result(120.0) for f in futs]
        return rows, dec.stats()


def _mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, name="fc1", num_hidden=16)
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=2)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy(n=96, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float32)
    return X, y


def _fit_trainstep():
    X, y = _toy()
    step = make_train_step(_mlp())
    step.fit(io.NDArrayIter(X, y, batch_size=32), num_epoch=2,
             initializer=Xavier(), lr=0.1)


def _fit_module():
    X, y = _toy()
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(io.NDArrayIter(X, y, batch_size=32), num_epoch=2,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1})


# ---------------------------------------------------------------------------
# the two sinks, read back
# ---------------------------------------------------------------------------

def _host_phases(trace_dir):
    """[(line, name, start_ns, end_ns)] of the `mxnet.*` events on the
    host plane of the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((line.name, e.name, e.start_ns,
                        e.start_ns + e.duration_ns)
                       for e in line.events
                       if e.name.startswith(PREFIX))
    return out


def _xplane_edges(events):
    """{"parent>child"} by containment on one thread line: each
    event's parent is the shortest other event that encloses it."""
    edges = set()
    for ln, name, s, e in events:
        best = None
        for ln2, name2, s2, e2 in events:
            if ln2 == ln and (s2, e2, name2) != (s, e, name) and \
                    s2 <= s and e <= e2 and \
                    (best is None or e2 - s2 < best[0]):
                best = (e2 - s2, name2)
        if best is not None:
            edges.add("%s>%s" % (best[1], name))
    return edges


class _Sinks:
    """Run `fn` with the chosen sinks on: a `jax.profiler` trace this
    test starts itself (never the profiler module), `MXNET_TRACE`, both
    or neither. Holds what each sink recorded."""

    def __init__(self, fn, tmp, xplane=False, spill=False):
        import jax
        self.events, self.shape, self.records = [], None, []
        xdir = os.path.join(str(tmp), "xplane")
        trace.stop_tracing()
        if spill:
            config.set_override("MXNET_TRACE",
                                os.path.join(str(tmp), "spill"))
        if xplane:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # as cellbench/run.py traces
            jax.profiler.start_trace(xdir, profiler_options=opts)
        base = profiler.host_sync_count()
        try:
            self.result = fn()
        finally:
            self.host_syncs = profiler.host_sync_count() - base
            if xplane:
                jax.profiler.stop_trace()
            path = trace.stop_tracing()
            config.clear_override("MXNET_TRACE")
        if xplane:
            self.events = _host_phases(xdir)
        if spill:
            self.records = trace_report.load(path)
            self.shape = trace.span_shape(self.records)


@pytest.fixture(scope="module")
def both_sinks(lm_params, tmp_path_factory):
    """Each workload once, with both sinks on."""
    tmp = tmp_path_factory.mktemp("both")
    return {
        "decode": _Sinks(lambda: _serve(lm_params), tmp / "decode",
                         xplane=True, spill=True),
        "trainstep": _Sinks(_fit_trainstep, tmp / "trainstep",
                            xplane=True, spill=True),
        "module": _Sinks(_fit_module, tmp / "module",
                         xplane=True, spill=True),
    }


WANT = [("decode", "serve.decode.admit", ADMIT_KIDS),
        ("decode", "serve.decode.step", STEP_KIDS),
        ("trainstep", "train.step", FIT_KIDS),
        ("module", "train.step", FIT_KIDS)]


@pytest.mark.parametrize("load,parent,kids", WANT,
                         ids=["%s-%s" % w[:2] for w in WANT])
def test_xplane_nests_children_in_parent(both_sinks, load, parent, kids):
    """ACCEPTANCE: the host plane of a trace the program did not start
    holds the parent phase and every child inside it."""
    edges = _xplane_edges(both_sinks[load].events)
    for kid in kids:
        assert "%s%s>%s%s" % (PREFIX, parent, PREFIX, kid) in edges, \
            (kid, sorted(edges))


@pytest.mark.parametrize("load,parent,kids", WANT,
                         ids=["%s-%s" % w[:2] for w in WANT])
def test_spill_has_the_same_names_bare(both_sinks, load, parent, kids):
    """ACCEPTANCE: the spill of the same run holds the same names
    without the prefix, with the same parent>child edges."""
    shape = both_sinks[load].shape
    assert parent in shape["spans"]
    for kid in kids:
        assert "%s>%s" % (parent, kid) in shape["edges"], shape


@pytest.mark.parametrize("load", ["decode", "trainstep", "module"])
def test_one_vocabulary_across_sinks(both_sinks, load):
    """xplane name = "mxnet." + spill name, edge for edge, over every
    live phase (retroactive spans never reach the xplane)."""
    run = both_sinks[load]
    # `profiler.scope` (the executor's dispatch) annotates too, but is
    # a host-timeline event of mx.profiler, not a span: phases only
    live = {n[len(PREFIX):] for _l, n, _s, _e in run.events} \
        & set(run.shape["spans"])
    want = {w[1] for w in WANT if w[0] == load}
    want.update(*(w[2] for w in WANT if w[0] == load))
    assert want <= live
    spill = {e for e in run.shape["edges"]
             if set(e.split(">")) <= live}
    xplane = {">".join(n[len(PREFIX):] for n in e.split(">"))
              for e in _xplane_edges(run.events)}
    assert spill == {e for e in xplane if set(e.split(">")) <= live}


def test_epoch_phases_and_idle_are_roots(both_sinks):
    assert {"train.epoch_begin", "train.epoch_end", "train.step"} <= \
        set(both_sinks["trainstep"].shape["roots"])
    assert "train.epoch_end" in both_sinks["module"].shape["roots"]
    roots = set(both_sinks["decode"].shape["roots"])
    assert {"serve.decode.idle", "serve.decode.admit",
            "serve.decode.step", "serve.decode.seq"} <= roots


def test_admit_phase_attrs(both_sinks):
    """`serve.decode.admit` says how many it popped and their lengths;
    `admit.prefill` says how many rows are real prompts and how many
    it `run`: the smallest rung (1 or B) that holds them."""
    spans = [r for r in both_sinks["decode"].records
             if r.get("kind") == "span"]
    admits = [s for s in spans if s["name"] == "serve.decode.admit"]
    assert sum(s["attrs"]["n"] for s in admits) == 5
    assert sorted(sum((s["attrs"]["lengths"] for s in admits), [])) \
        == [4, 4, 5, 6, 7]
    pre = [s for s in spans if s["name"] == "admit.prefill"]
    assert sum(s["attrs"]["rows"] for s in pre) == 5
    assert {s["attrs"]["P"] for s in pre} == {4, 5, 6, 7}
    assert all(s["attrs"]["run"] == (1 if s["attrs"]["rows"] == 1 else B)
               for s in pre)


def test_merge_phase_carries_rows(both_sinks):
    """`admit.merge` says how many prefilled rows its one compiled
    dispatch installed: the same count as its group's `admit.prefill`."""
    spans = [r for r in both_sinks["decode"].records
             if r.get("kind") == "span"]
    merges = [s for s in spans if s["name"] == "admit.merge"]
    pre = [s for s in spans if s["name"] == "admit.prefill"]
    assert [s["attrs"]["rows"] for s in merges] == \
        [s["attrs"]["rows"] for s in pre]
    assert sum(s["attrs"]["rows"] for s in merges) == 5


# ---------------------------------------------------------------------------
# the sinks change nothing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sinks_off(lm_params, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("off")
    _serve(lm_params)       # compile outside every compared run
    _fit_trainstep()
    return {"decode": _Sinks(lambda: _serve(lm_params), tmp / "d"),
            "trainstep": _Sinks(_fit_trainstep, tmp / "t")}


@pytest.mark.parametrize("xplane,spill", [(True, False), (False, True),
                                          (True, True)],
                         ids=["xplane", "spill", "both"])
@pytest.mark.parametrize("load", ["decode", "trainstep"])
def test_sinks_add_no_host_sync_and_no_step(lm_params, sinks_off,
                                            tmp_path, load, xplane,
                                            spill):
    """ACCEPTANCE: host syncs and decode steps identical with both
    sinks off, either on, both on."""
    fn = (lambda: _serve(lm_params)) if load == "decode" \
        else _fit_trainstep
    off = sinks_off[load]
    on = _Sinks(fn, tmp_path, xplane=xplane, spill=spill)
    assert on.host_syncs == off.host_syncs
    if load == "decode":
        rows_on, st_on = on.result
        rows_off, st_off = off.result
        assert st_on["steps"] == st_off["steps"]
        for a, b in zip(rows_on, rows_off):
            np.testing.assert_array_equal(a, b)


def test_off_leaves_no_spill_and_no_event(sinks_off):
    assert sinks_off["decode"].records == []
    assert sinks_off["decode"].events == []
    assert not trace.enabled()


# ---------------------------------------------------------------------------
# profiler.scope, counts, repairs
# ---------------------------------------------------------------------------

def test_profiler_scope_reaches_a_foreign_trace(tmp_path):
    """A trace started by `jax.profiler.trace` — not by
    `profiler_set_state` — sees `profiler.scope`'s annotation."""
    import jax
    assert not profiler.is_running()
    with jax.profiler.trace(str(tmp_path)):
        with profiler.scope("executor_forward", "executor"):
            jax.block_until_ready(jax.numpy.ones((4, 4)) + 1)
    names = {n for _l, n, _s, _e in _host_phases(str(tmp_path))}
    assert PREFIX + "executor_forward" in names


def test_stats_count_admit_rounds_and_prefill_rows(lm_params):
    _rows, st = _serve(lm_params)
    assert st["admitted"] == 5
    assert 1 <= st["admit_rounds"] <= st["admitted"]
    # every prefill forward runs the smallest rung of rows that holds
    # what it admits: one row or the pool's B here
    assert st["admitted"] <= st["prefill_rows"] <= B * st["prefills"]
    assert st["prefill_rows"] < B * st["prefills"]   # the lone 7 ran 1
    # one compiled merge dispatch per prefilled group, one program a
    # rung merged from
    assert st["merges"] == st["prefills"]
    assert 1 <= st["merge_programs"] <= 2


def test_compiled_serve_programs_have_names(lm_params):
    pool = Generator(lm_params, V, T, num_layers=L, num_heads=H,
                     dim=DIM, batch_size=B)
    assert pool._step_fn.__name__ == "generator_step"
    with pool.serving_decoder() as dec:
        assert dec._step_fn.__name__ == "decode_step"
        assert dec._merge_fn.__name__ == "cache_merge"
    draft = pool.truncated_draft(num_layers=1)
    with pool.serving_decoder(draft=draft) as dec:
        assert dec._draft_step_fn.__name__ == "draft_step"


def test_pool_gauges_published_at_turnover(lm_params):
    """The per-step writes moved to where a slot turns over: by the
    time a result is out, the gauges carry the live pool's values."""
    g_jit = telemetry.gauge("serve.decode.jit_cache_size")
    g_kv = telemetry.gauge("serve.decode.kv_bytes_per_slot")
    pool = Generator(lm_params, V, T, num_layers=L, num_heads=H,
                     dim=DIM, batch_size=B)
    with pool.serving_decoder() as dec:
        g_jit.set(-1)
        g_kv.set(-1)            # another Generator's static figure
        dec.submit(np.arange(1, 5), 4).result(120.0)
        assert g_jit.value == 1
        assert g_kv.value == dec._kv_bytes_per_slot


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

@pytest.fixture
def spill(tmp_path):
    trace.stop_tracing()
    config.set_override("MXNET_TRACE", str(tmp_path / "tr"))
    yield
    trace.stop_tracing()
    config.clear_override("MXNET_TRACE")


def test_phase_records_like_span_and_notes_attrs(spill):
    trace.tracer()                      # the hoisted lazy start
    with trace.phase("outer", a=1) as ph:
        with trace.phase("inner"):
            pass
        ph.note(b=2)
    recs = trace_report.load(trace.stop_tracing())
    spans = {r["name"]: r for r in recs if r.get("kind") == "span"}
    assert spans["outer"]["attrs"] == {"a": 1, "b": 2}
    assert spans["inner"]["parent"] == spans["outer"]["span"]
    assert spans["outer"]["parent"] is None


def test_phase_reads_no_config_knob(monkeypatch):
    """Off: a phase is an annotation and a flag read — no
    `config.get` per call (the loops hoist `tracer()` instead)."""
    trace.stop_tracing()
    config.clear_override("MXNET_TRACE")
    calls = []
    monkeypatch.setattr(trace._config, "get",
                        lambda name: calls.append(name) or "")
    for _ in range(100):
        with trace.phase("hot", n=1) as ph:
            ph.note(m=2)
    assert calls == []


def test_root_parent_ignores_the_open_phase(spill):
    """A retroactive lifecycle span emitted under a loop phase roots
    its own trace (`parent=trace.ROOT`) instead of parenting to it."""
    trace.tracer()
    with trace.phase("loop.phase"):
        ctx = trace.add_span("lifecycle", 0.0, 1.0, parent=trace.ROOT)
        trace.add_span("part", 0.0, 0.5, parent=ctx)
    shape = trace.span_shape(trace_report.load(trace.stop_tracing()))
    assert shape["roots"] == ["lifecycle", "loop.phase"]
    assert shape["edges"] == ["lifecycle>part"]


def test_span_closed_after_stop_is_dropped(tmp_path):
    """A loop thread that outlives `stop_tracing` closes its open
    phase into nothing — not into the next spill."""
    trace.stop_tracing()
    trace.start_tracing(str(tmp_path / "a"))
    ph = trace.phase("straddles")
    ph.__enter__()
    trace.stop_tracing()
    ph.__exit__(None, None, None)
    trace.start_tracing(str(tmp_path / "b"))
    with trace.phase("fresh"):
        pass
    shape = trace.span_shape(trace_report.load(trace.stop_tracing()))
    assert shape["spans"] == ["fresh"]
