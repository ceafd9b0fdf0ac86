"""Telemetry subsystem tests: tracer mechanics + the overhead contract.

The contract pinned here (see docs/observability.md):
  * tracing off (the default NULL_TRACER) leaves results bit-identical;
  * tracing on adds no blocking device fetches beyond the existing
    windowed syncs (counted via a device_get stub);
  * a traced run exports well-formed perfetto JSON with one superstep
    span per executed step, counter series riding the drain windows, and
    attributed recompile events;
  * streaming shape-change recompiles warn when untraced and are
    attributed ("e_max-repad") when traced.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.runner import run_partitioner
from repro.graphs.generators import dc_sbm
from repro.streaming import StreamConfig, StreamRunner, stream_from_graph

_TOOLS = os.path.join(os.path.dirname(__file__), os.pardir, "tools")


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_TOOLS, "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph():
    return dc_sbm(256, 2048, n_comm=4, mixing=0.25, degree_exponent=0.5,
                  seed=5)


# --------------------------------------------------------------------------
# tracer unit mechanics
# --------------------------------------------------------------------------

def test_null_tracer_is_default_and_noop():
    assert obs.current() is obs.NULL_TRACER
    assert not obs.NULL_TRACER.enabled
    with obs.NULL_TRACER.span("x", a=1):
        pass
    obs.NULL_TRACER.counter("c", 1.0)
    obs.NULL_TRACER.compile_event("r")
    assert obs.NULL_TRACER.now_us() == 0.0


def test_use_installs_and_restores():
    t = obs.Tracer()
    with obs.use(t):
        assert obs.current() is t
        with obs.use(None):
            assert obs.current() is obs.NULL_TRACER
        assert obs.current() is t
    assert obs.current() is obs.NULL_TRACER


def test_span_nesting_and_export(tmp_path):
    t = obs.Tracer()
    with t.span("outer", run=1):
        with t.span("inner"):
            pass
    t.instant("marker", note="hi")
    t.counter("gauge", 3.0, step=0)
    path = t.save(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "otherData"}
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    # inner closes before outer; both are complete events with durations
    assert by_name["inner"]["ph"] == "X" and by_name["outer"]["ph"] == "X"
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert by_name["marker"]["ph"] == "i"
    assert by_name["gauge"]["ph"] == "C"
    assert by_name["gauge"]["args"]["value"] == 3.0
    assert t.series["gauge"] == [(0, 3.0)]


def test_recompile_cause_priority():
    t = obs.Tracer()
    t.compile_event("superstep", e_max=128, algo="revolver")
    assert t.recompiles[-1]["cause"] == "first-compile"
    # inferred diff of static args against the previous compile
    t.compile_event("superstep", e_max=256, algo="revolver")
    assert t.recompiles[-1]["cause"] == "shape-change(e_max)"
    # a pre-registered semantic cause wins over inference
    t.note_recompile_cause("e_max-repad")
    t.compile_event("superstep", e_max=512, algo="revolver")
    assert t.recompiles[-1]["cause"] == "e_max-repad"
    # cleared causes must not leak onto the next event
    t.note_recompile_cause("halo-widen")
    t.clear_recompile_cause()
    t.compile_event("superstep", e_max=512, algo="spinner")
    assert t.recompiles[-1]["cause"] == "shape-change(algo)"
    assert t.series["recompiles"][-1][1] == 4.0


def test_annotate_tags_trace_time():
    t = obs.Tracer()
    with obs.use(t):
        with obs.annotate("edge-phase", impl="jnp"):
            pass
    ev = [e for e in t.events if e["name"] == "edge-phase"]
    assert len(ev) == 1 and ev[0]["args"]["during"] == "trace"


@pytest.fixture
def annotations(monkeypatch):
    """Labels of the profiler annotations opened, in order."""
    opened = []

    class Recorder:
        def __init__(self, label):
            opened.append(label)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return opened


def test_spans_reach_the_profiler(annotations):
    t = obs.Tracer()
    with t.span("superstep", step=0):
        with t.span("device-sync", steps=1, what="metrics"):
            pass
    with obs.use(t), obs.annotate("edge-phase", impl="jnp"):
        pass
    # trace-time phase spans stay host-side: their device time is in the
    # named scope, not in an annotation
    assert annotations == ["superstep", "device-sync:metrics"]
    assert [e["name"] for e in t.events] == ["device-sync", "superstep",
                                            "edge-phase"]


def test_null_tracer_opens_no_annotation(annotations):
    with obs.NULL_TRACER.span("superstep", step=0), obs.annotate("edge-phase"):
        pass
    assert annotations == []


def test_subclass_span_is_not_written_twice(annotations):
    """A subclass that writes its own annotation around `span` gets one
    annotation per span, not two."""

    class Own(obs.Tracer):
        @contextlib.contextmanager
        def span(self, name, **args):
            with jax.profiler.TraceAnnotation("own:" + name), \
                    super().span(name, **args):
                yield self

    with Own().span("superstep"):
        pass
    assert annotations == ["own:superstep"]


@pytest.mark.parametrize("tracer", [None, "on"])
def test_annotate_names_the_compiled_ops(tracer):
    """The phase scope reaches the compiled program whether or not a tracer
    is installed."""

    def f(x):
        with obs.annotate("edge-phase"):
            return jnp.sin(x) * 2

    with obs.use(obs.Tracer() if tracer else None):
        text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert "/edge-phase/" in text


# --------------------------------------------------------------------------
# traced batch runs
# --------------------------------------------------------------------------

def test_traced_run_records_spans_and_counters(graph):
    t = obs.Tracer()
    res = run_partitioner("revolver", graph, 5, seed=1, max_steps=5,
                          patience=10_000, trace=t)
    assert res.steps == 5
    sup = [e for e in t.events if e["name"] == "superstep" and e["ph"] == "X"]
    assert len(sup) == res.steps
    assert [e["args"]["step"] for e in sup] == list(range(res.steps))
    for name in ("local_edges", "max_norm_load", "migrations"):
        assert len(t.series[name]) == res.steps, name
        assert [s for s, _ in t.series[name]] == list(range(res.steps))
    # counter series mirror the history the untraced path reports
    assert [v for _, v in t.series["local_edges"]] == \
        pytest.approx(res.history["local_edges"])
    # migrations are bounded by the vertex count and someone moved at step 0
    migs = [v for _, v in t.series["migrations"]]
    assert all(0 <= v <= graph.n for v in migs) and migs[0] > 0
    # run manifest for trace_report --validate
    assert t.meta["runs"] == [{"algo": "revolver", "k": 5,
                               "schedule": "sequential", "steps": 5}]
    # jit-trace-time phase spans nested under the compiling superstep
    phases = {e["name"] for e in t.events
              if e.get("args", {}).get("during") == "trace"}
    assert "edge-phase" in phases and "la-update" in phases
    assert any(r["cause"] == "first-compile" for r in t.recompiles)
    summary = t.summary()
    assert summary["spans"]["superstep"]["count"] == res.steps
    json.dumps(summary)   # artifact-embeddable


def test_traced_run_spans_the_metric_dispatch(graph):
    t = obs.Tracer()
    res = run_partitioner("revolver", graph, 4, seed=1, max_steps=3,
                          patience=10_000, trace=t)
    spans = [e for e in t.events if e["ph"] == "X"]
    dispatch = [e for e in spans if e["name"] == "dispatch"]
    assert [e["args"] for e in dispatch] == [{"what": "metrics"}] * res.steps
    # each between its superstep's dispatch and the window's drain
    sup = [e for e in spans if e["name"] == "superstep"]
    assert all(s["ts"] + s["dur"] <= d["ts"] for s, d in zip(sup, dispatch))


def test_tracing_off_is_bit_identical(graph):
    kw = dict(seed=3, max_steps=4, patience=10_000)
    base = run_partitioner("revolver", graph, 4, **kw)
    traced = run_partitioner("revolver", graph, 4, trace=obs.Tracer(), **kw)
    again = run_partitioner("revolver", graph, 4, trace=None, **kw)
    np.testing.assert_array_equal(base.labels, traced.labels)
    np.testing.assert_array_equal(base.labels, again.labels)
    assert base.history == traced.history == again.history
    assert base.local_edges == traced.local_edges
    assert base.max_norm_load == traced.max_norm_load


def test_tracer_adds_no_device_syncs(graph, monkeypatch):
    """The traced loop must issue exactly as many blocking device fetches
    as the untraced one — counters ride the existing drain windows."""
    counts = []
    real = jax.device_get

    def counting(x):
        counts[-1] += 1
        return real(x)

    kw = dict(seed=2, max_steps=6, patience=10_000, sync_every=3,
              track_history=True)
    monkeypatch.setattr(jax, "device_get", counting)
    counts.append(0)
    run_partitioner("revolver", graph, 4, **kw)
    untraced = counts[-1]
    counts.append(0)
    run_partitioner("revolver", graph, 4, trace=obs.Tracer(), **kw)
    traced = counts[-1]
    assert untraced > 0
    assert traced == untraced


def test_async_traced_run_overlap_and_staleness(graph):
    """Traced async runs show the overlap pair (a halo-exchange span inside
    the interior-scan span's time range) and a halo_staleness counter series
    that never exceeds the bound — the schedule's observable contract,
    pinned without reading engine internals."""
    t = obs.Tracer()
    res = run_partitioner("revolver", graph, 4, seed=1, max_steps=6,
                          patience=10_000, chunk_schedule="async",
                          staleness_bound=1, trace=t)
    assert t.meta["runs"][0]["schedule"] == "async"
    interior = [e for e in t.events
                if e["name"] == "interior-scan" and e["ph"] == "X"]
    exchange = [e for e in t.events
                if e["name"] == "halo-exchange" and e["ph"] == "X"]
    assert interior and exchange
    assert any(h["ts"] >= i["ts"] and
               h["ts"] + h["dur"] <= i["ts"] + i["dur"]
               for i in interior for h in exchange), \
        "no halo-exchange span nested inside an interior-scan span"
    # the overlapped exchange is tagged so profiles can tell it apart from
    # the halo schedule's barrier exchange
    assert all(e["args"].get("overlap") == 1 for e in exchange)
    # staleness series: one point per superstep, bounded by staleness_bound,
    # and at least one genuinely stale superstep actually happened
    pts = t.series["halo_staleness"]
    assert [s for s, _ in pts] == list(range(res.steps))
    assert max(v for _, v in pts) <= 1
    assert any(v == 1 for _, v in pts)
    assert pts[0][1] == 0         # first superstep is always fresh
    # trace_report --validate knows the contract
    tr = _load_trace_report()
    doc = t.to_dict()
    assert tr.validate(doc) == []
    # ... and flags traces that claim async but lack the evidence
    no_stale = dict(doc)
    no_stale["traceEvents"] = [e for e in doc["traceEvents"]
                               if e["name"] != "halo_staleness"]
    assert any("halo_staleness" in p for p in tr.validate(no_stale))
    no_pair = dict(doc)
    no_pair["traceEvents"] = [e for e in doc["traceEvents"]
                              if e["name"] != "halo-exchange"]
    assert any("overlap" in p for p in tr.validate(no_pair))


def test_async_tracer_adds_no_device_syncs(graph, monkeypatch):
    """halo_staleness is emitted from the host-side refresh policy — the
    traced async loop must not fetch anything beyond the drain windows."""
    counts = []
    real = jax.device_get

    def counting(x):
        counts[-1] += 1
        return real(x)

    kw = dict(seed=2, max_steps=6, patience=10_000, sync_every=3,
              track_history=True, chunk_schedule="async", staleness_bound=2)
    monkeypatch.setattr(jax, "device_get", counting)
    counts.append(0)
    run_partitioner("revolver", graph, 4, **kw)
    untraced = counts[-1]
    counts.append(0)
    run_partitioner("revolver", graph, 4, trace=obs.Tracer(), **kw)
    assert untraced > 0
    assert counts[-1] == untraced


def test_trace_kwarg_smoke_other_schedules(graph):
    # sequential restream/spinner run traced end to end; schedule recorded
    for algo in ("spinner", "restream"):
        t = obs.Tracer()
        res = run_partitioner(algo, graph, 4, seed=0, max_steps=3,
                              patience=10_000, trace=t)
        assert t.meta["runs"][0]["algo"] == algo
        assert t.summary()["spans"]["superstep"]["count"] == res.steps


# --------------------------------------------------------------------------
# streaming
# --------------------------------------------------------------------------

def _stream_parts(graph, trace=None, deltas=4):
    cfg = StreamConfig(k=4, n_blocks=8, refine_max_steps=5,
                       refine_patience=10_000)
    runner = StreamRunner(graph.n, cfg, seed=7, trace=trace)
    runner.run(stream_from_graph(graph, deltas, seed=0))
    return runner


def test_streaming_traced_bit_identical_and_attributed(graph):
    # traced stream first: its e_max re-pads hit a cold jit cache, so the
    # recompile events actually fire (a warm cache would swallow them)
    t = obs.Tracer()
    traced = _stream_parts(graph, trace=t)
    base = _stream_parts(graph)
    np.testing.assert_array_equal(base.labels, traced.labels)
    assert [r.local_edges for r in base.reports] == \
        [r.local_edges for r in traced.reports]
    # one delta span per ingest, superstep spans numbered globally
    assert t.summary()["spans"]["delta"]["count"] == 4
    sup_steps = [e["args"]["step"] for e in t.events
                 if e["name"] == "superstep" and e["ph"] == "X"]
    assert sup_steps == list(range(traced.total_steps))
    # per-delta counters
    assert len(t.series["delta_dirty_blocks"]) == 4
    assert len(t.series["delta_m"]) == 4
    assert t.series["delta_m"][-1][1] == traced.reports[-1].m
    # this stream re-pads e_max after the first delta; the traced run's
    # recompiles must carry the pre-registered semantic cause
    repads = [r for r in traced.reports[1:] if r.repadded]
    assert repads, "fixture stream no longer re-pads; enlarge the deltas"
    causes = {r["cause"] for r in t.recompiles}
    assert "e_max-repad" in causes
    # run manifest covers every delta
    assert sum(r["steps"] for r in t.meta["runs"]) == traced.total_steps


def test_streaming_untraced_repad_warns(graph, caplog):
    with caplog.at_level(logging.WARNING, logger="repro.streaming"):
        runner = _stream_parts(graph)
    assert any(r.repadded for r in runner.reports[1:])
    warnings = [r for r in caplog.records
                if "recompiles the refine superstep" in r.getMessage()]
    assert warnings, "silent recompile: expected a one-line warning"


# --------------------------------------------------------------------------
# trace_report tool
# --------------------------------------------------------------------------

def test_trace_report_validates_real_trace(graph, tmp_path):
    tr = _load_trace_report()
    t = obs.Tracer()
    run_partitioner("revolver", graph, 4, seed=0, max_steps=3,
                    patience=10_000, trace=t)
    path = str(tmp_path / "trace.json")
    t.save(path)
    doc = tr.load(path)
    assert tr.validate(doc) == []
    assert "superstep" in tr.report(doc)
    assert tr.main([path, "--validate"]) == 0


def test_trace_report_rejects_corrupted(graph, tmp_path):
    tr = _load_trace_report()
    t = obs.Tracer()
    run_partitioner("revolver", graph, 4, seed=0, max_steps=3,
                    patience=10_000, trace=t)
    doc = t.to_dict()

    # dropped superstep span -> count mismatch against otherData.runs
    pruned = dict(doc)
    pruned["traceEvents"] = [e for e in doc["traceEvents"]
                             if e["name"] != "superstep"][:]
    problems = tr.validate(pruned)
    assert any("superstep" in p for p in problems)

    # malformed event (missing ts)
    broken = dict(doc)
    broken["traceEvents"] = doc["traceEvents"] + [{"name": "x", "ph": "X"}]
    assert any("missing" in p for p in tr.validate(broken))

    # not trace-event JSON at all
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([1, 2, 3]))
    with pytest.raises(ValueError):
        tr.load(str(bad))
    assert tr.main([str(bad), "--validate"]) == 2
