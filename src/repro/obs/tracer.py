"""Host-side tracing + metrics for the partitioner engine.

The engine is asynchronous by design: supersteps are dispatched without
blocking and scores come back in `sync_every`-sized windows, so the only
honest places to *measure* are the host-visible boundaries — superstep
dispatch, the windowed device sync, layout builds, jit (re)compiles — plus
whatever per-superstep scalars can ride the existing drain windows without
adding host syncs. This module records exactly those:

  * **Spans** — nested wall-clock regions (`Tracer.span`) emitted as
    Chrome/perfetto trace-event JSON (`Tracer.save` -> load the file at
    https://ui.perfetto.dev). An enabled tracer also writes every span into
    the profiler's trace as a `jax.profiler.TraceAnnotation` labelled
    ``name`` (``name:what`` when a ``what`` arg is given), so a device
    profile captured with `jax.profiler.trace` shows the device's work on
    the same clock as the spans that waited for it.
  * **Phase scopes** — `annotate` opens a `jax.named_scope`, whatever the
    current tracer, so every operation XLA compiles inside it carries the
    phase name in its ``op_name`` metadata and in device profiles. Inside
    jitted code the Python block runs only while XLA traces it; an enabled
    tracer also records it as a span tagged ``during="trace"``, nested
    under the superstep that triggered the compile, giving the phase
    structure (edge-phase / la-update / halo-exchange) of every compiled
    superstep variant.
  * **Counters** — per-superstep series (`Tracer.counter`) emitted as
    trace-event counter tracks and retained in `Tracer.series` for reports
    and bench artifacts.
  * **Recompile events** — the engine's jitted superstep bodies call
    `obs.record_compile(...)` as their first statement, which fires exactly
    once per jit-cache miss. The tracer attributes a cause: the first event
    per region is ``first-compile``; a caller that knows *why* shapes
    changed (streaming's `e_max` re-pad / halo widen) pre-registers the
    cause with `note_recompile_cause`; otherwise the cause is inferred by
    diffing the static shape args against the region's previous compile.

Overhead contract (pinned by tests/test_obs.py): the default `NULL_TRACER`
leaves every instrumented path bit-identical and adds no work — `span`
returns a shared no-op context manager, `annotate` only the named scope
(compile-time metadata: labels are unchanged), and every recording method
is a pass. An enabled tracer adds per-superstep host timestamps, one O(n)
device comparison for the migration counter, and counter drains that ride
the *existing* `sync_every` windows — never an additional device sync.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

_NULL_CTX = contextlib.nullcontext()


class NullTracer:
    """Default tracer: records nothing, costs (almost) nothing.

    Kept API-compatible with `Tracer` so instrumented code never branches
    on the tracer kind — it just calls the method.
    """

    enabled = False

    def span(self, name: str, **args):
        return _NULL_CTX

    def annotate(self, name: str, **args):
        return jax.named_scope(name)

    def instant(self, name: str, **args) -> None:
        pass

    def counter(self, name: str, value, step: Optional[int] = None,
                ts: Optional[float] = None) -> None:
        pass

    def compile_event(self, region: str, **args) -> None:
        pass

    def note_recompile_cause(self, cause: str) -> None:
        pass

    def clear_recompile_cause(self) -> None:
        pass

    def now_us(self) -> float:
        return 0.0


NULL_TRACER = NullTracer()


class Tracer:
    """Collects spans/counters/events and exports perfetto-loadable JSON.

    One `Tracer` spans one logical run (a `run_partitioner` call, a whole
    stream, a CLI invocation with several algorithms); pass it via
    ``run_partitioner(trace=...)`` / ``StreamRunner(trace=...)`` /
    ``launch partition --trace PATH`` and call `save(path)` at the end.
    """

    enabled = True

    def __init__(self):
        self.events: List[Dict[str, Any]] = []
        # counter name -> [(step, value)]; step is None for run-level gauges
        self.series: Dict[str, List[Tuple[Optional[int], float]]] = {}
        self.recompiles: List[Dict[str, Any]] = []
        self.meta: Dict[str, Any] = {}
        self._pid = os.getpid()
        self._t0 = time.perf_counter_ns()
        self._pending_causes: List[str] = []
        self._last_compile_args: Dict[str, Dict[str, Any]] = {}
        # a subclass that overrides `span` writes its own profiler
        # annotation (or none): bridging here too would write each span twice
        self._bridge = type(self).span is Tracer.span

    # ------------------------------------------------------------------ #
    # clocks / event plumbing
    # ------------------------------------------------------------------ #
    def now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    def _emit(self, ev: Dict[str, Any]) -> None:
        ev.setdefault("pid", self._pid)
        ev.setdefault("tid", threading.get_ident() & 0xFFFF)
        self.events.append(ev)

    # ------------------------------------------------------------------ #
    # spans
    # ------------------------------------------------------------------ #
    @contextlib.contextmanager
    def _record(self, name: str, args: Dict[str, Any]):
        ts = self.now_us()
        try:
            yield self
        finally:
            self._emit({"ph": "X", "name": name, "ts": ts,
                        "dur": self.now_us() - ts, "args": args})

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Record a complete ("X") span around the enclosed block, and write
        it into the profiler's trace as ``name`` (``name:what``)."""
        if not self._bridge:
            with self._record(name, args):
                yield self
            return
        label = f"{name}:{args['what']}" if "what" in args else name
        with jax.profiler.TraceAnnotation(label), self._record(name, args):
            yield self

    @contextlib.contextmanager
    def annotate(self, name: str, **args):
        """Phase scope for code that may run under `jax.jit`.

        The `jax.named_scope` puts ``name`` into the ``op_name`` of every
        operation compiled inside it, where the phase's device time lives.
        The recorded span is *trace* time (tagged ``during="trace"``): one
        per compiled variant, nested under the superstep that triggered
        the compile.
        """
        with jax.named_scope(name), \
                self._record(name, dict(args, during="trace")):
            yield self

    def instant(self, name: str, **args) -> None:
        self._emit({"ph": "i", "s": "t", "name": name, "ts": self.now_us(),
                    "args": args or {}})

    # ------------------------------------------------------------------ #
    # counters
    # ------------------------------------------------------------------ #
    def counter(self, name: str, value, step: Optional[int] = None,
                ts: Optional[float] = None) -> None:
        """Record one point of a counter track.

        `step` indexes the superstep (or delta) the value belongs to and is
        retained in `series`; `ts` back-dates the trace event to when the
        value was *produced* (the superstep's dispatch), not when it was
        drained — counters ride the windowed sync, so the two differ by up
        to `sync_every` supersteps.
        """
        value = float(value)
        self.series.setdefault(name, []).append((step, value))
        ev: Dict[str, Any] = {"ph": "C", "name": name,
                              "ts": self.now_us() if ts is None else ts,
                              "args": {"value": value}}
        self._emit(ev)

    # ------------------------------------------------------------------ #
    # recompile events
    # ------------------------------------------------------------------ #
    def note_recompile_cause(self, cause: str) -> None:
        """Pre-register the semantic cause of the *next* compile event —
        callers that change shapes knowingly (streaming `e_max` re-pad,
        halo widen) call this right before dispatching the rebuilt
        function. Consumed by the next `compile_event`; cleared by
        `clear_recompile_cause` if no compile fired (a stale cause must not
        mis-attribute a later, unrelated recompile)."""
        if cause not in self._pending_causes:
            self._pending_causes.append(cause)

    def clear_recompile_cause(self) -> None:
        self._pending_causes = []

    def compile_event(self, region: str, **args) -> None:
        """Called (via `obs.record_compile`) from inside a jitted body —
        i.e. exactly once per jit-cache miss. Attributes a cause:
        pre-registered > first-compile > inferred static-shape diff."""
        prev = self._last_compile_args.get(region)
        if self._pending_causes:
            cause = "+".join(self._pending_causes)
            self._pending_causes = []
        elif prev is None:
            cause = "first-compile"
        else:
            changed = sorted(k for k in set(prev) | set(args)
                             if prev.get(k) != args.get(k))
            cause = ("shape-change(" + ",".join(changed) + ")"
                     if changed else "unattributed")
        self._last_compile_args[region] = dict(args)
        rec = {"region": region, "cause": cause, **args}
        self.recompiles.append(rec)
        self.instant("recompile", **rec)
        self.counter("recompiles", len(self.recompiles))

    # ------------------------------------------------------------------ #
    # export / summaries
    # ------------------------------------------------------------------ #
    def to_dict(self) -> Dict[str, Any]:
        return {
            "traceEvents": self.events,
            "displayTimeUnit": "ms",
            "otherData": dict(self.meta),
        }

    def save(self, path: str) -> str:
        """Write perfetto/chrome trace-event JSON (open at ui.perfetto.dev
        or chrome://tracing)."""
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path

    def summary(self) -> Dict[str, Any]:
        """Aggregates for bench artifacts: per-span totals, counter
        min/max/last, recompile causes. No raw series (those stay in
        `series` / the saved trace)."""
        spans: Dict[str, Dict[str, float]] = {}
        for ev in self.events:
            if ev.get("ph") != "X":
                continue
            agg = spans.setdefault(ev["name"], {"count": 0, "total_ms": 0.0})
            agg["count"] += 1
            agg["total_ms"] += ev.get("dur", 0.0) / 1e3
        counters = {
            name: {
                "points": len(pts),
                "last": pts[-1][1],
                "min": min(v for _, v in pts),
                "max": max(v for _, v in pts),
            }
            for name, pts in self.series.items() if pts
        }
        causes: Dict[str, int] = {}
        for rec in self.recompiles:
            causes[rec["cause"]] = causes.get(rec["cause"], 0) + 1
        return {
            "spans": {k: {"count": v["count"],
                          "total_ms": round(v["total_ms"], 3)}
                      for k, v in sorted(spans.items())},
            "counters": counters,
            "recompiles": len(self.recompiles),
            "recompile_causes": causes,
        }


# ---------------------------------------------------------------------------
# current-tracer plumbing (module-global; the jitted engine bodies and rule
# modules cannot take a tracer argument — it would be a jit static arg)
# ---------------------------------------------------------------------------
_current: Any = NULL_TRACER


def current():
    """The active tracer (`NULL_TRACER` unless inside a `use` block)."""
    return _current


@contextlib.contextmanager
def use(tracer):
    """Install `tracer` as the current tracer for the enclosed block (pass
    None for the no-op tracer). Entry points (`run_partitioner`,
    `StreamRunner.ingest`) wrap their whole body in this so engine- and
    rule-level instrumentation sees the caller's tracer."""
    global _current
    prev = _current
    _current = tracer if tracer is not None else NULL_TRACER
    try:
        yield _current
    finally:
        _current = prev


def annotate(name: str, **args):
    """`current().annotate(...)` — the form instrumented jit-side code uses."""
    return _current.annotate(name, **args)


def record_compile(region: str = "superstep", **args) -> None:
    """First statement of every jitted superstep body: fires once per
    jit-cache miss (the body only runs while XLA traces it), recording a
    recompile event with attributed cause. No-op when tracing is off."""
    if _current.enabled:
        _current.compile_event(region, **args)
