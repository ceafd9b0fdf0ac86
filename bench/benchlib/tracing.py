"""The traced run: a `jax.profiler` trace of the window, and its reduction.

The program's own `repro.obs.Tracer` writes each of its spans into the
profiler's trace as a `TraceAnnotation` (`name`, or `name:what`), so the
program's spans (`run-partitioner`, `superstep`, `device-sync`, ...) sit on
the device's clock. `phases.extract` turns the profiler's `.xplane.pb` into
a plain record: the device's operation and program (module) events, and the
host annotations. `reduce_trace` works on that record alone, so it is
checked against a small recorded trace with no chip.

Device time is attributed here by program: the program's jitted superstep
(a module whose name holds "superstep") against everything else the device
runs; `phases.reduce_phases` splits both further, by phase scope and by
host span.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

# host annotations the reduction reads: the program's spans and the
# benchmark's own ("bench-window" around the measured window, "bench-job"
# around each job)
HOST_SPANS = ("bench-window", "bench-job", "run-partitioner", "prepare-layout",
              "superstep", "device-sync")
SUPERSTEP_MODULE = "superstep"
TOP = 10
_HLO = re.compile(r"^(%\S+) = (\S+) ([a-z][\w-]*)\(")


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # a Python call trace would swamp the window
    opts.host_tracer_level = 2
    return opts


def op_name(hlo_text: str) -> str:
    """'%fusion.12 s32[7033344]{0} fusion' from an operation's HLO line."""
    m = _HLO.match(hlo_text)
    return " ".join(m.groups()) if m else hlo_text[:80]


def _union(intervals) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _overlap(a: List[tuple], b: List[tuple]) -> float:
    """Total length of the intersection of two unions of intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _self_times(ops) -> Dict[str, float]:
    """Per operation name, the time not covered by operations nested in it
    (a `while` holds its body's operations)."""
    out: Dict[str, float] = {}
    stack: List[list] = []     # [end, name]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= e - s
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append([e, name])
    return out


def _innermost(host: List[list], t: float) -> str:
    """Name of the shortest host span open at time t."""
    best: Optional[list] = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[2]):
            best = [name, s, d]
    return best[0] if best else "none"


def _attribute(gap: tuple, host: List[list], out: Dict[str, float]) -> None:
    """Split an idle gap at the host spans' edges and add each piece to the
    innermost span open in it."""
    s, e = gap
    near = [h for h in host if h[1] < e and h[1] + h[2] > s]
    cuts = sorted({s, e, *(x for h in near for x in (h[1], h[1] + h[2])
                           if s < x < e)})
    for a, b in zip(cuts, cuts[1:]):
        what = _innermost(near, (a + b) / 2)
        out[what] = out.get(what, 0.0) + (b - a)


def reduce_trace(rec: dict) -> dict:
    """Within the "bench-window" annotation: device busy time, the time in
    the program's superstep and outside it, the supersteps run, the top
    operations by self time and the idle time by the host span open in
    it."""
    windows = [h for h in rec["host"] if h[0] == "bench-window"]
    if not windows:
        raise RuntimeError("the trace holds no bench-window annotation")
    _, w0, wd = windows[0]
    w1 = w0 + wd

    def clip(events):
        return [(name, max(s, w0), min(s + d, w1))
                for name, s, d in events if s < w1 and s + d > w0]

    ops = clip(rec["ops"])
    busy = _union((s, e) for _, s, e in ops)
    step_mods = _union((s, e) for name, s, e in clip(rec["modules"])
                       if SUPERSTEP_MODULE in name)
    busy_s = sum(e - s for s, e in busy) / 1e9
    in_step_s = _overlap(busy, step_mods) / 1e9
    host = [h for h in rec["host"] if h[1] < w1 and h[1] + h[2] > w0]
    gaps: Dict[str, float] = {}
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            _attribute((s, e), host, gaps)
    top_ops = sorted(_self_times(ops).items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": wd / 1e9,
        "busy_s": busy_s,
        "superstep_busy_s": in_step_s,
        "outside_superstep_busy_s": busy_s - in_step_s,
        "supersteps": sum(1 for h in host if h[0] == "superstep"),
        "device_ops": [[n, v / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / 1e9] for n, v in top_gaps],
    }
