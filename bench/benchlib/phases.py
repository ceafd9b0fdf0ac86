"""Device time of a traced run by the program's phase scopes and spans.

The program puts the operations of its superstep in phase scopes
(`obs.annotate`, a `jax.named_scope`): `la-select`, `edge-phase`,
`migrate`, `la-update` in the Revolver rule, `halo-exchange` and
`interior-scan` in the sharded and overlapped schedules, which may hold
others. The scope reaches the operation's HLO `op_name` metadata, which
the TPU profile keeps as the `tf_op` stat of the operation's event
metadata, e.g.
`jit(_sequential_superstep)/while/body/closed_call/edge-phase/gather:`; an
operation's phase is the innermost scope on that path. `extract` reads the
profile with `xplane` (`jax.profiler.ProfileData` shows no metadata
stats) and returns the device's operation and program (module) events and
the host spans, with the path and the phase of each operation beside it
(`op_paths`, `op_phases`); `tracing.reduce_trace` reads the same record.

An operation the compiler made has no `op_name` of its own, and the
profile then names the instruction that holds it (the block scan's
`while`). The TPU compiler expands a large scatter-add, such as the edge
phase's histograms, into sorts and loops of that kind. Its phase is read
from the compiled module (`instruction_phases`): the op_names of the
instructions it calls, else the one phase of the values it reads. A phase
is read from the program's metadata alone, never guessed from an
operation's name.

`reduce_phases` works on the record alone, so it is checked against
recorded traces with no chip:

- `phase_busy_s`: self time of the operations inside the superstep
  programs by phase, one key per scope of SCOPES, and `unscoped` for the
  rest (the block scan's `while`, its splices). None where no such
  operation has a phase: the phases are then unknown, not zero.
- `phase_from_hlo_s`: the part of each phase placed through the compiled
  module rather than by the operation's own path.
- `busy_by_span`: device busy time outside the superstep programs, by the
  innermost host span open while it ran, as `tracing`'s idle gaps are.
  The per-step quality metrics of the convergence loop run under
  `dispatch:metrics` (the host waits there for their buffers) and
  `device-sync:metrics`.
"""
from __future__ import annotations

import glob
from typing import Dict, FrozenSet, List, Optional

from benchlib import tracing, xplane

# every scope the program opens (`obs.annotate` in `src/repro`)
SCOPES = ("la-select", "edge-phase", "migrate", "la-update", "halo-exchange",
          "interior-scan")
UNSCOPED = "unscoped"
# stats of an operation's event metadata: its HLO op_name and its program,
# and of a module's on the metadata plane: the compiled module
OP_NAME_STAT, PROGRAM_STAT, HLO_STAT = "tf_op", "program_id", "Hlo Proto"
METADATA_PLANE = "/host:metadata"
# tracing's host spans, and the program's dispatch of the per-step metrics
HOST_SPANS = tracing.HOST_SPANS + ("dispatch",)


def phase_of(path: str) -> str:
    """The innermost scope of SCOPES on an op_name path, else UNSCOPED."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def instruction_phases(hlo_proto: bytes) -> Dict[str, str]:
    """The phase of each instruction of a compiled module, by name. An
    instruction with an op_name has its phase, or none. One without (the
    compiler made it) has the phases of the instructions it calls, where
    they have op_names, else the phases of the values it reads, through
    other such instructions. UNSCOPED where that is not exactly one."""
    instructions, computations = xplane.hlo_module(hlo_proto)

    def own(i: int) -> Optional[FrozenSet[str]]:
        ins = instructions[i]
        names = [ins.op_name] if ins.op_name else [
            instructions[j].op_name for c in ins.calls
            for j in computations.get(c, ()) if instructions[j].op_name]
        if not names:
            return None
        return frozenset(phase_of(n) for n in names) - {UNSCOPED}

    memo: Dict[int, FrozenSet[str]] = {}
    for root in instructions:
        stack, open_ = [root], set()
        while stack:
            i = stack[-1]
            if i in memo:
                stack.pop()
                continue
            got = own(i)
            if got is None:
                reads = [o for o in instructions[i].operands if o in instructions]
                todo = [o for o in reads if o not in memo and o not in open_]
                if todo and i not in open_:
                    open_.add(i)
                    stack.extend(todo)
                    continue
                # an operand still open is on a cycle: it adds nothing
                got = frozenset().union(*(memo.get(o, frozenset())
                                          for o in reads))
            memo[i] = got
            open_.discard(i)
            stack.pop()
    return {ins.name: next(iter(memo[i])) if len(memo[i]) == 1 else UNSCOPED
            for i, ins in instructions.items()}


def extract(log_dir: str, device: str = "/device:TPU:0") -> dict:
    """Plain record of a profile: ``{"ops": [[name, start_ns, dur_ns],
    ...], "modules": [...], "host": [...], "op_paths": [...],
    "op_phases": [...]}``: the device plane's "XLA Ops" and "XLA Modules"
    lines, the host spans in HOST_SPANS, and the op_name path of each entry
    of ``"ops"`` ("" where it has none) and its phase, in the same order."""
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise RuntimeError(f"no profile written under {log_dir}")
    planes = xplane.read(paths[0])
    # the compiled superstep modules, by program id ("<name>(<id>)")
    hlo = {int(name.rsplit("(", 1)[-1].rstrip(")")): stats[HLO_STAT]
           for plane in planes if plane.name == METADATA_PLANE
           for name, stats in plane.metadata.items()
           if tracing.SUPERSTEP_MODULE in name and HLO_STAT in stats}
    by_program: Dict[int, Dict[str, str]] = {}

    def phase(ev, path: str) -> str:
        program = ev.stats.get(PROGRAM_STAT)
        if phase_of(path) != UNSCOPED or program not in hlo:
            return phase_of(path)
        if program not in by_program:
            by_program[program] = instruction_phases(hlo[program])
        # the event is named by its HLO text, "%<instruction> = ..."
        name = ev.name.split(" ", 1)[0].lstrip("%")
        return by_program[program].get(name, UNSCOPED)

    rec: Dict[str, list] = {"ops": [], "op_paths": [], "op_phases": [],
                            "modules": [], "host": []}
    for plane in planes:
        if plane.name == device:
            for ev in plane.lines.get("XLA Ops", []):
                path = ev.stats.get(OP_NAME_STAT, "")
                rec["ops"].append([tracing.op_name(ev.name), ev.start_ns,
                                   ev.duration_ns])
                rec["op_paths"].append(path)
                rec["op_phases"].append(phase(ev, path))
            rec["modules"] = [[ev.name, ev.start_ns, ev.duration_ns]
                              for ev in plane.lines.get("XLA Modules", [])]
        elif plane.name.startswith("/host:"):
            rec["host"] += [[ev.name, ev.start_ns, ev.duration_ns]
                            for line in plane.lines.values() for ev in line
                            if ev.name.split(":")[0] in HOST_SPANS]
    return rec


def _minus(a: List[tuple], b: List[tuple]) -> List[tuple]:
    """The parts of a union of intervals `a` outside the union `b`."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def reduce_phases(rec: dict) -> dict:
    """Within the "bench-window" annotation: superstep self time by phase,
    and the device time outside the superstep by host span."""
    windows = [h for h in rec["host"] if h[0] == "bench-window"]
    if not windows:
        raise RuntimeError("the trace holds no bench-window annotation")
    _, w0, wd = windows[0]
    w1 = w0 + wd

    def clip(events):
        return [(name, max(s, w0), min(s + d, w1))
                for name, s, d in events if s < w1 and s + d > w0]

    paths = rec.get("op_paths") or [""] * len(rec["ops"])
    by_path = [phase_of(p) for p in paths]
    # (phase, placed through the compiled module) of each operation
    keys = [(ph, ph != own) for ph, own in
            zip(rec.get("op_phases") or by_path, by_path)]
    ops = clip([(key, s, d) for (_, s, d), key in zip(rec["ops"], keys)])
    step_mods = tracing._union((s, e) for name, s, e in clip(rec["modules"])
                               if tracing.SUPERSTEP_MODULE in name)

    def in_step(s: float, e: float) -> bool:
        mid = (s + e) / 2
        return any(ms <= mid <= me for ms, me in step_mods)

    step_ops = [op for op in ops if in_step(op[1], op[2])]
    phase_busy: Optional[Dict[str, float]] = None
    from_hlo: Dict[str, float] = {}
    if any(ph != UNSCOPED for (ph, _), _, _ in step_ops):
        self_ns = tracing._self_times(step_ops)
        phase_busy = {p: 0.0 for p in SCOPES + (UNSCOPED,)}
        for (ph, hlo), ns in self_ns.items():
            phase_busy[ph] += ns / 1e9
            if hlo:
                from_hlo[ph] = from_hlo.get(ph, 0.0) + ns / 1e9

    host = [h for h in rec["host"] if h[1] < w1 and h[1] + h[2] > w0]
    outside = _minus(tracing._union((s, e) for _, s, e in ops), step_mods)
    by_span: Dict[str, float] = {}
    for iv in outside:
        tracing._attribute(iv, host, by_span)
    return {
        "phase_busy_s": phase_busy,
        "phase_from_hlo_s": from_hlo,
        "busy_by_span": {n: v / 1e9 for n, v in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
    }


def phase_ms(rec: dict, scope: str) -> Optional[float]:
    """Device self time per superstep of `scope` in a run record's trace,
    in ms; None where the trace has no phases or no time in that scope."""
    t = rec.get("trace") or {}
    busy = (t.get("phase_busy_s") or {}).get(scope)
    if not busy or not t.get("supersteps"):
        return None
    return 1e3 * busy / t["supersteps"]
