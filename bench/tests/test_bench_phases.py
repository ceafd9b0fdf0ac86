"""The reduction by phase scope and program span: a hand-made trace with
known answers; an excerpt of a `usa-k8` trace recorded on a TPU v5e; the
recorded WIKI excerpt of `test_bench_trace.py`, whose operations carry no
scope; and the profile's protobufs, written by hand, read as
`jax.profiler.ProfileData` reads them."""
import glob
import gzip
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import phases, spec, tracing, work  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6  # ns
BODY = "jit(_sequential_superstep)/while/body/closed_call"


def _hand_made():
    ops = [  # [name, start, dur], path
        (["%while.1 (...) while", 10 * MS, 50 * MS],
         "jit(_sequential_superstep)/while"),
        (["%gather.2 s32[8] gather", 12 * MS, 20 * MS], BODY + "/edge-phase/gather"),
        (["%scatter.3 f32[8] scatter", 33 * MS, 10 * MS],
         BODY + "/la-select/scatter-add"),
        (["%fusion.4 f32[8] fusion", 43 * MS, 5 * MS], BODY + "/migrate/add"),
        (["%fusion.5 f32[8,4] fusion", 48 * MS, 7 * MS], BODY + "/la-update/div"),
        # named like a phase, in none: its path decides
        (["%la-update.6 s32[8] dynamic-update-slice", 55 * MS, 3 * MS],
         "jit(_sequential_superstep)/while/body/dynamic_update_slice"),
        (["%fusion.7 s32[1] fusion", 58 * MS, 1 * MS],
         BODY + "/not-edge-phase/add"),
        # the per-step metric under the metric drain; the label fetch; one
        # op that starts before the window
        (["%fusion.8 f32[1] fusion", 62 * MS, 16 * MS], "jit(local_edges)/gather"),
        (["%gather.9 s32[8] gather", 90 * MS, 5 * MS], "jit(_take)/gather"),
        (["%fusion.10 f32[1] fusion", -5 * MS, 7 * MS], "jit(_where)/select_n"),
    ]
    return {
        "host": [["bench-window", 0, 100 * MS],
                 ["run-partitioner", 1 * MS, 98 * MS],
                 ["superstep", 10 * MS, 1 * MS],
                 ["device-sync:scores", 11 * MS, 49 * MS],
                 ["device-sync:metrics", 60 * MS, 20 * MS],
                 ["device-sync:result", 90 * MS, 8 * MS]],
        "modules": [["jit__sequential_superstep(1)", 10 * MS, 50 * MS],
                    ["jit_local_edges(2)", 62 * MS, 16 * MS],
                    ["jit__take(3)", 90 * MS, 5 * MS]],
        "ops": [op for op, _ in ops],
        "op_paths": [path for _, path in ops],
    }


def test_phases_of_a_hand_made_trace():
    r = phases.reduce_phases(_hand_made())
    got = r["phase_busy_s"]
    assert got["edge-phase"] == pytest.approx(0.020)
    assert got["la-select"] == pytest.approx(0.010)
    assert got["migrate"] == pytest.approx(0.005)
    assert got["la-update"] == pytest.approx(0.007)
    # the while's self time (50 - 46) and the two ops in no phase scope
    assert got["unscoped"] == pytest.approx(0.008)
    # together, the superstep's device time as tracing reads it
    assert sum(got.values()) == pytest.approx(
        tracing.reduce_trace(_hand_made())["superstep_busy_s"])


def test_busy_time_outside_the_superstep_by_span():
    r = phases.reduce_phases(_hand_made())
    assert r["busy_by_span"] == pytest.approx({
        "device-sync:metrics": 0.016, "device-sync:result": 0.005,
        "bench-window": 0.001, "run-partitioner": 0.001})
    assert sum(r["busy_by_span"].values()) == pytest.approx(
        tracing.reduce_trace(_hand_made())["outside_superstep_busy_s"])


def test_tracing_reads_the_record_unchanged():
    rec = _hand_made()
    plain = {k: v for k, v in rec.items() if k != "op_paths"}
    assert tracing.reduce_trace(rec) == tracing.reduce_trace(plain)


def test_no_scope_reads_as_unknown():
    rec = _hand_made()
    rec["op_paths"] = [p.replace("edge-phase", "x").replace("la-", "x-")
                       .replace("migrate", "x") for p in rec["op_paths"]]
    assert phases.reduce_phases(rec)["phase_busy_s"] is None
    del rec["op_paths"]
    assert phases.reduce_phases(rec)["phase_busy_s"] is None


@pytest.mark.parametrize("path, phase", [
    (BODY + "/edge-phase/gather", "edge-phase"),
    ("jit(f)/la-update/jit(clip)/max", "la-update"),
    (BODY + "/edge-phases/add", "unscoped"),
    ("", "unscoped"),
    # nested scopes: the innermost decides
    ("jit(s)/interior-scan/while/body/edge-phase/gather", "edge-phase"),
    ("jit(s)/interior-scan/halo-exchange/all-gather", "halo-exchange"),
    ("jit(s)/interior-scan/while/add", "interior-scan"),
])
def test_phase_is_a_path_component(path, phase):
    assert phases.phase_of(path) == phase


def test_scopes_are_every_scope_the_program_opens():
    opened = set()
    for path in glob.glob(os.path.join(ROOT, "src", "repro", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            opened |= set(re.findall(r'annotate\(\s*"([^"]+)"', f.read()))
    assert opened and opened == set(phases.SCOPES)


def test_interval_difference():
    assert phases._minus([(0, 10), (20, 30)], [(2, 4), (6, 22), (29, 40)]) == [
        (0, 2), (4, 6), (22, 29)]
    assert phases._minus([(0, 10)], []) == [(0, 10)]


def test_recorded_trace_without_scopes():
    """The WIKI excerpt was recorded before the scopes: its superstep has no
    phase to read, while its metric drain is still found."""
    with gzip.open(os.path.join(HERE, "data", "wiki_k8_trace_excerpt.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    r = phases.reduce_phases(rec)
    assert r["phase_busy_s"] is None
    assert sum(r["busy_by_span"].values()) == pytest.approx(
        tracing.reduce_trace(rec)["outside_superstep_busy_s"], rel=1e-9)
    assert r["busy_by_span"]["device-sync:metrics"] > 0


def _usa_excerpt():
    """One superstep and its metric drain from a `usa-k8` window traced on
    a TPU v5e (23.9M vertices, 58.4M edges, k=8), with the program's own
    tracer; its "bench-window" is the job's span, so the job's spans stay
    the innermost where no other is open."""
    with gzip.open(os.path.join(HERE, "data", "usa_k8_phase_excerpt.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_usa_trace_splits_the_superstep():
    rec = _usa_excerpt()
    t = tracing.reduce_trace(rec)
    r = phases.reduce_phases(rec)
    assert t["supersteps"] == 1
    got = r["phase_busy_s"]
    assert got == pytest.approx({
        "la-select": 0.216169357, "edge-phase": 3.800699503,
        "migrate": 0.420185289, "la-update": 0.07611863,
        "halo-exchange": 0.0, "interior-scan": 0.0,
        "unscoped": 0.059328764}, rel=1e-8)
    # the four phases and the block scan's own operations make the
    # superstep; the scan's own are a small remainder
    assert sum(got.values()) == pytest.approx(t["superstep_busy_s"], rel=1e-9)
    assert got["unscoped"] < 0.05 * t["superstep_busy_s"]
    # the edge phase's histograms, expanded by the compiler, are placed
    # through the compiled module: by op_name alone they are unscoped
    assert r["phase_from_hlo_s"]["edge-phase"] == pytest.approx(1.188369002,
                                                                rel=1e-8)
    by_path = phases.reduce_phases(
        {k: v for k, v in rec.items() if k != "op_phases"})["phase_busy_s"]
    assert by_path["edge-phase"] == pytest.approx(
        got["edge-phase"] - r["phase_from_hlo_s"]["edge-phase"], rel=1e-6)
    assert by_path["unscoped"] > 0.25 * t["superstep_busy_s"]


def test_recorded_usa_trace_places_the_metric_drain():
    """The step's quality metrics run after its superstep, while the host
    waits to dispatch them, then to fetch them: nearly all the device time
    outside the superstep."""
    rec = _usa_excerpt()
    t = tracing.reduce_trace(rec)
    r = phases.reduce_phases(rec)
    spans = r["busy_by_span"]
    assert sum(spans.values()) == pytest.approx(
        t["outside_superstep_busy_s"], rel=1e-9)
    assert spans["device-sync:metrics"] == pytest.approx(0.641263298, rel=1e-8)
    assert spans["dispatch:metrics"] == pytest.approx(0.461684972, rel=1e-8)
    metrics = spans["device-sync:metrics"] + spans["dispatch:metrics"]
    assert 0.99 * t["outside_superstep_busy_s"] < metrics <= \
        t["outside_superstep_busy_s"]


PHASE_METRICS = ("la_select_ms", "edge_phase_ms", "edge_phase_roofline",
                 "migrate_ms", "la_update_ms", "step_metrics_ms")


def _usa_record(trace):
    """A `usa-k8` run record (23,941,449 vertices, 58,391,992 symmetrized
    slots, k=8) around a reduced trace."""
    return {"n": 23941449, "ms": 58391992, "k": 8,
            "device_kind": "TPU v5 lite", "trace": trace}


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_metrics_read_the_recorded_usa_trace(name):
    """Each phase metric's reader gives, from the run record, the number
    `reduce_phases` gives for the excerpt's one superstep."""
    rec = _usa_excerpt()
    r = phases.reduce_phases(rec)
    busy, spans = r["phase_busy_s"], r["busy_by_span"]
    edge_s = busy["edge-phase"]
    want = {
        "la_select_ms": 1e3 * busy["la-select"],
        "edge_phase_ms": 1e3 * edge_s,
        "edge_phase_roofline": 100 * work.edge_phase_bytes(23941449, 58391992, 8)
        / 819e9 / edge_s,
        "migrate_ms": 1e3 * busy["migrate"],
        "la_update_ms": 1e3 * busy["la-update"],
        "step_metrics_ms": 1e3 * (spans["dispatch:metrics"]
                                  + spans["device-sync:metrics"]),
    }[name]
    trace = {**tracing.reduce_trace(rec), **r}
    got = spec.metric_reader(ROOT, name)(_usa_record(trace))
    assert got == pytest.approx(want, rel=1e-12)
    assert got > 0
    if name == "edge_phase_roofline":
        assert got < 100


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_metrics_read_nothing_without_phases(name):
    """A record whose trace was reduced by program alone has no phases to
    read: they are unknown, not zero."""
    rec = _usa_record(tracing.reduce_trace(_usa_excerpt()))
    assert spec.metric_reader(ROOT, name)(rec) is None


def test_the_phases_and_the_scan_make_the_superstep_metric():
    """The four phase metrics and the unscoped remainder add up to
    `superstep_ms` on the recorded superstep."""
    rec = _usa_excerpt()
    r = phases.reduce_phases(rec)
    record = _usa_record({**tracing.reduce_trace(rec), **r})
    parts = sum(spec.metric_reader(ROOT, n)(record) for n in (
        "la_select_ms", "edge_phase_ms", "migrate_ms", "la_update_ms"))
    assert parts + 1e3 * r["phase_busy_s"]["unscoped"] == pytest.approx(
        spec.metric_reader(ROOT, "superstep_ms")(record), rel=1e-9)


# ---- the profile's protobufs, written by hand --------------------------------

def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str length-prefixed,
    a list of ints packed."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, list):
        value = b"".join(_varint(v) for v in value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields if v is not None)


def _hlo(*computations):
    """An xla.HloProto; each computation is (id, [(id, name, op_name,
    operand ids, called computation ids)])."""
    comps = [_msg((1, f"c{cid}"), (5, cid), *[
        (2, _msg((1, name), (7, _msg((2, op)) if op else None), (35, iid),
                 (36, ops or None), (38, calls or None)))
        for iid, name, op, ops, calls in instrs]) for cid, instrs in computations]
    return _msg((1, _msg((1, "m"), *[(3, c) for c in comps])))


def _plane(name, lines, events_md, stat_names):
    """An XPlane: lines of (name, start ns, [(metadata id, offset ps,
    duration ps)]), event metadata {id: (name, [(stat id, value)])} with a
    str value as str_value, bytes as bytes_value, an int as uint64_value."""
    def stat(sid, v):
        kind = {str: 5, bytes: 6, int: 3}[type(v)]
        return _msg((1, sid), (kind, v))

    return _msg(
        (2, name),
        *[(3, _msg((2, ln), (3, t0), *[
            (4, _msg((1, mid), (2, off), (3, dur))) for mid, off, dur in evs]))
          for ln, t0, evs in lines],
        *[(4, _msg((1, mid), (2, _msg((1, mid), (2, md_name),
                                      *[(5, stat(s, v)) for s, v in stats]))))
          for mid, (md_name, stats) in events_md.items()],
        *[(5, _msg((1, sid), (2, _msg((1, sid), (2, sname)))))
          for sid, sname in stat_names.items()])


def test_instruction_phases_from_a_compiled_module():
    hlo = _hlo(
        (1, [(1, "gather.1", "jit(s)/while/body/edge-phase/gather", [], []),
             (2, "splice.2", "jit(s)/while/body/dynamic_update_slice", [1], []),
             (3, "sort.3", "", [1], []),              # reads edge-phase only
             (4, "div.4", "jit(s)/while/body/la-update/div", [], []),
             (5, "fusion.5", "", [3, 4], []),         # reads two phases
             (6, "fusion.6", "", [1], [2]),           # its fusion says migrate
             (7, "constant.7", "", [], []),           # reads nothing
             (8, "loop.8", "", [9], []),              # a cycle through 9,
             (9, "loop.9", "", [8, 3], [])]),         # fed by the sort
        (2, [(10, "add.10", "jit(s)/while/body/migrate/add", [], [])]))
    got = phases.instruction_phases(hlo)
    assert got == {"gather.1": "edge-phase", "splice.2": "unscoped",
                   "sort.3": "edge-phase", "div.4": "la-update",
                   "fusion.5": "unscoped", "fusion.6": "migrate",
                   "constant.7": "unscoped", "loop.8": "edge-phase",
                   "loop.9": "edge-phase", "add.10": "migrate"}


def _profiledata_record(log_dir, device="/device:TPU:0"):
    """The device's operations and modules and the host spans of a profile,
    as `jax.profiler.ProfileData` reads them."""
    import jax

    pd = jax.profiler.ProfileData.from_file(
        next(log_dir.glob("*.xplane.pb")).as_posix())
    rec = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            for ev in line.events:
                name = tracing.op_name(ev.name) if key == "ops" else ev.name
                if plane.name == device and key is not None:
                    rec[key].append([name, float(ev.start_ns),
                                     float(ev.duration_ns)])
                elif (plane.name.startswith("/host:")
                      and ev.name.split(":")[0] in phases.HOST_SPANS):
                    rec["host"].append([ev.name, float(ev.start_ns),
                                        float(ev.duration_ns)])
    return rec


def test_extract_reads_what_profiledata_reads(tmp_path):
    """`phases.extract` on a hand-written profile: the record
    `jax.profiler.ProfileData` reads, plus each operation's path and
    phase."""
    step = "jit__sequential_superstep(77)"
    body = "jit(_sequential_superstep)/while/body/closed_call"
    hlo = _hlo((1, [(1, "gather.1", body + "/edge-phase/gather", [], []),
                    (2, "sort.2", "", [1], []),
                    (3, "fusion.3", "", [2], [])]))
    device = _plane(
        "/device:TPU:0",
        [("XLA Modules", 1000, [(5, 0, 9_000_000)]),
         ("XLA Ops", 1000, [(1, 0, 2_500_500), (2, 2_600_000, 1_000_000),
                            (3, 3_700_000, 5_000_000), (4, 9_500_000, 10_000)])],
        {1: ("%gather.1 = s32[8]{0} gather(s32[8]{0} %p.1)",
             [(1, body + "/edge-phase/gather:"), (2, 77)]),
         2: ("%sort.2 = s32[8]{0} sort(s32[8]{0} %gather.1)",
             [(1, "jit(_sequential_superstep)/while:"), (2, 77)]),
         3: ("%fusion.3 = f32[8]{0} fusion(s32[8]{0} %sort.2)",
             [(1, "jit(_sequential_superstep)/while:"), (2, 77)]),
         4: ("%copy.4 = f32[8]{0} copy(f32[8]{0} %x)", [(2, 78)]),
         5: (step, [])},
        {1: "tf_op", 2: "program_id"})
    metadata = _plane("/host:metadata", [], {1: (step, [(1, hlo)])},
                      {1: "Hlo Proto"})
    host = _plane(
        "/host:CPU",
        [("python3", 500, [(1, 0, 20_000_000_000), (2, 400_000, 100_000),
                           (3, 9_000_000, 2_000_000), (4, 1_000, 10)])],
        {1: ("bench-window", []), 2: ("superstep", []),
         3: ("dispatch:metrics", []), 4: ("not-a-span", [])}, {})
    (tmp_path / "x.xplane.pb").write_bytes(_msg(
        (1, device), (1, metadata), (1, host)))

    rec = phases.extract(str(tmp_path))
    plain = _profiledata_record(tmp_path)
    assert rec["ops"] == plain["ops"] and rec["modules"] == plain["modules"]
    assert rec["host"] == plain["host"]
    assert rec["ops"][0] == ["%gather.1 s32[8]{0} gather", 1000.0, 2500.0]
    assert rec["host"] == [["bench-window", 500.0, 20_000_000.0],
                           ["superstep", 900.0, 100.0],
                           ["dispatch:metrics", 9500.0, 2000.0]]
    assert rec["op_paths"] == [body + "/edge-phase/gather:",
                               "jit(_sequential_superstep)/while:",
                               "jit(_sequential_superstep)/while:", ""]
    # the sort and the fusion the compiler made read the edge phase's gather
    assert rec["op_phases"] == ["edge-phase"] * 3 + ["unscoped"]
    r = phases.reduce_phases(rec)
    assert r["phase_busy_s"]["edge-phase"] == pytest.approx(8.5e-6)
    assert r["phase_from_hlo_s"] == pytest.approx({"edge-phase": 6e-6})
    assert r["busy_by_span"] == pytest.approx({"dispatch:metrics": 1e-8})
