"""The trace reduction: a hand-made trace with known answers, and an excerpt
of a trace recorded on a TPU v5e (a 1.79M-vertex, 28.5M-edge graph at k=8,
one job's first superstep with its metric drain)."""
import gzip
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import spec, tracing, work  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6  # ns


def _hand_made():
    return {
        "host": [["bench-window", 0, 100 * MS],
                 ["run-partitioner", 1 * MS, 98 * MS],
                 ["superstep", 10 * MS, 1 * MS],
                 ["device-sync:metrics", 60 * MS, 20 * MS],
                 ["device-sync:result", 90 * MS, 8 * MS]],
        "modules": [["jit__sequential_superstep(1)", 10 * MS, 50 * MS],
                    ["jit_gather(2)", 70 * MS, 10 * MS]],
        # a while (10-60) holding two body ops; a metric op (70-80) that
        # overlaps nothing; one op that starts before the window
        "ops": [["%while.1 (...) while", 10 * MS, 50 * MS],
                ["%gather.2 s32[8] gather", 12 * MS, 20 * MS],
                ["%scatter.3 f32[8] scatter", 35 * MS, 20 * MS],
                ["%fusion.4 s32[8] fusion", 70 * MS, 10 * MS],
                ["%fusion.5 f32[1] fusion", -5 * MS, 7 * MS]],
    }


def test_reduction_of_a_hand_made_trace():
    r = tracing.reduce_trace(_hand_made())
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [0, 2] + [10, 60] + [70, 80]
    assert r["busy_s"] == pytest.approx(0.062)
    assert r["superstep_busy_s"] == pytest.approx(0.050)
    assert r["outside_superstep_busy_s"] == pytest.approx(0.012)
    assert r["supersteps"] == 1
    ops = dict(r["device_ops"])
    assert ops["%while.1 (...) while"] == pytest.approx(0.010)   # self time
    assert ops["%gather.2 s32[8] gather"] == pytest.approx(0.020)
    assert ops["%fusion.5 f32[1] fusion"] == pytest.approx(0.002)  # clipped
    gaps = dict(r["idle_gaps"])
    # idle: 2-10 under run-partitioner alone, 60-70 in the metric drain,
    # 80-100 split at the label fetch's edges (90-98) and the job's end (99)
    assert gaps["device-sync:metrics"] == pytest.approx(0.010)
    assert gaps["device-sync:result"] == pytest.approx(0.008)
    assert gaps["run-partitioner"] == pytest.approx(0.019)
    assert gaps["bench-window"] == pytest.approx(0.001)


def test_op_names_are_cut_from_hlo_text():
    assert tracing.op_name(
        "%fusion.123 = s32[7033344]{0:T(1024)S(1)} fusion(s32[224312]{0} "
        "%copy-done.6), kind=kCustom") == "%fusion.123 s32[7033344]{0:T(1024)S(1)} fusion"


def _excerpt():
    with gzip.open(os.path.join(HERE, "data", "wiki_k8_trace_excerpt.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_reduction_of_a_recorded_trace():
    r = tracing.reduce_trace(_excerpt())
    assert r["supersteps"] == 1
    assert r["window_s"] == pytest.approx(4.347)
    assert r["busy_s"] == pytest.approx(4.339789589, rel=1e-9)
    assert r["superstep_busy_s"] == pytest.approx(3.871988396, rel=1e-9)
    assert r["outside_superstep_busy_s"] == pytest.approx(0.467801193, rel=1e-8)
    name, seconds = r["device_ops"][0]
    assert name == "%fusion.123 s32[7044864]{0:T(1024)} fusion"
    assert seconds == pytest.approx(1.062324315, rel=1e-9)
    assert dict(r["idle_gaps"])["device-sync:metrics"] == pytest.approx(
        0.002172974, rel=1e-6)


def test_per_layer_metrics_of_a_recorded_trace():
    """The readers of BENCHMARK.json's per-layer metrics on the excerpt."""
    rec = {"n": 1794474, "ms": 56202030, "k": 8, "device_kind": "TPU v5 lite",
           "setup": {"layout_s": 4.975790829999994},
           "trace": tracing.reduce_trace(_excerpt())}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    got = spec.read_metrics(ROOT, bench["per_layer"], rec)
    assert got["superstep_ms"]["value"] == pytest.approx(3871.988396, rel=1e-9)
    assert got["loop_device_ms"]["value"] == pytest.approx(467.801193, rel=1e-8)
    assert got["device_idle_share"]["value"] == pytest.approx(
        100 * (1 - 4.339789589 / 4.347), rel=1e-6)
    assert got["layout_s"]["value"] == pytest.approx(4.975790829999994)
    # least bytes of a superstep over 819 GB/s, over the measured 3.872 s
    least = (56202030 * 22 / 8 + 2 * 1794474 * 3 / 8
             + 2 * 1794474 * 8 * 4 + 2 * 1794474 * 3 / 8) / 819e9
    assert got["superstep_roofline"]["value"] == pytest.approx(
        100 * least / 3.871988396, rel=1e-9)
    assert 0 < got["superstep_roofline"]["value"] < 100


def test_readers_return_nothing_without_a_device_trace():
    rec = {"n": 10, "ms": 20, "k": 8, "device_kind": "TPU v5 lite",
           "setup": {"layout_s": 1.0},
           "trace": {"window_s": 1.0, "busy_s": 0.0, "superstep_busy_s": 0.0,
                     "outside_superstep_busy_s": 0.0, "supersteps": 3}}
    for name in ("superstep_ms", "superstep_roofline", "loop_device_ms"):
        assert spec.metric_reader(ROOT, name)(rec) is None


@pytest.mark.parametrize("k", [2, 8, 64, 127, 256])
def test_superstep_bytes_are_its_phases(k):
    """The edge phase's and the LA update's least bytes, each as the
    superstep's count has it, add up to the superstep's to the same float."""
    def bits(x):
        return max(1, math.ceil(math.log2(x)))

    for n in (1, 7, 1794474, 23941449, 2**22, 2**31 - 1):
        for ms in (0, 3, 56202030, 58391992, 127 * 2**20):
            edge = ms * (bits(n) + 1) / 8 + 2 * n * bits(k) / 8
            la = 2 * n * k * 4 + 2 * n * bits(k) / 8
            assert work.edge_phase_bytes(n, ms, k) == edge
            assert work.la_update_bytes(n, k) == la
            assert work.superstep_bytes(n, ms, k) == edge + la
