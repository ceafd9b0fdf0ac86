"""The edge phase's share of its roofline, in %: the least HBM bytes of a
superstep's edge phase (benchlib/work.py) at the chip's peak bandwidth
(benchlib/peaks.py), over the device time per superstep in the program's
`edge-phase` scope. None where the trace has no phases."""
from benchlib import peaks, phases, work


def read(rec):
    ms = phases.phase_ms(rec, "edge-phase")
    if ms is None:
        return None
    least = (work.edge_phase_bytes(rec["n"], rec["ms"], rec["k"])
             / peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
