"""The superstep's share of its roofline, in %: the least HBM bytes a
superstep needs (benchlib/work.py) at the chip's peak bandwidth
(benchlib/peaks.py), over the device time per superstep inside the
program's jitted superstep."""
from benchlib import peaks, work


def read(rec):
    t = rec["trace"]
    if not t["superstep_busy_s"] or not t["supersteps"]:
        return None
    least = (work.superstep_bytes(rec["n"], rec["ms"], rec["k"])
             / peaks.peaks(rec["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / (t["superstep_busy_s"] / t["supersteps"])
