"""Device time per superstep inside the program's jitted superstep (the
device programs whose name holds "superstep"), in the traced window."""


def read(rec):
    t = rec["trace"]
    if not t["superstep_busy_s"] or not t["supersteps"]:
        return None
    return 1e3 * t["superstep_busy_s"] / t["supersteps"]
