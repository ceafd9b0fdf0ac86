"""Device time per superstep outside the program's jitted superstep: what
the convergence loop runs around it (job init, the per-step quality
metrics, the labels put back in vertex order), in the traced window."""


def read(rec):
    t = rec["trace"]
    if not t["busy_s"] or not t["supersteps"]:
        return None
    return 1e3 * t["outside_superstep_busy_s"] / t["supersteps"]
