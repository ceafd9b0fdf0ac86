"""Device time per superstep outside the program's jitted superstep under
its `dispatch:metrics` and `device-sync:metrics` spans: the convergence
loop's per-step `local_edges` / `max_norm_load`. None where the trace was
not split by host span or holds neither span."""

SPANS = ("dispatch:metrics", "device-sync:metrics")


def read(rec):
    t = rec.get("trace") or {}
    by_span = t.get("busy_by_span") or {}
    if not t.get("supersteps") or not any(s in by_span for s in SPANS):
        return None
    return 1e3 * sum(by_span.get(s, 0.0) for s in SPANS) / t["supersteps"]
