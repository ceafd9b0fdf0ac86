"""1 - (union of device operation intervals / traced window), in %."""


def read(rec):
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
