"""The device's peak_bytes_in_use after the window, in GiB."""


def read(rec):
    peak = rec["peak_bytes"]
    return None if peak is None else peak / 2**30
