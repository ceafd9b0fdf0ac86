"""Host clock around the program's `prepare_device_graph` in set-up."""


def read(rec):
    return rec["setup"]["layout_s"]
