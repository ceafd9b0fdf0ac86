"""Generation + layout + warm-up, on the host clock."""


def read(rec):
    return rec["setup"]["setup_s"]
