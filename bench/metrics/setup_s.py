"""setup_s: process start to the start of the measured window (host clock)."""


def read(rec):
    return rec["setup_s"]
