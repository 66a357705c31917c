"""Seconds from process start to the window: build, load, warm-up, compiles."""


def read(rec):
    return rec["setup_s"]
