"""Median due-to-done latency over every request due in the window."""
from harness import readers


def read(rec):
    return readers.req_p50_ms(rec)
