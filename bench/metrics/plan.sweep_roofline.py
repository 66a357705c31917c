"""Least time of one sweep (ops, bytes vs peaks) over its device time, in %."""
from harness import readers


def read(rec):
    return readers.sweep_roofline(rec)
