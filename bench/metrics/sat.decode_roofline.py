"""Least bytes of a decode step at HBM peak over its device time, above capacity, in %."""
from harness import readers


def read(rec):
    return readers.decode_roofline(rec)
