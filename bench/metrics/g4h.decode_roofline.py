"""Least bytes of the decode steps at each span's own batch, at HBM peak, over their device time, in %."""
from harness import engine_spans


def read(rec):
    return engine_spans.decode_roofline(rec)
