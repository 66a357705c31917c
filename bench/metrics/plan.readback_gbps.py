"""Bytes the program's ceft.readback spans copy to the host over their
self time, in GB/s (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.readback_gbps(rec)
