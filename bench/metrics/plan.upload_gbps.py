"""Bytes the program's ceft.upload spans stage for the device over their
self time, in GB/s (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.upload_gbps(rec)
