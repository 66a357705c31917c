"""Sweep operations over (mean plan wall time x bf16 peak), in %."""
from harness import readers


def read(rec):
    return readers.plan_mfu(rec)
