"""Plans completed per second of the window (closed loop, one client)."""
from harness import readers


def read(rec):
    return readers.plans_per_s(rec)
