"""Device-busy milliseconds inside each plan span (traced window)."""
from harness import readers


def read(rec):
    s = readers.sweep_device_s(rec)
    return None if s is None else 1e3 * s
