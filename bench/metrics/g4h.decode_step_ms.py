"""Device milliseconds per decode call of the hybrid engine."""
from harness import readers


def read(rec):
    s = readers.decode_step_s(rec)
    return None if s is None else 1e3 * s
