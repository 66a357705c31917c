"""Model operations of completed requests over peak x busy time, above capacity, in %."""
from harness import readers


def read(rec):
    return readers.engine_mfu(rec)
