"""Model operations of completed requests over peak x serve-loop busy time, in %."""
from harness import readers


def read(rec):
    return readers.engine_mfu(rec)
