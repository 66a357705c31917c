"""Share of the traced window with no device operation running, hybrid engine, in %."""
from harness import readers


def read(rec):
    return readers.idle_share(rec)
