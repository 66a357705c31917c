"""90th percentile due-to-done latency above capacity (swings; not judged)."""
from harness import readers


def read(rec):
    return readers.req_p90_ms(rec)
