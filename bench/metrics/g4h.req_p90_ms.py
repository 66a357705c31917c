"""90th percentile due-to-done latency above capacity, hybrid engine (recorded, not judged)."""
from harness import readers


def read(rec):
    return readers.req_p90_ms(rec)
