"""90th percentile due-to-done latency below capacity (recorded, not judged: it swings with where ticks end)."""
from harness import readers


def read(rec):
    return readers.req_p90_ms(rec)
