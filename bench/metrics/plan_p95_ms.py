"""95th percentile of per-plan latency, call to result on the host."""
from harness import readers


def read(rec):
    return readers.plan_p95_ms(rec)
