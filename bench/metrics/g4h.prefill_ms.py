"""Device milliseconds per prefill call of the hybrid engine (the chunked SSD path)."""
from harness import engine_spans


def read(rec):
    s = engine_spans.prefill_s(rec)
    return None if s is None else 1e3 * s
