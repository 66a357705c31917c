"""Device-idle time inside the program's ceft.wait spans, in ms per plan
(traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.idle_in_wait_ms(rec)
