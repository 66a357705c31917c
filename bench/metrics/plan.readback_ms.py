"""Device-to-host transfer and result assembly per plan, in ms: the
program's ceft.readback and ceft.finalize spans (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.readback_ms(rec)
