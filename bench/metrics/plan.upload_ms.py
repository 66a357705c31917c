"""Host-side staging of uploads per plan, in ms: the program's ceft.upload
spans, which end when the runtime has taken the arrays (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.upload_ms(rec)
