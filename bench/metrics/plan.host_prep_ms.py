"""Host preprocessing per plan, in ms: self time of the program's
ceft.graph, ceft.levels and ceft.fuse spans (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.host_prep_ms(rec)
