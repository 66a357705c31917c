"""Real edges over the edge slots the fused sweep relaxes, in %: the stats
of the program's ceft.sweep spans (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.sweep_useful_share(rec)
