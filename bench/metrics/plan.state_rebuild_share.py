"""Share of the program's ceft.state spans inside plans that rebuilt the
graph's device state, in % (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.state_rebuild_share(rec)
