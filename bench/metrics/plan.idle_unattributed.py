"""Share of the device-idle time inside plan spans that no ceft.* span of
the program covers, in % (traced window)."""
from harness import program_spans


def read(rec):
    return program_spans.idle_unattributed(rec)
