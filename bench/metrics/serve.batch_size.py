"""Requests per engine dispatch, from the router's counters."""
from harness import readers


def read(rec):
    return readers.batch_size(rec)
