"""Output tokens of requests completed within the window, per second."""
from harness import readers


def read(rec):
    return readers.tokens_per_s(rec)
