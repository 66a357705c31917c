"""Output tokens of the window's requests per second, over the window and its drain."""
from harness import readers


def read(rec):
    return readers.tokens_per_s(rec)
