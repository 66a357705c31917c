"""Routed (useful) tokens per held expert per decode step, from the engine spans' counters."""
from harness import engine_spans


def read(rec):
    return engine_spans.held_expert_load(rec)
