"""The serving engine's own ``engine.*`` spans and programs in a traced run.

The engine records ``engine.prefill`` and ``engine.decode`` around each
``generate()`` call (``src/repro/serve/engine.py`` lists them and their
stats) through ``jax.profiler.TraceAnnotation``, so they sit in the traced
run's ``.xplane.pb`` with their integer stats.  This module reads them once
per file, checks that the file is this run's trace (its ``tick`` spans
match the record's in count and first start), and reduces them, with the
engine's ``jit_prefill`` / ``jit_decode`` programs, to per-layer numbers.
Every reader returns None where there is nothing to read: an untraced run,
another run's trace, or a program that records no ``engine.*`` span.
"""
from __future__ import annotations

import functools
import glob
import os

from . import common, counts, hybrid_counts, readers, trace

PREFIX = "engine."
TICK = "tick"
DECODE = "engine.decode"
PREFILL_PROGRAM = "jit_prefill"


@functools.lru_cache(maxsize=4)
def _load_file(path: str) -> tuple:
    """(name, start_ns, end_ns, stats) of every ``tick`` and ``engine.*``
    host event in one trace file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if (plane.name.startswith("/device:")
                and not plane.name.startswith("/device:CPU")):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == TICK or ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return tuple(out)


def load(trace_dir) -> tuple | None:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return _load_file(files[-1]) if files else None


def matches(spans, bench_spans) -> bool:
    """True where the ``tick`` spans agree with the benchmark's own record
    of them in count and first start."""
    mine = sorted(s for n, s, _, _ in spans if n == TICK)
    theirs = sorted(s for n, s, _ in bench_spans if n == TICK)
    return bool(mine) and len(mine) == len(theirs) and mine[0] == theirs[0]


def spans_of(rec):
    """The run's ``engine.*`` spans, or None where the record is untraced,
    the newest trace is not this run's, or the program records none."""
    tr = rec.get("trace")
    if tr is None:
        return None
    spans = load(common.TRACE_DIR)
    if not spans or not matches(spans, tr["spans"]):
        return None
    mine = [sp for sp in spans if sp[0].startswith(PREFIX)]
    return mine or None


def held_expert_load(rec):
    """Routed (useful) tokens per held expert per decode step: the held
    (token, expert) slots the router chose, as the ``engine.decode`` spans
    counted them over every layer, over their decode steps times the
    experts held in a layer times the layers (each has an expert layer).
    The dropless layer runs every held expert on every token; this counts
    only the tokens routed to it."""
    spans = spans_of(rec)
    if spans is None:
        return None
    decode = [st for n, _, _, st in spans if n == DECODE and "held_slots" in st]
    steps = sum(st.get("steps", 0) for st in decode)
    if not steps:
        return None
    held = sum(st["held_slots"] for st in decode)
    cfg = rec["config"]
    return held / steps / cfg["num_local_experts"] / cfg["num_hidden_layers"]


def prefill_s(rec):
    """Device seconds per ``jit_prefill`` program."""
    tr = rec.get("trace")
    if tr is None or "requests" not in rec:
        return None
    calls, ns = trace.count_modules(tr["modules"], PREFILL_PROGRAM)
    return ns / 1e9 / calls if calls else None


def decode_roofline(rec):
    """Least time of the decode steps the ``engine.decode`` spans ran, each
    at its span's own ``batch`` and with the cache at its ``prompt``
    length, averaged over the steps, over the measured device time per
    decode program, in %."""
    spans = spans_of(rec)
    step = readers.decode_step_s(rec)
    if spans is None or step is None:
        return None
    cfg = rec["config"]
    least = n = 0
    for name, _, _, st in spans:
        if name == DECODE and st.get("steps", 0):
            nbytes = hybrid_counts.decode_bytes(cfg, st["batch"],
                                                st["prompt"])
            least += st["steps"] * counts.least_seconds(
                0, nbytes, rec["peak"])[0]
            n += st["steps"]
    return 100.0 * least / n / step if n else None
