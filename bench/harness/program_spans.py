"""The program's own ``ceft.*`` spans inside the benchmark's ``plan`` spans.

The planner call records spans through ``jax.profiler.TraceAnnotation``
(``src/repro/core/spans.py`` lists them), so they sit in the traced run's
``.xplane.pb`` on the device's clock, with their integer stats.  This module
reads them once per process, checks that the file is this run's trace (its
``plan`` spans match the record's in count and first start) and reduces them
to per-plan numbers.  Every reader returns None where there is nothing to
read: an untraced run, another run's trace, or a program that records no
``ceft.*`` span.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os

from . import common, trace

PREFIX = "ceft."
PLAN = "plan"
HOST_PREP = ("ceft.graph", "ceft.levels", "ceft.fuse")
READBACK = ("ceft.readback", "ceft.finalize")


@functools.lru_cache(maxsize=4)
def _load_file(path: str) -> tuple:
    """(name, start_ns, end_ns, stats) of every ``plan`` and ``ceft.*``
    host event in one trace file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        # host planes, as trace.load reads the benchmark's own spans
        if (plane.name.startswith("/device:")
                and not plane.name.startswith("/device:CPU")):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == PLAN or ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return tuple(out)


def load(trace_dir) -> tuple | None:
    """The ``plan`` and ``ceft.*`` spans of the newest ``.xplane.pb`` under
    ``trace_dir`` (None where there is none), read once per file."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return _load_file(files[-1]) if files else None


def matches(spans, bench_spans) -> bool:
    """True where the ``plan`` spans agree with the benchmark's own record
    of them in count and first start."""
    mine = sorted(s for n, s, _, _ in spans if n == PLAN)
    theirs = sorted(s for n, s, _ in bench_spans if n == PLAN)
    return bool(mine) and len(mine) == len(theirs) and mine[0] == theirs[0]


def spans_of(rec):
    """The run's ``plan`` and ``ceft.*`` spans, or None where the record is
    untraced or the newest trace is not this run's."""
    tr = rec.get("trace")
    if tr is None:
        return None
    spans = load(common.TRACE_DIR)
    return spans if spans and matches(spans, tr["spans"]) else None


def inside_plans(spans):
    """(merged plan intervals, number of plan spans, the ``ceft.*`` spans
    that lie inside one)."""
    plans = trace.union((s, e) for n, s, e, _ in spans if n == PLAN)
    starts = [s for s, _ in plans]
    mine = []
    for n, s, e, st in spans:
        if not n.startswith(PREFIX):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= plans[i][1]:
            mine.append((n, s, e, st))
    return plans, sum(1 for n, _, _, _ in spans if n == PLAN), mine


def self_ns(spans, names) -> int:
    """Summed self time of the spans called one of ``names``: each span's
    duration less what the other spans inside it cover."""
    ordered = sorted((s, e) for _, s, e, _ in spans)
    starts = [s for s, _ in ordered]
    tot = 0
    for n, s, e, _ in spans:
        if n not in names:
            continue
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        kids = [(cs, ce) for cs, ce in ordered[lo:hi]
                if ce <= e and (cs, ce) != (s, e)]
        tot += (e - s) - trace.length(trace.clip(trace.union(kids), s, e))
    return tot


def _program(rec):
    """:func:`inside_plans` of the run, or None with nothing to read."""
    spans = spans_of(rec)
    if spans is None:
        return None
    got = inside_plans(spans)
    return got if got[1] and got[2] else None


def per_plan_ms(rec, names):
    """Self time of the named spans, in ms per plan span."""
    got = _program(rec)
    if got is None:
        return None
    _, n_plans, mine = got
    return self_ns(mine, set(names)) / 1e6 / n_plans


def host_prep_ms(rec):
    return per_plan_ms(rec, HOST_PREP)


def upload_ms(rec):
    return per_plan_ms(rec, ("ceft.upload",))


def readback_ms(rec):
    return per_plan_ms(rec, READBACK)


def gbps(rec, name):
    """Summed ``bytes`` stat over summed self time of the spans called
    ``name`` inside plans, in GB/s."""
    got = _program(rec)
    if got is None:
        return None
    named = [sp for sp in got[2] if sp[0] == name]
    nbytes = sum(st.get("bytes", 0) for _, _, _, st in named)
    ns = self_ns(got[2], {name})
    return nbytes / ns if nbytes and ns else None


def upload_gbps(rec):
    return gbps(rec, "ceft.upload")


def readback_gbps(rec):
    return gbps(rec, "ceft.readback")


def state_rebuild_share(rec):
    """Share of the device-state fetches inside plans that rebuilt the
    state (``hit`` 0), in %."""
    got = _program(rec)
    if got is None:
        return None
    hits = [st.get("hit") for n, _, _, st in got[2] if n == "ceft.state"]
    return 100.0 * hits.count(0) / len(hits) if hits else None


def sweep_useful_share(rec):
    """Real edges over edge slots relaxed by the sweeps inside plans, in %."""
    got = _program(rec)
    if got is None:
        return None
    sweeps = [st for n, _, _, st in got[2] if n == "ceft.sweep"]
    slots = sum(st.get("edge_slots", 0) for st in sweeps)
    real = sum(st.get("real_edges", 0) for st in sweeps)
    return 100.0 * real / slots if slots else None


def idle_in(rec, intervals) -> int:
    """Device-idle nanoseconds of the traced window inside ``intervals``
    (merged)."""
    tr = rec["trace"]
    busy = trace.union((s, e) for _, s, e in tr["ops"])
    inside = trace.clip(intervals, tr["lo"], tr["hi"])
    return trace.length(inside) - trace.overlap(busy, inside)


def idle_unattributed(rec):
    """Device-idle time inside ``plan`` spans that no ``ceft.*`` span
    covers, over all device-idle time inside ``plan`` spans, in %."""
    got = _program(rec)
    if got is None:
        return None
    plans, _, mine = got
    idle = idle_in(rec, plans)
    covered = idle_in(rec, trace.union((s, e) for _, s, e, _ in mine))
    return 100.0 * (idle - covered) / idle if idle else None


def idle_in_wait_ms(rec):
    """Device-idle time inside the ``ceft.wait`` spans of plans, in ms per
    plan: the device idles while the host waits on it."""
    got = _program(rec)
    if got is None:
        return None
    _, n_plans, mine = got
    waits = trace.union((s, e) for n, s, e, _ in mine if n == "ceft.wait")
    return idle_in(rec, waits) / 1e6 / n_plans
