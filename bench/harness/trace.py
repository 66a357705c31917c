"""Reduction from a profiler trace to the benchmark's device numbers.

A trace is reduced to two lists of (name, start_ns, end_ns) intervals on one
clock: the device's operations and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names).  Everything else here is plain
interval arithmetic on those lists, so it is checked on synthetic traces in
``bench/tests``.
"""
from __future__ import annotations

import glob
import os

# the line of a device plane whose events are single device operations;
# "XLA Modules" holds whole programs (one event per jitted call)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) pairs covering the given intervals."""
    out: list[list[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def clip(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def overlap(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy_ns(ops, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which some device operation ran."""
    return length(clip(union((s, e) for _, s, e in ops), lo, hi))


def device_ns_in(ops, spans, name: str) -> int:
    """Device-busy nanoseconds that fall inside host spans called ``name``."""
    busy = union((s, e) for _, s, e in ops)
    inside = union((s, e) for n, s, e in spans if n == name)
    return overlap(busy, inside)


def top_ops(ops, k: int = 10) -> list[list]:
    """The k operation names with the most device time: [[name, seconds]]."""
    tot: dict[str, int] = {}
    for n, s, e in ops:
        tot[n] = tot.get(n, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_by_span(ops, spans, lo: int, hi: int, k: int = 10) -> list[list]:
    """Idle device time in [lo, hi), split by the host span it fell in
    (``"none"`` where the host was in no span): [[span, seconds]], most
    idle first, at most k entries."""
    busy = clip(union((s, e) for _, s, e in ops), lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    tot: dict[str, int] = {}
    covered = 0
    for name in sorted({n for n, _, _ in spans}):
        ns = overlap(gaps, union((s, e) for n, s, e in spans if n == name))
        if ns:
            tot[name] = ns
            covered += ns
    rest = length(gaps) - covered
    if rest > 0:
        tot["none"] = rest
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def count_modules(modules, prefix: str) -> tuple[int, int]:
    """(calls, device ns) of the programs whose name starts with prefix."""
    hits = [(s, e) for n, s, e in modules if n.startswith(prefix)]
    return len(hits), sum(e - s for s, e in hits)


def load(trace_dir: str, span_names) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` and reduce it
    (``reduce``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(ProfileData.from_file(files[-1]).planes, span_names)


def reduce(planes, span_names) -> dict:
    """{"ops", "modules", "spans", "n_devices"} of a trace's planes: device
    operations and whole-program events of every accelerator plane, and the
    host spans whose names are in ``span_names``, each as (name, start_ns,
    end_ns).  An accelerator counts in ``n_devices`` only where it ran
    operations: a TPU trace also holds a ``/device:CUSTOM:...`` plane with
    no operation line."""
    wanted = set(span_names)
    ops, modules, spans = [], [], []
    devices = 0
    for plane in planes:
        is_device = (plane.name.startswith("/device:")
                     and not plane.name.startswith("/device:CPU"))
        lines = list(plane.lines)
        names = {line.name for line in lines}
        # single operations: the "XLA Ops" line, or any line of operations
        # where a device names it otherwise
        op_lines = ({OPS_LINE} if OPS_LINE in names else
                    {n for n in names if "Ops" in n})
        devices += is_device and bool(op_lines)
        for line in lines:
            if is_device and (line.name in op_lines
                              or line.name == MODULES_LINE):
                dst = modules if line.name == MODULES_LINE else ops
                for ev in line.events:
                    s = int(ev.start_ns)
                    dst.append((ev.name, s, s + int(ev.duration_ns)))
            elif not is_device:
                for ev in line.events:
                    if ev.name in wanted:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return {"ops": ops, "modules": modules, "spans": spans,
            "n_devices": devices}
