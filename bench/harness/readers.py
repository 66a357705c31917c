"""Shared arithmetic of the per-metric readers in ``bench/metrics``.

Each reader takes the run's record and returns a number, or None where the
run has nothing to read (the harness then leaves the metric out)."""
from __future__ import annotations

import math

from . import counts, trace
from .common import percentile


# ------------------------------------------------------------------ planner
def plans_per_s(rec):
    return rec["plans"] / rec["window_s"] if "plans" in rec else None


def plan_p95_ms(rec):
    lat = rec.get("latencies_s")
    return 1e3 * percentile(lat, 95) if lat else None


def plan_ops_bytes(rec):
    return (counts.ceft_ops(rec["n_edges"], rec["P"]),
            counts.ceft_bytes(rec["n"], rec["n_edges"], rec["P"]))


def traced_plans(rec):
    """(plan spans in the trace, device ns inside them)."""
    tr = rec["trace"]
    n = sum(1 for name, _, _ in tr["spans"] if name == "plan")
    return n, trace.device_ns_in(tr["ops"], tr["spans"], "plan")


def sweep_device_s(rec):
    if rec.get("trace") is None or "plans" not in rec:
        return None
    n, ns = traced_plans(rec)
    return ns / 1e9 / n if n and ns else None


def sweep_roofline(rec):
    dev = sweep_device_s(rec)
    if dev is None:
        return None
    least, _ = counts.least_seconds(*plan_ops_bytes(rec), rec["peak"])
    return 100.0 * least / dev


def plan_mfu(rec):
    lat = rec.get("latencies_s")
    if not lat:
        return None
    ops, _ = plan_ops_bytes(rec)
    wall = sum(lat) / len(lat)
    return 100.0 * ops / (wall * rec["peak"]["bf16_flops_per_s"])


# ------------------------------------------------------------------- shared
def idle_share(rec):
    tr = rec.get("trace")
    if tr is None or tr["hi"] <= tr["lo"]:
        return None
    busy = trace.busy_ns(tr["ops"], tr["lo"], tr["hi"])
    return 100.0 * (1.0 - busy / (tr["hi"] - tr["lo"]))


# ------------------------------------------------------------------ serving
def latencies_s(rec):
    """Due-to-done seconds of every request due in the window; infinite for
    one refused or never completed."""
    out = []
    for r in rec.get("requests", []):
        done = None if r["rejected"] else r["done"]
        out.append(math.inf if done is None else done - r["due"])
    return out


def req_p50_ms(rec):
    lat = latencies_s(rec)
    return 1e3 * percentile(lat, 50) if lat else None


def req_p90_ms(rec):
    lat = latencies_s(rec)
    return 1e3 * percentile(lat, 90) if lat else None


def tokens_per_s(rec):
    """Output tokens of every request due in the window, over the seconds
    from the window's start until the last of them completed (the window's
    length, if that is longer): all the work the window offered and all the
    time it took.  A router tick hands back its answers when it ends, so a
    count cut at the window's end would swing with where the last tick
    ends; a request never completed adds no tokens."""
    if "requests" not in rec:
        return None
    done = [r for r in rec["requests"]
            if not r["rejected"] and r["done"] is not None]
    end = max([rec["window_s"]] + [r["done"] for r in done])
    return sum(r["max_new"] for r in done) / end


def batch_size(rec):
    st = rec.get("router")
    if not st or not st["dispatches"]:
        return None
    return (st["dispatches"] + st["coalesced"]) / st["dispatches"]


DECODE_PROGRAM = "jit_decode"


def decode_step_s(rec):
    tr = rec.get("trace")
    if tr is None or "requests" not in rec:
        return None
    calls, ns = trace.count_modules(tr["modules"], DECODE_PROGRAM)
    return ns / 1e9 / calls if calls else None


def decode_roofline(rec):
    """Least time of a decode step over its measured device time.  The
    least bytes are the smallest any decode call of the mix needs: every
    weight at the compute dtype and one sequence's cache at the mix's
    shortest total length."""
    step = decode_step_s(rec)
    if step is None:
        return None
    cfg, tr = rec["config"], rec["traffic"]
    shortest = min(int(p) for p in tr["prompt_len"]) + min(
        int(m) for m in tr["max_new"])
    nbytes = counts.dense_lm_decode_bytes(cfg, 1, shortest)
    least, _ = counts.least_seconds(0, nbytes, rec["peak"])
    return 100.0 * least / step


def engine_mfu(rec):
    """Model operations of every completed request over peak times the
    seconds the serve loop spent in router ticks."""
    if "requests" not in rec or not rec.get("busy_s"):
        return None
    cfg = rec["config"]
    ops = sum(counts.dense_lm_request_ops(cfg, r["prompt"].size,
                                          r["max_new"])
              for r in rec["requests"]
              if not r["rejected"] and r["done"] is not None)
    return 100.0 * ops / (rec["busy_s"] * rec["peak"]["bf16_flops_per_s"])
