"""Find the highest request rate a serving cell sustains: run its runner at
each given rate, in one process, and print what the window showed.

  python3 bench/sweep.py --workload serve.minicpm2b.chat --seconds 30 \
      --seed 5 --rates 0.5 1.0 1.5 2.0

One JSON line per rate: the offered and completed rates, the share of the
requests due before the window's last ``TAIL_S`` seconds that completed
inside it, the median and 90th-percentile latency, the median latency of
the window's first and last thirds (a last third far slower means the
backlog grew through the run), and requests per dispatch.  The arrangement
of arrivals and sizes follows ``--seed`` even where the traffic file fixes
it (``pattern_seed``), so that two seeds see two arrangements.  A cell's
traffic file then fixes its rate as a number; the benchmark's own runs never
sweep.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import common, readers  # noqa: E402
import run as bench_run  # noqa: E402

# the longest request of the chat mix takes about 2 s of engine time: one
# due in the window's last seconds cannot complete inside it at any rate
TAIL_S = 5.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = common.load_json(BENCH.parent / "BENCHMARK.json")
    wl, config, traffic = bench_run.cell(bench, args.workload)
    device, peak = common.device_info(int(wl["chips"]))
    common.enable_cache()
    import jax

    from harness import serving

    dev = jax.devices()[0]
    for rate in args.rates:
        tr = dict(traffic, rate_per_s=rate)
        tr.pop("pattern_seed", None)
        ctx = SimpleNamespace(
            workload=wl, config=config, traffic=tr, seed=args.seed,
            seconds=args.seconds, t_start=time.perf_counter(), program=None,
            spans=common.Spans(), compiles=common.Compiles(),
            tracer=common.Tracer(args.workload, 0),
            memory_peak=lambda: common.memory_peak(dev))
        out = serving.run(ctx)
        rec = dict(out["rec"], window_s=out["window_s"], config=config,
                   traffic=tr, peak=peak)
        lat = readers.latencies_s(rec)
        third = max(1, len(lat) // 3)
        early = [r for r in rec["requests"]
                 if r["due"] <= args.seconds - TAIL_S]
        print(json.dumps({
            "rate": rate, "due": len(lat), "correct": out["correct"],
            "completed_in_window": sum(
                1 for r in rec["requests"]
                if r["done"] is not None and r["done"] <= args.seconds),
            "done_share": sum(
                1 for r in early
                if r["done"] is not None and r["done"] <= args.seconds)
                / max(len(early), 1),
            "p50_ms": readers.req_p50_ms(rec),
            "p90_ms": readers.req_p90_ms(rec),
            "first_third_p50_ms": 1e3 * statistics.median(lat[:third]),
            "last_third_p50_ms": 1e3 * statistics.median(lat[-third:]),
            "tokens_per_s": readers.tokens_per_s(rec),
            "batch_size": readers.batch_size(rec),
            "setup_s": out["setup_s"], "compiles_in_window":
                ctx.compiles.window,
            "memory_peak_bytes": out["memory_peak_bytes"],
            "device": device}), flush=True)
        del out, ctx, rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
