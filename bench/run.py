"""Run one benchmark cell once on the accelerator and print its result.

  python3 bench/run.py --workload plan.rgg16k.replan --seed 7 --seconds 10 \
      --trace 0

The cell, its configuration (``bench/configs/``), its traffic
(``bench/traffic/<traffic>.json``) and its metrics (``bench/metrics/<name>.py``)
are all found by name from ``BENCHMARK.json``; the configuration's ``runner``
names the module under ``bench/harness/`` that sets the cell up, drives the
measured window and checks the answers.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window's first seconds and
reports its per-layer metrics.

The last stdout line is one JSON object (correct, attempted, failed,
metrics, device, [breakdown], checks).  With no accelerator, too few chips,
a device missing from ``bench/peaks.json`` or no program beside the
benchmark, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness import common, trace as tracing  # noqa: E402

SPAN_NAMES = ("plan", "client", "submit", "tick", "wait-arrival")


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic) of the named cell."""
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = common.load_json(ROOT / cfg_entry["file"])
    traffic = common.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return w, config, traffic


def metrics_for(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced) or per-layer ones (traced)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def read_metric(name: str, rec: dict):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    bench = common.load_json(ROOT / "BENCHMARK.json")
    wl, config, traffic = cell(bench, args.workload)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        common.log(f"no program to measure beside the benchmark: {e}")
        return 2
    try:
        device, peak = common.device_info(int(wl["chips"]))
    except common.NoDevice as e:
        common.log(f"refusing to run: {e}")
        return 3
    common.enable_cache()
    import jax

    dev = jax.devices()[0]
    ctx = SimpleNamespace(
        workload=wl, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, t_start=T_START, program=None,
        spans=common.Spans(), compiles=common.Compiles(),
        tracer=common.Tracer(args.workload, min(
            args.seconds, traffic.get("trace_seconds", args.seconds))
            if args.trace else 0),
        memory_peak=lambda: common.memory_peak(dev))
    runner = importlib.import_module(f"harness.{config['runner']}")
    out = runner.run(ctx)
    common.log(f"set-up {out['setup_s']:.3f}s (compiles: {ctx.compiles.setup}"
               f"), window {out['window_s']:.3f}s, compiles in the window: "
               f"{ctx.compiles.window}")

    rec = dict(out["rec"], setup_s=out["setup_s"], window_s=out["window_s"],
               peak=peak, config=config, traffic=traffic, trace=None,
               spans=ctx.spans.items)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        ctx.tracer.wait()
        tr = tracing.load(str(ctx.tracer.dir), SPAN_NAMES)
        lo = min(s for _, s, _ in tr["spans"])
        hi = max(e for _, _, e in tr["spans"])
        tr.update(lo=lo, hi=hi)
        rec["trace"] = tr
        busy = tracing.busy_ns(tr["ops"], lo, hi) / max(tr["n_devices"], 1)
        device.update(busy_s=busy / 1e9, window_s=(hi - lo) / 1e9)
        breakdown = {"device_ops": tracing.top_ops(tr["ops"]),
                     "idle_gaps": tracing.idle_by_span(
                         tr["ops"], tr["spans"], lo, hi)}
    metrics = {}
    for m in metrics_for(bench, args.workload, bool(args.trace)):
        value = read_metric(m["name"], rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    common.log(f"correct={out['correct']}")
    for name, value, limit in out["checks"]:
        common.log(f"check {name} = {value!r} (limit {limit!r}): "
                   f"{'ok' if value <= limit else 'FAIL'}")
    print(common.result_line(out["correct"], out["attempted"], out["failed"],
                             metrics, device, out["checks"], breakdown),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
