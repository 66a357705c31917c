"""Readings that set a cell's limits: the program's compared numbers and the
control's, on the same answers, over many seeds in one process.

  python3 bench/control.py --workload plan.rgg16k.replan --seconds 3 \
      --seeds 11 12 13

For each seed it runs the cell's runner for a short window (on the chip, as
the benchmark does) and prints one JSON line with the program's compared
numbers and the control's on the same sampled answers: the reference in the
next precision below the configuration's (bfloat16 for the float32 planner;
per-tensor float8 for the bfloat16 engine), put in the program's place.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import common  # noqa: E402
import run as bench_run  # noqa: E402


def planner_control(out) -> dict:
    import ml_dtypes

    from harness import ceft_ref

    worst = {"cpl_rel": 0.0, "ceft_rel": 0.0, "path_rel": 0.0}
    for w, comp, _ in out["sample"]:
        args = (w["n"], w["src"], w["dst"], w["data"], comp, w["L"], w["bw"])
        ref = ceft_ref.ceft(*args)
        low = ceft_ref.ceft(*args, dtype=ml_dtypes.bfloat16)
        cpl = ref["cpl"]
        got = {"cpl_rel": abs(low["cpl"] - cpl) / cpl,
               "ceft_rel": float((abs(low["ceft"] - ref["ceft"])
                                  / abs(ref["ceft"]).clip(1.0)).max()),
               "path_rel": abs(ceft_ref.chain_cost(low["path"], *args)
                               - cpl) / cpl}
        for k, v in got.items():
            worst[k] = max(worst[k], v)
    return worst


def serving_control(cfg, out) -> dict:
    from harness import dense_lm, serving

    params, checked = out["sample"]
    return {"logit_gap": serving.compare(cfg, params, checked,
                                         quant=dense_lm.fp8_round)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = common.load_json(BENCH.parent / "BENCHMARK.json")
    wl, config, traffic = bench_run.cell(bench, args.workload)
    device, _ = common.device_info(int(wl["chips"]))
    common.enable_cache()
    import jax

    from harness import planner, serving

    dev = jax.devices()[0]
    runner = planner if config["runner"] == "planner" else serving
    for seed in args.seeds:
        ctx = SimpleNamespace(
            workload=wl, config=config, traffic=traffic, seed=seed,
            seconds=args.seconds, t_start=time.perf_counter(), program=None,
            spans=common.Spans(), compiles=common.Compiles(),
            tracer=common.Tracer(args.workload, 0),
            memory_peak=lambda: common.memory_peak(dev))
        out = runner.run(ctx)
        program = {name: value for name, value, _ in out["checks"]}
        control = (planner_control(out) if runner is planner
                   else serving_control(config, out))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"], "program": program,
                          "control": control, "device": device,
                          "setup_s": out["setup_s"]}), flush=True)
        del out, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
