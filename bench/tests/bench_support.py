"""Drive a benchmark cell on the CPU at a small size, without the device
guard: the same runner, window and checks as a run on the chip."""
from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import common  # noqa: E402

PLANNER_SMALL = {"n": 256, "P": 8}
SERVING_SMALL = {"arch": "minicpm-2b", "n_layers": 2, "d_model": 64,
                 "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                 "vocab": 512}


def small_config(name: str) -> dict:
    cfg = common.load_json(BENCH / "configs" / f"{name}.json")
    cfg.update(PLANNER_SMALL if cfg["runner"] == "planner" else SERVING_SMALL)
    return cfg


def small_arch(cfg: dict):
    """The program's configuration at the small test size."""
    import dataclasses

    from repro import configs

    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab")
    return dataclasses.replace(configs.get(cfg["arch"]),
                               **{k: cfg[k] for k in keys})


def context(config: dict, traffic: dict, *, seed: int, seconds: float,
            program=None, trace_seconds: float = 0.0):
    return SimpleNamespace(
        workload={"name": "test", "chips": 1}, config=config,
        traffic=traffic, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), program=program,
        spans=common.Spans(), compiles=common.Compiles(),
        tracer=common.Tracer("test", trace_seconds),
        memory_peak=lambda: 0)
