"""Chip smoke: drive the system's main path once on a TPU and check what comes
out.  It is the quickest proof that the system still starts on the chip.

  python chip_smoke.py               # one chip: planner, serving, reference
  python chip_smoke.py --four-chips  # four chips: sharded training, 1 vs 4

One-chip phases:

* planner -- the paper's largest RGG (n=16384 tasks over P=64 classes) swept
  by the CSR CEFT planner on the chip, checked against the host NumPy CEFT
  (critical-path length and backtracked path), then 8 re-planning scenarios
  in one batched sweep, checked against a single sweep per scenario;
* serving -- the router launcher's own function, as for ``python -m
  repro.launch.serve --router --arch minicpm-2b --pool serve --batch 2
  --tenants 2 --requests 2 --prompt-len 128 --max-new 16``: minicpm-2b at
  published widths behind the CEFT router, every admitted request completed
  exactly once, every token in the vocabulary, prefill logits finite.
  ``--batch 2`` caps the micro-batch: with float32 weights the decode step
  fits one v5e at batch 2 (15.40 GiB) but not at batch 4 (16.11 GiB);
* reference -- minicpm-2b widths cut to 2 layers: last-token prefill logits
  on the chip against the same weights on the host CPU in float32 with
  "highest" matmul precision, max |chip - cpu| / max |cpu| held to
  REFERENCE_BOUND.

--four-chips runs only the sharded Trainer (minicpm-2b widths, 2 layers,
seq 512, batch 8, 3 steps at peak learning rate TRAIN_LR) on a (data=1,
model=4) mesh against the same run on one chip: per-step losses within
LOSS_BOUND, and each chip holding about a quarter of the sharded parameter
bytes.

Everything runs in this one process: a chip belongs to one process at a
time.  With no TPU the script exits non-zero before any phase.  Times on the
phase lines include set-up and compilation and are not a benchmark.  The
last line of stdout is a JSON object: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

# seeds the graphs, weights and prompts; the bounds below were set with it
SEED = 0
# bf16-vs-f32 last-token logits, max |err| / max |ref|: the same check run
# on the CPU (bf16 compute against float32 "highest") gives 0.0144-0.0165
# for seeds 0-2; the bound leaves 3x room for the chip's own bf16 rounding
REFERENCE_BOUND = 0.05
# peak learning rate of the 3 training steps: the trainer's smoke-scale 5e-3
# sends the 2-layer cut at published width from 11.3 to 18.9 at step 3
TRAIN_LR = 3e-4
# per-step |loss(1 chip) - loss(4 chips)| / loss(1 chip).  The model-axis
# split reorders partial sums, nothing else differs: 3.1e-7, 7.1e-7 and
# 2.9e-5 at steps 1-3 on four v5e chips.  Dropping the MLP down-projection's
# model-axis reduction moves the step-1 loss by 3.0e-3 there, which a bound
# of 1e-2 let through
LOSS_BOUND = 3e-4
# each chip's share of the sharded parameter bytes may stray this far from 1/4
SHARE_SLACK = 0.1

SRC = Path(__file__).resolve().parent / "src"


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ planner
def planner_phase() -> None:
    from repro.core import Machine, ceft
    from repro.core.ceft_jax import ceft_batch_csr_results, ceft_jax_csr
    from repro.graphs import rgg

    rng = np.random.default_rng(SEED)
    n, P = 16384, 64
    wl = rgg("high", n, P, rng, o=4, alpha=0.75, beta=50)
    g, comp, m = wl.graph, wl.comp, wl.machine
    t0 = time.perf_counter()
    host = ceft(g, comp, m)
    t_host = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev = ceft_jax_csr(g, comp, m)
    t_dev = time.perf_counter() - t0
    check(np.isclose(dev.cpl, host.cpl, rtol=2e-5),
          f"cpl {dev.cpl} on the chip vs {host.cpl} on the host")
    check(dev.path == host.path, "critical path differs from the host's")
    log(f"planner: rgg n={n} P={P} edges={g.n_edges} cpl={dev.cpl:.6f} "
        f"host_cpl={host.cpl:.6f} path_len={len(dev.path)} (path as host) "
        f"(chip {t_dev:.2f}s incl. compile, host numpy {t_host:.2f}s; "
        "not a benchmark)")

    # 8 re-planning scenarios: per-class slowdowns and startup scalings
    B = 8
    comps = comp[None] * rng.uniform(0.5, 2.0, (B, 1, P))
    Ls = np.asarray(m.L)[None] * rng.uniform(0.5, 2.0, (B, 1))
    bws = np.repeat(np.asarray(m.bw)[None], B, 0)
    t0 = time.perf_counter()
    batch = ceft_batch_csr_results(g, comps, Ls, bws)
    t_batch = time.perf_counter() - t0
    worst = 0.0
    for b in range(B):
        one = ceft_jax_csr(g, comps[b], Machine(Ls[b], bws[b], m.counts))
        check(np.isclose(batch[b].cpl, one.cpl, rtol=2e-5),
              f"scenario {b}: batched cpl {batch[b].cpl} vs single {one.cpl}")
        check(batch[b].path == one.path,
              f"scenario {b}: batched path differs from the single sweep's")
        worst = max(worst, float(np.max(np.abs(batch[b].ceft - one.ceft)
                                        / np.maximum(np.abs(one.ceft), 1.0))))
    log(f"planner: batched {B} scenarios agree with per-scenario sweeps "
        f"(cpl, path; max ceft rel diff {worst:.3g}) "
        f"({t_batch:.2f}s incl. compile; not a benchmark)")


# ------------------------------------------------------------------ serving
def serving_phase() -> None:
    from repro.launch import serve

    argv = ["--router", "--arch", "minicpm-2b", "--pool", "serve",
            "--batch", "2", "--tenants", "2", "--requests", "2",
            "--prompt-len", "128", "--max-new", "16"]
    args = serve.parser().parse_args(argv)
    t0 = time.perf_counter()
    cfg, router, done = serve.run_router(args)  # exits 1 unless exactly once
    t_serve = time.perf_counter() - t0
    check(len(done) == args.tenants * args.requests,
          f"{len(done)} of {args.tenants * args.requests} requests completed")
    for rid, toks in done.items():
        check(toks.shape[0] > 0 and toks.min() >= 0 and toks.max() < cfg.vocab,
              f"request {rid}: token out of [0, {cfg.vocab})")
    check(router.stats["plans"] >= 1, "the router never planned a tick")
    engine = router.slots[0].engine
    prompts = np.stack([toks[:args.prompt_len] for toks in done.values()
                        if toks.shape[0] == args.prompt_len + args.max_new])
    _, logits = engine.prefill(prompts)
    logits = np.asarray(logits)
    check(np.isfinite(logits).all(), "prefill logits are not finite")
    log(f"serving: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"vocab={cfg.vocab}: {len(done)}/{len(done)} requests completed "
        f"exactly once, plans={router.stats['plans']} "
        f"dispatches={router.stats['dispatches']}, prefill logits "
        f"{logits.shape} finite ({t_serve:.1f}s incl. compile; "
        "not a benchmark)")


# ---------------------------------------------------------------- reference
def reference_phase() -> None:
    import jax

    from repro import configs as C
    from repro.models.model import build

    cfg = dataclasses.replace(C.get("minicpm-2b"), n_layers=2)
    model = build(cfg)
    chip, cpu = jax.devices()[0], jax.devices("cpu")[0]
    params = model.init(jax.random.PRNGKey(SEED))
    tokens = np.random.default_rng(SEED).integers(2, cfg.vocab, (2, 128))
    tokens = tokens.astype(np.int32)
    _, got = jax.jit(model.prefill)(
        params, {"tokens": jax.device_put(tokens, chip)})
    check(next(iter(got.devices())) == chip, "prefill did not run on the chip")
    ref_model = build(dataclasses.replace(cfg, compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(ref_model.prefill)(
            jax.device_put(params, cpu), {"tokens": jax.device_put(tokens, cpu)})
    got = np.asarray(got, np.float64)[:, -1]
    want = np.asarray(want, np.float64)[:, -1]
    check(want.shape == (2, cfg.vocab), f"logits shape {want.shape}")
    err = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"reference: 2-layer minicpm-2b last-token logits, chip bf16 vs "
        f"cpu float32 highest: max rel err {err:.6g} (bound {REFERENCE_BOUND})")
    check(err <= REFERENCE_BOUND, f"max rel err {err} > {REFERENCE_BOUND}")


# --------------------------------------------------------------- four chips
def four_chip_phase() -> None:
    import jax

    from repro import configs as C
    from repro.configs.base import ShapeCell
    from repro.launch.mesh import make_test_mesh
    from repro.train import Trainer, TrainerConfig

    check(len(jax.devices()) >= 4, f"need 4 chips, have {len(jax.devices())}")
    cfg = dataclasses.replace(C.get("minicpm-2b"), n_layers=2)
    cell = ShapeCell("chip_smoke", seq_len=512, global_batch=8, kind="train")
    losses = {}
    for n in (1, 4):
        ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            tcfg = TrainerConfig(steps=3, ckpt_every=10**9, ckpt_dir=ckpt,
                                 log_every=1, seed=SEED, peak_lr=TRAIN_LR,
                                 profile="opt1")
            t0 = time.perf_counter()
            tr = Trainer(cfg, cell, tcfg, lambda n=n: make_test_mesh(n))
            metrics = tr.run()
            dt = time.perf_counter() - t0
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
        losses[n] = [mm["loss"] for mm in metrics if "loss" in mm]
        check(len(losses[n]) == 3 and np.isfinite(losses[n]).all(),
              f"{n} chip(s): losses {losses[n]}")
        mesh = dict(zip(tr.mesh.axis_names, tr.mesh.devices.shape))
        log(f"train: {n} chip(s) mesh={mesh} losses="
            f"{[round(x, 6) for x in losses[n]]} ({dt:.1f}s incl. compile "
            "and 2 checkpoints; not a benchmark)")
        if n == 4:
            per_dev: dict[int, int] = {}
            for leaf in jax.tree.leaves(tr.params):
                if leaf.sharding.is_fully_replicated:
                    continue
                for shard in leaf.addressable_shards:
                    per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                                + shard.data.nbytes)
            total = sum(per_dev.values())
            shares = {d: b / total for d, b in sorted(per_dev.items())}
            log(f"train: sharded parameter bytes per chip "
                f"{dict(sorted(per_dev.items()))} shares "
                f"{ {d: round(s, 4) for d, s in shares.items()} }")
            check(len(shares) == 4 and all(abs(s - 0.25) <= 0.25 * SHARE_SLACK
                                           for s in shares.values()),
                  f"parameters not split across 4 chips: {shares}")
        del tr
    rel = [abs(a - b) / abs(a) for a, b in zip(losses[1], losses[4])]
    log(f"train: 1 vs 4 chips per-step loss rel diff "
        f"{[f'{r:.3g}' for r in rel]} (bound {LOSS_BOUND})")
    check(max(rel) <= LOSS_BOUND, f"losses differ: {losses}")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1-vs-4-chip sharded training check")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's default device is {dev.platform!r}",
              file=sys.stderr)
        return 2
    log(f"device_kind={dev.device_kind!r} count={len(devices)} "
        f"jax={jax.__version__}")
    sys.path.insert(0, str(SRC))
    from repro.substrate import enable_compile_cache

    log(f"compilation cache: {enable_compile_cache()}")
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    phases = ([("four_chips", four_chip_phase)] if args.four_chips else
              [("planner", planner_phase), ("serving", serving_phase),
               ("reference", reference_phase)])
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except BaseException as e:  # SystemExit from the launcher included
            traceback.print_exc()
            print(f"chip_smoke: FAIL {name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 1
        log(f"phase {name} ok ({time.perf_counter() - t0:.1f}s wall; "
            "not a benchmark)")
    log(f"compilation cache hits={cache_events['hits']} "
        f"misses={cache_events['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
