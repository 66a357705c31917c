"""Runner for serving deployments: one model engine behind the program's
CEFT router, offered an open loop of requests from the traffic file, and
checked against the plain float32 reference once the window has closed.

The client submits every request that is due, then lets the router run one
tick (plan, dispatch, generate) and takes back what it completed; it sleeps
only when nothing is pending.  A request's latency runs from when it was
due, so a late submit counts against the server.  Requests due in the window
are served to the end after it closes (a drain of at most ``drain_s``).
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace

import numpy as np

from . import common, dense_lm, traffic

EOS_ID = 1   # the engine's end-of-sequence id: tokens after it are filler


def program():
    """The entries of the system under test that the window drives."""
    from repro import configs
    from repro.serve import EnginePool, Engine, Request, Router, WorkerSpec
    from repro.serve.engine import ServeConfig

    return SimpleNamespace(arch=configs.get, Engine=Engine, Pool=EnginePool,
                           Worker=WorkerSpec, Router=Router, Request=Request,
                           ServeConfig=ServeConfig)


MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab", "head_dim", "tie_embeddings", "norm_eps", "rope_theta",
              "compute_dtype", "param_dtype", "window", "family", "mlp_style")


def check_model(cfg: dict, arch) -> None:
    """The program must run the configuration as this file states it."""
    diff = {k: (cfg[k], getattr(arch, k)) for k in MODEL_KEYS
            if cfg[k] != getattr(arch, k)}
    if diff:
        raise ValueError(f"the program's {cfg['arch']} differs from "
                         f"the configuration (file, program): {diff}")


def build(ctx, prog):
    cfg = ctx.config
    arch = prog.arch(cfg["arch"])
    check_model(cfg, arch)
    params = dense_lm.make_params(cfg, ctx.seed)
    import jax

    jax.block_until_ready(params)
    engine = prog.Engine(arch, params=params, profile=cfg["profile"])
    pool = prog.Pool([prog.Worker(f"{cfg['arch']}:{cfg['profile']}",
                                  profile=cfg["profile"], engine=engine)],
                     probe="static", high_water=cfg["max_batch"])
    router = prog.Router(pool, max_batch=cfg["max_batch"])
    return params, engine, pool, router


def warm(ctx, prog, engine, router) -> None:
    """Compile what the window will run and nothing else: the engine's
    programs at every (batch, prompt, new) the mix can form, and the
    router's plans for every number of classes that can be pending."""
    cfg, tr = ctx.config, ctx.traffic
    rng = np.random.default_rng([ctx.seed, 1 << 22])
    for b, plen, new in traffic.shapes(tr, cfg["max_batch"]):
        prompts = rng.integers(2, cfg["vocab"], (b, plen)).astype(np.int32)
        engine.generate(prompts, prog.ServeConfig(max_new_tokens=new))
    classes = [(int(p), int(m)) for p in tr["prompt_len"]
               for m in tr["max_new"]]
    for g in range(len(classes), 0, -1):
        for plen, new in classes[:g]:
            router.submit(prog.Request(
                "warm", rng.integers(2, cfg["vocab"], plen).astype(np.int32),
                new))
        router.tick()   # plan only: the dispatches are dropped


def served_len(toks: np.ndarray, plen: int) -> int:
    """Generated tokens that the model chose: up to and including the first
    end-of-sequence token."""
    gen = toks[plen:]
    hit = np.nonzero(gen == EOS_ID)[0]
    return int(hit[0]) + 1 if hit.size else int(gen.size)


def compare(cfg: dict, params, reqs: list, quant=None) -> float:
    """Widest gap, over every served position of the given requests, by
    which the served token's logit lies below the reference's best.  With
    ``quant`` the gap is that of the token the quantized reference puts
    first instead (the control)."""
    import jax
    import jax.numpy as jnp

    S = max(r["prompt"].size + r["max_new"] for r in reqs)
    N = max(r["max_new"] for r in reqs)

    def gaps(params, tokens, positions, served):
        ref = dense_lm.forward_logits(cfg, params, tokens, positions)
        if quant is None:
            return dense_lm.served_gaps(ref, served)
        low = dense_lm.forward_logits(cfg, params, tokens, positions, quant)
        return dense_lm.served_gaps(ref, jnp.argmax(low, -1).astype(
            jnp.int32))

    fn = jax.jit(gaps)
    worst = 0.0
    for r in reqs:
        plen, toks = r["prompt"].size, r["tokens"]
        k = served_len(toks, plen)
        padded = np.zeros(S, np.int32)
        padded[:toks.size] = toks
        pos = np.full(N, plen - 1, np.int32)
        pos[:k] = np.arange(plen - 1, plen - 1 + k)
        served = np.full(N, toks[plen], np.int32)
        served[:k] = toks[plen:plen + k]
        g = np.asarray(fn(params, padded, pos, served))[:k]
        worst = max(worst, float(g.max()))
    return worst


def sample(done: list, k: int, rng) -> list:
    """k completed requests drawn from the seed, the longest among them."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt"].size + r["max_new"],
                                       -r["due"]))
    rest = [r for r in done if r is not longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def run(ctx) -> dict:
    cfg, tr = ctx.config, ctx.traffic
    prog = ctx.program or program()
    params, engine, pool, router = build(ctx, prog)
    warm(ctx, prog, engine, router)
    sched = traffic.open_loop(tr, ctx.seconds, cfg["vocab"], ctx.seed)
    setup_s = time.perf_counter() - ctx.t_start

    spans, tracer = ctx.spans, ctx.tracer
    recs = [dict(r, submit=None, done=None, tokens=None, rejected=False)
            for r in sched]
    by_rid: dict[int, dict] = {}
    completions = 0
    stats0 = dict(router.stats)
    drain = float(tr["drain_s"])
    busy = 0.0
    ctx.compiles.in_window = True
    tracer.start()
    t0 = time.perf_counter()
    nxt = 0
    while True:
        now = time.perf_counter() - t0
        if tracer.due(now):
            tracer.stop(background=True)
        with spans("submit"):
            while nxt < len(recs) and recs[nxt]["due"] <= now:
                r = recs[nxt]
                req = prog.Request(r["tenant"], r["prompt"], r["max_new"])
                r["submit"] = time.perf_counter() - t0
                if router.submit(req):
                    by_rid[req.rid] = r
                else:
                    r["rejected"] = True
                nxt += 1
        if now >= ctx.seconds + drain:
            break
        if len(router.queue) or router.resident:
            a = time.perf_counter()
            with spans("tick"):
                out = router.serve(max_ticks=1)
            b = time.perf_counter()
            busy += b - a
            for rid, toks in out.items():
                r = by_rid[rid]
                completions += 1
                if r["done"] is None:
                    r["done"] = b - t0
                    r["tokens"] = np.asarray(toks)
            continue
        if nxt >= len(recs):
            break
        with spans("wait-arrival"):
            time.sleep(max(0.0, recs[nxt]["due"] - now))
    tracer.stop()
    ctx.compiles.in_window = False
    stats = {k: router.stats[k] - stats0[k] for k in ("dispatches",
                                                      "coalesced", "plans")}
    mem = ctx.memory_peak()
    late = [r["submit"] - r["due"] for r in recs if r["submit"] is not None]
    common.log(f"open loop: {len(recs)} due, submitted late by at most "
               f"{max(late, default=0.0):.4f}s (median "
               f"{float(np.median(late)) if late else 0.0:.4f}s)")

    admitted = [r for r in recs if not r["rejected"]]
    missing = sum(r["done"] is None for r in admitted)
    doubled = completions - sum(r["done"] is not None for r in admitted)
    bad_tokens = sum(int(((r["tokens"] < 0) | (r["tokens"] >= cfg["vocab"]))
                         .any()) + int(not np.array_equal(
                             r["tokens"][:r["prompt"].size], r["prompt"]))
                     for r in admitted if r["done"] is not None)
    # the program's state goes before the reference runs on the same chip
    pool.close()
    del engine, pool, router
    gc.collect()
    rng = np.random.default_rng([ctx.seed, 1 << 23])
    done = [r for r in admitted if r["done"] is not None]
    checked = sample(done, int(tr["check_requests"]), rng)
    gap = compare(cfg, params, checked) if checked else float("inf")
    limits = cfg["limits"]
    checks = [("missing", missing, 0), ("duplicated", doubled, 0),
              ("bad_tokens", bad_tokens, 0),
              ("logit_gap", gap, limits["logit_gap"])]
    correct = all(v <= lim for _, v, lim in checks)
    return {
        "setup_s": setup_s, "window_s": ctx.seconds,
        "attempted": len(recs), "failed": sum(r["rejected"] for r in recs),
        "correct": correct, "checks": checks, "memory_peak_bytes": mem,
        "sample": (params, checked),
        "rec": {"requests": recs, "router": stats, "busy_s": busy,
                "checked_positions": sum(
                    served_len(r["tokens"], r["prompt"].size)
                    for r in checked)},
    }
