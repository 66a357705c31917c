"""Runner for planner deployments: one client plans, in a closed loop, on
the program's CSR CEFT sweep; answers are checked against the float64
reference once the window has closed.

Traffic modes:
  replan -- one resident graph, a fresh cost plane per call (every class
            slowed or sped by a factor from the seed): the straggler re-plan;
  fresh  -- a graph never seen before per call: a random relabelling, kept
            topological, of one of a few base structures.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from . import ceft_ref, rgg


def program():
    """The entries of the system under test that the window drives."""
    from repro.core.ceft_jax import ceft_jax_csr
    from repro.core.machine import Machine
    from repro.core.taskgraph import from_edge_arrays

    return SimpleNamespace(plan=ceft_jax_csr, graph=from_edge_arrays,
                           machine=Machine)


def relabel(base: dict, rng) -> dict:
    """The base graph under a random relabelling that keeps ids topological
    (ordered by level, shuffled within each level)."""
    order = np.lexsort((rng.random(base["n"]), base["level"]))
    new_id = np.empty(base["n"], np.int32)
    new_id[order] = np.arange(base["n"], dtype=np.int32)
    return dict(base, src=new_id[base["src"]], dst=new_id[base["dst"]],
                comp=base["comp"][order], level=base["level"][order])


def compare(w: dict, comp: np.ndarray, res) -> dict:
    """The numbers compared for one plan: relative gaps to the float64
    reference in the critical-path length, in the whole CEFT table, and in
    the float64 cost of the program's backtracked path."""
    args = (w["n"], w["src"], w["dst"], w["data"], comp, w["L"], w["bw"])
    ref = ceft_ref.ceft(*args)
    cpl = ref["cpl"]
    got = np.asarray(res.ceft, np.float64)
    table = float(np.max(np.abs(got - ref["ceft"])
                         / np.maximum(np.abs(ref["ceft"]), 1.0)))
    path_cost = ceft_ref.chain_cost(list(res.path), *args)
    return {"cpl_rel": abs(float(res.cpl) - cpl) / cpl,
            "ceft_rel": table,
            "path_rel": abs(path_cost - cpl) / cpl}


def run(ctx) -> dict:
    cfg, tr, seed = ctx.config, ctx.traffic, ctx.seed
    prog = ctx.program or program()
    mode = tr["mode"]
    t_setup = ctx.t_start
    # the deployment's structures are fixed by the configuration; the seed
    # draws their costs and every call's variation
    n_base = int(tr.get("base_graphs", 1))
    bases = []
    for b in range(n_base):
        bases.append(rgg.workload(
            cfg, np.random.default_rng(cfg["structure_seed"] + b),
            np.random.default_rng([seed, b])))
    for w in bases[1:]:   # one machine serves every structure
        w.update(L=bases[0]["L"], bw=bases[0]["bw"])
    m = prog.machine(bases[0]["L"], bases[0]["bw"],
                     np.ones(cfg["P"], np.int64))
    graphs = [prog.graph(w["n"], w["src"], w["dst"], w["data"])
              for w in bases]
    rng = np.random.default_rng([seed, 1 << 20])
    pick = np.random.default_rng([seed, 1 << 21])

    def next_call(i):
        if mode == "replan":
            f = rng.uniform(*tr["scale_range"], (1, cfg["P"]))
            return bases[0], bases[0]["comp"] * f, graphs[0]
        w = relabel(bases[i % n_base], rng)
        return w, w["comp"], None

    def call(w, comp, g):
        if g is None:
            g = prog.graph(w["n"], w["src"], w["dst"], w["data"])
        return prog.plan(g, comp, m)

    # warm every shape the window uses: each base, both paths
    for i in range(2 * n_base):
        w, comp, g = next_call(i)
        call(w, comp, g)
    setup_s = time.perf_counter() - t_setup

    keep = int(tr["check_plans"])
    sample: list = []          # reservoir of (w, comp, result) to check
    lat: list[float] = []
    spans, tracer = ctx.spans, ctx.tracer
    ctx.compiles.in_window = True
    tracer.start()
    t0 = time.perf_counter()
    i = 0
    while True:
        elapsed = time.perf_counter() - t0
        if tracer.due(elapsed):
            tracer.stop()
        if elapsed >= ctx.seconds:
            break
        with spans("client"):
            w, comp, g = next_call(i)
        with spans("plan"):
            a = time.perf_counter()
            res = call(w, comp, g)
            b = time.perf_counter()
        if b - t0 <= ctx.seconds:
            lat.append(b - a)
            k = len(lat)
            if len(sample) < keep:
                sample.append((w, comp, res))
            elif pick.integers(0, k) < keep:
                sample[int(pick.integers(0, keep))] = (w, comp, res)
        i += 1
    window_s = time.perf_counter() - t0
    tracer.stop()
    ctx.compiles.in_window = False
    mem = ctx.memory_peak()

    worst = {"cpl_rel": 0.0, "ceft_rel": 0.0, "path_rel": 0.0}
    for w, comp, res in sample:
        for k, v in compare(w, comp, res).items():
            worst[k] = max(worst[k], v)
    limits = cfg["limits"]
    checks = [(k, worst[k], limits[k]) for k in ("cpl_rel", "ceft_rel",
                                                 "path_rel")]
    correct = bool(sample) and all(v <= lim for _, v, lim in checks)
    w0 = bases[0]
    return {
        "setup_s": setup_s, "window_s": min(window_s, ctx.seconds),
        "attempted": i, "failed": 0, "correct": correct, "checks": checks,
        "memory_peak_bytes": mem, "sample": sample,
        "rec": {"latencies_s": lat, "plans": len(lat),
                "n": w0["n"], "P": cfg["P"], "n_edges": int(w0["src"].size)},
    }
