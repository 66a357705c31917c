"""The paper's random task graphs (section 7.1), as plain arrays.

A copy of ``repro.graphs.rgg`` (``rgg_structure`` and the two-node-weight
``interval_workload``) that draws the same numbers from the same generator in
the same order, but returns edge arrays instead of a built graph.  The
benchmark keeps its own copy so that a change to the program's generator
cannot change the benchmark's inputs; ``bench/tests`` checks that the two
still agree.
"""
from __future__ import annotations

import numpy as np

INTERVALS = {
    "resource": ((1e2, 1e3), (1e3, 1e4)),
    "low": ((1e2, 1e3), (1e3, 1e4)),
    "medium": ((1e2, 1e3), (1e4, 1e5)),
    "high": ((1e2, 1e3), (1e5, 1e6)),
}


def structure(n: int, o: float, alpha: float, rng: np.random.Generator):
    """Level-structured DAG: (sorted (src, dst) edge list, level of vertex)."""
    height = max(2, min(n, int(round(np.sqrt(n) / alpha))))
    mean_w = max(1.0, alpha * np.sqrt(n))
    widths = []
    left = n
    for lvl in range(height):
        remaining_lvls = height - lvl
        if remaining_lvls == 1:
            w = left
        else:
            w = int(np.clip(rng.uniform(0.5 * mean_w, 1.5 * mean_w), 1,
                            left - (remaining_lvls - 1)))
        widths.append(w)
        left -= w
        if left == 0:
            break
    levels = []
    start = 0
    for w in widths:
        levels.append(np.arange(start, start + w))
        start += w
    lvl_of = np.zeros(n, np.int32)
    for li, lv in enumerate(levels):
        lvl_of[lv] = li
    edges: set[tuple[int, int]] = set()
    for li in range(1, len(levels)):
        for v in levels[li]:
            u = int(rng.choice(levels[li - 1]))
            edges.add((u, int(v)))
    target = int(o * n)
    later = [np.concatenate(levels[li + 1:]) if li + 1 < len(levels)
             else np.empty(0, int) for li in range(len(levels))]
    attempts = 0
    while len(edges) < target and attempts < 20 * target:
        attempts += 1
        u = int(rng.integers(0, n))
        cand = later[lvl_of[u]]
        if cand.size == 0:
            continue
        v = int(rng.choice(cand))
        edges.add((u, v))
    return sorted(edges), lvl_of


def _skew(lvl_of: np.ndarray, gamma: float, rng: np.random.Generator):
    n_lvl = int(lvl_of.max()) + 1
    hot = rng.random(n_lvl) < gamma
    return np.where(hot[lvl_of], 1.0 + 9.0 * gamma, 1.0)


def workload(spec: dict, rng: np.random.Generator,
             rng_costs: np.random.Generator | None = None) -> dict:
    """One RGG workload from a configuration's sizes: edge arrays sorted by
    (src, dst), the (n, P) cost plane and the machine's L and bw.  The
    structure comes from ``rng``; the costs from ``rng_costs`` where given
    (the program's generator draws both from one stream)."""
    n, P = spec["n"], spec["P"]
    b = spec["beta"] / 100.0 if spec["beta"] > 1 else spec["beta"]
    gamma = spec["gamma"]
    edges, lvl_of = structure(n, spec["o"], spec["alpha"], rng)
    rng = rng if rng_costs is None else rng_costs
    src = np.fromiter((a for a, _ in edges), np.int32, len(edges))
    dst = np.fromiter((z for _, z in edges), np.int32, len(edges))
    tI1, tI2 = INTERVALS[spec["heterogeneity"]]
    rI1, rI2 = INTERVALS["resource"]

    def draw_two(nu, I1, I2, prob):
        swap = rng.random(nu) >= prob
        lo = rng.uniform(*I1, size=nu)
        hi = rng.uniform(*I2, size=nu)
        return np.where(swap, hi, lo), np.where(swap, lo, hi)

    tw1, tw0 = draw_two(n, tI1, tI2, b)
    if gamma > 0:
        f = _skew(lvl_of, gamma, rng)
        tw1, tw0 = tw1 * f, tw0 * f
    pW1, pW0 = draw_two(P, rI1, rI2, spec["proc_beta"])
    comp = tw1[:, None] / pW1[None, :] + tw0[:, None] / pW0[None, :]
    wbar = comp.mean(axis=1)
    data = wbar[src] * spec["c"] * rng.uniform(1 - b / 2, 1 + b / 2,
                                               size=src.size)
    lo, hi = np.log(spec["bw_range"][0]), np.log(spec["bw_range"][1])
    bw = np.exp(rng.uniform(lo, hi, size=(P, P)))
    bw = np.sqrt(bw * bw.T)
    L = rng.uniform(0.0, 0.0, size=P)
    return {"n": n, "P": P, "src": src, "dst": dst, "data": data,
            "level": lvl_of, "comp": comp, "L": L, "bw": bw}
