"""The plain CEFT reference (paper section 4, Algorithm 1), on edge arrays.

A copy of the program's vectorized NumPy ``ceft`` (one dense max-min-plus
contraction per task, float64), written against plain arrays so that it
takes nothing the program builds.  ``dtype`` selects the arithmetic: float64
is the reference, and a narrower type (bfloat16) gives the control that the
comparison must reject.  ``bench/tests`` checks it against the program's own
``repro.core.ceft``.
"""
from __future__ import annotations

import numpy as np


def parents_csr(n: int, src: np.ndarray, dst: np.ndarray, data: np.ndarray):
    """Parent lists of every vertex, parents ascending: (indptr, ids, data)."""
    order = np.lexsort((src, dst))
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, dst.astype(np.int64) + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, src[order].astype(np.int64), data[order]


def ceft(n, src, dst, data, comp, L, bw, dtype=np.float64) -> dict:
    """CEFT table, predecessor tables and the critical path.

    Returns {"ceft", "pred_task", "pred_proc", "cpl", "path"} with ``path``
    the [(task, class)] chain from an entry task to the exit that sets the
    critical-path length."""
    P = comp.shape[1]
    indptr, par, pdat = parents_csr(n, src, dst, data)
    comp_t = np.asarray(comp).astype(dtype)
    L_t = np.asarray(L).astype(dtype)
    bw_t = np.asarray(bw).astype(dtype)
    pdat_t = np.asarray(pdat).astype(dtype)
    table = np.zeros((n, P), dtype)
    pred_task = np.full((n, P), -1, np.int64)
    pred_proc = np.full((n, P), -1, np.int64)
    off = (~np.eye(P, dtype=bool)).astype(dtype)
    cols = np.arange(P)
    for ti in range(n):
        lo, hi = indptr[ti], indptr[ti + 1]
        if lo == hi:
            table[ti] = comp_t[ti]
            continue
        parents = par[lo:hi]
        comm = (L_t[:, None] + pdat_t[lo:hi, None, None] / bw_t) * off
        cand = table[parents][:, :, None] + comm
        argl = cand.argmin(axis=1)
        minl = np.take_along_axis(cand, argl[:, None, :], 1)[:, 0, :]
        argk = minl.argmax(axis=0)
        table[ti] = comp_t[ti] + minl[argk, cols]
        pred_task[ti] = parents[argk]
        pred_proc[ti] = argl[argk, cols]
    out_deg = np.bincount(src, minlength=n)
    sinks = np.nonzero(out_deg == 0)[0]
    per_proc = np.argmin(table[sinks], axis=1)
    per_cost = table[sinks, per_proc]
    k = int(np.argmax(per_cost))
    path = []
    t, p = int(sinks[k]), int(per_proc[k])
    while t >= 0:
        path.append((t, p))
        t, p = int(pred_task[t, p]), int(pred_proc[t, p])
    return {"ceft": table.astype(np.float64), "pred_task": pred_task,
            "pred_proc": pred_proc, "cpl": float(per_cost[k]),
            "path": path[::-1]}


def chain_cost(path, n, src, dst, data, comp, L, bw) -> float:
    """Exact float64 cost of a [(task, class)] chain: execution times plus
    class-view communication along its edges.  Infinite where the chain does
    not start at an entry task, does not end at an exit task, or uses an
    edge the graph does not have."""
    if not path:
        return float("inf")
    indptr, par, pdat = parents_csr(n, src, dst, data)
    out_deg = np.bincount(src, minlength=n)
    first, last = path[0][0], path[-1][0]
    if indptr[first + 1] != indptr[first] or out_deg[last] != 0:
        return float("inf")
    total = 0.0
    for i, (t, p) in enumerate(path):
        if not (0 <= t < n and 0 <= p < comp.shape[1]):
            return float("inf")
        total += float(comp[t, p])
        if i + 1 < len(path):
            t2, p2 = path[i + 1]
            if not 0 <= t2 < n:
                return float("inf")
            ps = par[indptr[t2]:indptr[t2 + 1]]
            hit = np.nonzero(ps == t)[0]
            if hit.size == 0:
                return float("inf")
            if p != p2:
                d = float(pdat[indptr[t2] + hit[0]])
                total += float(L[p]) + d / float(bw[p, p2])
    return total
