"""The one generator that turns a traffic file into requests.

Every seed gets the same work: the counts of each size and tenant follow the
file's shares exactly (largest-remainder rounding), and only their order and
the arrival times come from the seed.  So runs on different seeds differ in
arrangement, not in the amount of work.  A file that names a
``pattern_seed`` fixes the arrangement too (arrival times, tenants and
sizes, drawn from that seed); the run's seed then draws the prompts' tokens
alone, as a replayed trace does.
"""
from __future__ import annotations

import numpy as np


def exact_counts(shares: dict, total: int) -> dict:
    """Split ``total`` by ``shares`` (need not sum to 1) into whole counts
    that sum to ``total``, by largest remainder; ties go to the earlier key."""
    keys = list(shares)
    w = np.array([float(shares[k]) for k in keys])
    raw = w / w.sum() * total
    base = np.floor(raw).astype(int)
    rest = total - int(base.sum())
    order = sorted(range(len(keys)), key=lambda i: (-(raw[i] - base[i]), i))
    for i in order[:rest]:
        base[i] += 1
    return {k: int(c) for k, c in zip(keys, base)}


def shuffled_labels(shares: dict, total: int, rng) -> list:
    counts = exact_counts(shares, total)
    labels = [k for k, c in counts.items() for _ in range(c)]
    rng.shuffle(labels)
    return labels


def intensity(t: np.ndarray, bursts: dict | None) -> np.ndarray:
    """Relative arrival intensity at times t: 1, or ``factor`` during the
    first ``on_s`` seconds of every ``period_s``."""
    if not bursts:
        return np.ones_like(t)
    on = (t % bursts["period_s"]) < bursts["on_s"]
    return np.where(on, float(bursts["factor"]), 1.0)


def arrival_times(n: int, seconds: float, rng, bursts: dict | None = None):
    """n arrival times in [0, seconds): a Poisson stream's gaps, scaled so
    the n arrivals fill the window at its mean rate, then mapped through the
    burst pattern's cumulative intensity.  The gaps are the exponential
    distribution's n + 1 evenly spaced quantiles in an order drawn from the
    seed, so every seed offers the same gaps, arranged otherwise."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
    rng.shuffle(gaps)
    u = np.cumsum(gaps)[:n] / gaps.sum()            # in (0, 1), sorted
    grid = np.linspace(0.0, seconds, 4097)
    lam = intensity(grid[:-1], bursts)
    cum = np.concatenate([[0.0], np.cumsum(lam * np.diff(grid))])
    return np.interp(u * cum[-1], cum, grid)


def open_loop(traffic: dict, seconds: float, vocab: int, seed: int) -> list:
    """The requests due in a window of ``seconds``: dicts with ``due`` (s
    from the window's start), ``tenant``, ``prompt`` (int32 ids in
    [2, vocab)) and ``max_new``, sorted by ``due``."""
    rng = np.random.default_rng(seed)
    pattern = traffic.get("pattern_seed")
    arrange = rng if pattern is None else np.random.default_rng(pattern)
    n = int(round(traffic["rate_per_s"] * seconds))
    due = arrival_times(n, seconds, arrange, traffic.get("bursts"))
    tenants = shuffled_labels(traffic["tenants"], n, arrange)
    plens = shuffled_labels(traffic["prompt_len"], n, arrange)
    news = shuffled_labels(traffic["max_new"], n, arrange)
    out = []
    for i in range(n):
        plen = int(plens[i])
        out.append({"due": float(due[i]), "tenant": tenants[i],
                    "prompt": rng.integers(2, vocab, plen).astype(np.int32),
                    "max_new": int(news[i])})
    return out


def shapes(traffic: dict, max_batch: int) -> list[tuple[int, int, int]]:
    """Every (batch, prompt, max_new) an engine call of this mix can have."""
    return [(b, int(p), int(m)) for b in range(1, max_batch + 1)
            for p in traffic["prompt_len"] for m in traffic["max_new"]]
