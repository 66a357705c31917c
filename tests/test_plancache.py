"""Unified plan cache (repro.sched.plancache): bit-identity of partial
dirty-frontier re-sweeps vs from-scratch sweeps on adversarial multi-run
graphs, reverse-index invalidation, LRU eviction, trace-grid reuse and
concurrent plan/invalidate safety (ISSUE 6)."""
import threading

import numpy as np
import pytest

from repro.core import Machine, from_edges, uniform_machine
from repro.core import ceft_jax
from repro.core.ceft_jax import CSR_TRACES, ceft_jax_csr
from repro.sched import PlanCache
from repro.sched import plancache as PC


#: adversarial shape: alternating wide plateaus and width-1 tails defeat the
#: fuse-waste heuristic into FOUR fused runs (spans (1,6),(6,10),(10,14),
#: (14,18)), so dirty-frontier resume engages at several distinct depths.  A
#: uniform layered graph fuses into a single run and every delta degenerates
#: to a full sweep.
WIDTHS = (64,) + (1,) * 5 + (64,) + (1,) * 5 + (64,) + (1,) * 5


def _layered_graph(rng, widths=WIDTHS, max_par=3):
    """Layered DAG with <= ``max_par`` parents per vertex, random weights."""
    starts, edges, base = [], [], 0
    for w in widths:
        starts.append(base)
        base += w
    n = base
    for li in range(1, len(widths)):
        lo, w = starts[li], widths[li]
        plo, pw = starts[li - 1], widths[li - 1]
        for v in range(lo, lo + w):
            k = min(pw, int(rng.integers(1, max_par + 1)))
            for u in rng.choice(pw, size=k, replace=False):
                edges.append((plo + int(u), v, float(rng.uniform(0.5, 4.0))))
    return from_edges(n, edges), np.asarray(starts)


def _machine(P=3):
    return uniform_machine(P, bw=1.0, L=0.1)


def _assert_bit_identical(res, ref):
    np.testing.assert_array_equal(res.ceft, ref.ceft)
    np.testing.assert_array_equal(res.pred_task, ref.pred_task)
    np.testing.assert_array_equal(res.pred_proc, ref.pred_proc)
    assert res.sink == ref.sink and res.sink_proc == ref.sink_proc
    assert res.cpl == ref.cpl
    assert res.path == ref.path and res.assignment == ref.assignment


def test_graph_splits_into_multiple_runs():
    """Precondition for everything below: the adversarial shape must produce
    >= 2 fused runs past the folded level-0 init."""
    g, _ = _layered_graph(np.random.default_rng(0))
    runs, _, _ = PC.device_state(g)
    spans = [r.levels for r in runs]
    assert len(spans) >= 3, spans
    # spans tile the non-source levels contiguously from level 1
    assert spans[0][0] == 1
    for (a, b), (c, d) in zip(spans, spans[1:]):
        assert b == c


@pytest.mark.parametrize("where", ["deep", "mid", "source"])
def test_cost_delta_resweeps_are_bit_identical(where):
    """A changed cost plane re-sweeps from its dirty frontier only, and the
    result is bit-identical to a from-scratch sweep: deep deltas resume a
    late run (partial), mid deltas an earlier one, source deltas force a
    full sweep (level 0 is folded into the init)."""
    rng = np.random.default_rng(1)
    g, starts = _layered_graph(rng)
    m = _machine()
    comp = rng.uniform(1, 10, (g.n, m.P))
    pc = PlanCache()
    res0, status0, _ = pc.plan(g, comp, m)
    assert status0 == "full"
    _assert_bit_identical(res0, ceft_jax_csr(g, comp, m))

    comp2 = comp.copy()
    # deep: last run's tail; mid: second run; source: level 0 (folded into
    # the init — any delta there must force a full sweep)
    row = {"deep": int(starts[16]), "mid": int(starts[7]), "source": 0}[where]
    comp2[row] *= 1.7
    res2, status2, _ = pc.plan(g, comp2, m)
    assert status2 == ("full" if where == "source" else "partial")
    _assert_bit_identical(res2, ceft_jax_csr(g, comp2, m))
    assert pc.snapshot()["hits"] == 0


@pytest.mark.parametrize("where", ["deep", "mid"])
def test_partial_resume_sweep_span_counts_only_resumed_runs(where,
                                                            ceft_trace):
    """The ceft.sweep span of a partial re-sweep counts the runs it resumes
    and their edge work, not the cached prefix's; its read-back is a full
    one."""
    rng = np.random.default_rng(3)
    g, starts = _layered_graph(rng)
    m = _machine(8)
    comp = rng.uniform(1, 10, (g.n, m.P))
    pc = PlanCache()
    pc.plan(g, comp, m)
    comp2 = comp.copy()
    level = {"deep": 16, "mid": 7}[where]
    comp2[int(starts[level])] *= 1.7
    (res, status, _), spans = ceft_trace(lambda: pc.plan(g, comp2, m))
    assert status == "partial"
    _assert_bit_identical(res, ceft_jax_csr(g, comp2, m))

    fused, v_b, run_spans = ceft_jax._fused_runs(g)
    start = next(r for r, (lo, hi) in enumerate(run_spans) if level < hi)
    assert start >= 1
    tail = fused[start:]
    # a dense run relaxes a (R, W, D) block of parent slots, a segment run
    # R levels of E edge slots
    want = {"edge_slots": sum(r.par.size if hasattr(r, "par")
                              else r.edge_src.size for r in tail),
            "real_edges": sum(int((r.par >= 0).sum()) if hasattr(r, "par")
                              else int(r.e_real.sum()) for r in tail)}
    assert [(n, st) for n, _, _, st in spans if n == "ceft.sweep"] == [
        ("ceft.sweep", want)]
    assert [n for n, _, _, _ in spans] == [
        "ceft.state", "ceft.upload", "ceft.sweep", "ceft.wait",
        "ceft.readback", "ceft.finalize"]


def test_chained_partials_and_straggler_flip_bit_identical():
    """partial -> partial -> column-rescale (straggler flip: every level
    dirty => full) -> partial again, each bit-identical to from-scratch."""
    rng = np.random.default_rng(2)
    g, starts = _layered_graph(rng)
    m = _machine()
    comp = rng.uniform(1, 10, (g.n, m.P))
    pc = PlanCache()
    pc.plan(g, comp, m)

    expected = {"full_sweeps": 1, "partial_sweeps": 0}
    deltas = {0: 6, 1: 10, 3: 15}  # levels in runs 1, 2 and 3
    for step in range(4):
        if step == 2:  # straggler flip: one class column 2.3x slower
            slow = np.ones(m.P)
            slow[1] = 2.3
            comp = comp * slow[None, :]
            expected["full_sweeps"] += 1
            want = "full"
        else:  # point deltas at increasing depth
            comp = comp.copy()
            comp[int(starts[deltas[step]])] *= float(rng.uniform(1.1, 3.0))
            expected["partial_sweeps"] += 1
            want = "partial"
        res, status, _ = pc.plan(g, comp, m)
        assert status == want, (step, status)
        _assert_bit_identical(res, ceft_jax_csr(g, comp, m))
    snap = pc.snapshot()
    assert snap["full_sweeps"] == expected["full_sweeps"]
    assert snap["partial_sweeps"] == expected["partial_sweeps"]


def test_arrival_departure_churn_bit_identical():
    """Different graphs (arrivals/departures change the DAG) get independent
    entries; revisiting an earlier graph+plane is a pure hit and every plan
    stays bit-identical to from-scratch."""
    rng = np.random.default_rng(3)
    m = _machine()
    pc = PlanCache()
    graphs = []
    for tail in (3, 5, 7):  # churn: the request tail grows/shrinks
        g, _ = _layered_graph(rng, widths=WIDTHS[:13] + (1,) * tail)
        comp = rng.uniform(1, 10, (g.n, m.P))
        res, status, _ = pc.plan(g, comp, m)
        assert status == "full"
        _assert_bit_identical(res, ceft_jax_csr(g, comp, m))
        graphs.append((g, comp))
    # departures: back to the first DAG — same plane, pure hit
    g0, comp0 = graphs[0]
    res, status, _ = pc.plan(g0, comp0, m)
    assert status == "hit"
    _assert_bit_identical(res, ceft_jax_csr(g0, comp0, m))
    assert len(pc) == 3


def test_lru_eviction_marks_evicted_entry_dirty():
    g = from_edges(4, [(0, 2, 1.0), (1, 2, 2.0), (2, 3, 1.0)])
    m = _machine(2)
    comp = np.asarray([[2.0, 3.0], [1.0, 4.0], [3.0, 2.0], [2.0, 2.0]])
    pc = PlanCache(capacity=2)
    _, _, e0 = pc.plan(g, comp, m, slot="a", classes=[(8, 4)])
    _, _, e1 = pc.plan(g, comp * 2, m, slot="b", classes=[(8, 4)])
    assert not e0.dirty
    pc.plan(g, comp * 3, m, slot="c")
    assert len(pc) == 2
    assert e0.dirty, "evicted entry must be flagged so holders replan"
    assert not e1.dirty
    # eviction also unindexed slot "a": a class invalidation flips only e1
    assert pc.invalidate(wclass=(8, 4)) == 1
    assert e1.dirty


def test_reverse_index_scopes_invalidation_to_workload_class():
    rng = np.random.default_rng(4)
    g, _ = _layered_graph(rng)
    m = _machine()
    comp = rng.uniform(1, 10, (g.n, m.P))
    pc = PlanCache()
    _, _, ea = pc.plan(g, comp, m, slot="a", classes=[(8, 4), (16, 4)])
    _, _, eb = pc.plan(g, comp * 2, m, slot="b", classes=[(32, 4)])
    assert pc.invalidate(wclass=(16, 4)) == 1
    assert ea.dirty and not eb.dirty
    assert pc.invalidate(wclass=(16, 4)) == 0  # already dirty: no new flips
    assert pc.invalidate(wclass=(99, 9)) == 0  # unknown class: touches nothing
    # an engine (straggler) delta rescales a whole comp column: dirty all
    assert pc.invalidate(engine=1) == 1
    assert eb.dirty
    # a byte-equal plane clears the advisory flag on its entry (hit)
    _, status, ea2 = pc.plan(g, comp, m, slot="a")
    assert status == "hit" and ea2 is ea and not ea.dirty


def test_partial_resume_reuses_jit_trace_grid():
    """ISSUE 6 satellite: dirty-frontier resumes must ride the existing
    _geo_bucket shape grid — re-sweeping with deltas at varied depths may
    not mint new jit traces."""
    rng = np.random.default_rng(5)
    g, starts = _layered_graph(rng)
    m = _machine()
    comp = rng.uniform(1, 10, (g.n, m.P))
    pc = PlanCache()
    pc.plan(g, comp, m)          # warm: full sweep traces this shape grid
    comp1 = comp.copy()
    comp1[g.n - 1] *= 1.5
    pc.plan(g, comp1, m)         # warm: one partial (cont-call traces)
    before = set(CSR_TRACES)
    for depth in (6, 10, 12, 16):  # resumes at several distinct runs/depths
        comp = comp.copy()
        comp[int(starts[depth])] *= float(rng.uniform(1.1, 2.0))
        _, status, _ = pc.plan(g, comp, m)
        assert status == "partial"
    assert set(CSR_TRACES) == before, (
        f"partial resumes minted new traces: {set(CSR_TRACES) - before}")


def test_concurrent_plan_and_invalidate_keeps_cache_coherent():
    """ISSUE 6 satellite: worker threads calling plan() on alternating cost
    planes while another thread hammers invalidate() must never serve a
    stale plan or tear the reverse index."""
    rng = np.random.default_rng(6)
    g, _ = _layered_graph(rng)
    m = _machine()
    planes = [rng.uniform(1, 10, (g.n, m.P)) for _ in range(2)]
    refs = [ceft_jax_csr(g, p, m) for p in planes]
    pc = PlanCache()
    errors: list = []
    stop = threading.Event()

    def planner(i):
        try:
            for it in range(12):
                p = planes[(i + it) % 2]
                res, _status, _ = pc.plan(
                    g, p, m, slot=None, classes=[(8, 4)])
                _assert_bit_identical(res, refs[(i + it) % 2])
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    def chaos():
        while not stop.is_set():
            pc.invalidate(wclass=(8, 4))
            pc.invalidate(engine=0)

    threads = [threading.Thread(target=planner, args=(i,)) for i in range(2)]
    tc = threading.Thread(target=chaos)
    tc.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    tc.join()
    assert not errors, errors
    snap = pc.snapshot()
    assert snap["hits"] + snap["full_sweeps"] + snap["partial_sweeps"] == 24
    # reverse index only references live plan keys
    with pc._lock:
        for keys in pc._by_class.values():
            assert keys <= set(pc._plans)


def test_graph_store_returns_same_object_for_equal_arrays():
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([2, 2, 3], np.int32)
    data = np.asarray([1.0, 2.0, 1.0])
    g1 = PC.graph_for(4, src, dst, data)
    g2 = PC.graph_for(4, src.copy(), dst.copy(), data.copy())
    assert g1 is g2
    # and identity-keyed device state is shared too
    r1 = PC.device_state(g1)
    r2 = PC.device_state(g2)
    assert r1[0] is r2[0]


def test_store_false_pass_is_transient_and_cannot_poison_cache():
    """ISSUE 8: a speculative pricing pass (the router's hedge re-plan) with
    store=False returns a correct fresh result but never evicts or
    overwrites the cached entry the steady-state ticks are served from."""
    rng = np.random.default_rng(9)
    g, _ = _layered_graph(rng)
    m = _machine()
    comp = rng.uniform(1, 10, (g.n, m.P))
    pc = PlanCache()
    res0, status0, entry0 = pc.plan(g, comp, m, slot="router")
    assert status0 == "full"
    # transient pass with a DIFFERENT plane into the same slot key
    hedged = comp.copy()
    hedged[:, 0] *= 1e6                      # price class 0 as lost
    res1, _, entry1 = pc.plan(g, hedged, m, slot="router", store=False)
    _assert_bit_identical(res1, ceft_jax_csr(g, hedged, m))
    assert entry1 is not entry0
    # the cached entry is untouched: the original plane still HITS
    res2, status2, entry2 = pc.plan(g, comp, m, slot="router")
    assert status2 == "hit" and entry2 is entry0
    _assert_bit_identical(res2, res0)
