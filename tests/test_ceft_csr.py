"""CSR/edge-centric CEFT sweep (ISSUE 3): equivalence against the paper's
Algorithm 1 on adversarial shapes, bit-identity against the padded dense
sweep, tie-breaking, and the bounded-compilation (bucketed jit shapes)
guarantee."""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import (
    ceft,
    ceft_reference,
    csr_level_segments,
    from_edges,
    linear_chain,
    random_machine,
    uniform_machine,
)
from repro.core.ceft_jax import (
    CSR_TRACES,
    ceft_jax,
    ceft_jax_csr,
    csr_device_inputs,
)
from repro.graphs import (
    epigenomics,
    fft_graph,
    gaussian_elimination,
    heavy_tail_fan_in,
    molecular_dynamics,
    rgg,
    star_fan_in,
)
from conftest import make_random_dag


def _machine(P, seed=0):
    return random_machine(P, np.random.default_rng(seed),
                          bw_range=(0.5, 2.0), L_range=(0.0, 1.0))


def _assert_equiv(g, comp, m):
    """CSR sweep == Algorithm 1 (values, cpl, backtracked path) and
    bit-identical to the padded dense jax sweep (same f32 arithmetic)."""
    ref = ceft_reference(g, comp, m)
    pad = ceft_jax(g, comp, m)
    csr = ceft_jax_csr(g, comp, m)
    np.testing.assert_allclose(csr.ceft, ref.ceft, rtol=2e-5)
    assert csr.cpl == pytest.approx(ref.cpl, rel=2e-5)
    assert csr.path == ref.path
    np.testing.assert_array_equal(csr.ceft, pad.ceft)
    np.testing.assert_array_equal(csr.pred_task, pad.pred_task)
    np.testing.assert_array_equal(csr.pred_proc, pad.pred_proc)
    assert csr.path == pad.path and csr.cpl == pad.cpl


# ------------------------------------------------------------ adversarial shapes
def test_single_task():
    g = from_edges(1, [])
    comp = np.array([[3.0, 7.0]])
    _assert_equiv(g, comp, _machine(2))


def test_linear_chain():
    rng = np.random.default_rng(1)
    g = linear_chain(17, data=2.5)
    _assert_equiv(g, rng.uniform(1, 10, (17, 3)), _machine(3))


def test_star_fan_in_degree_much_larger_than_mean():
    rng = np.random.default_rng(2)
    g = star_fan_in(65)  # sink in-degree 64, every other in-degree 0
    assert int(g.in_degree.max()) == 64
    _assert_equiv(g, rng.uniform(1, 10, (65, 4)), _machine(4))


def test_heavy_tail_fan_in():
    rng = np.random.default_rng(3)
    g = heavy_tail_fan_in(80, rng)
    assert int(g.in_degree.max()) > 2 * float(g.in_degree.mean())
    _assert_equiv(g, rng.uniform(1, 10, (80, 3)), _machine(3))


@pytest.mark.parametrize("seed,g", [
    (101, gaussian_elimination(6)),
    (102, fft_graph(8)),
    (103, molecular_dynamics()),
    (104, epigenomics(6)),
])
def test_realworld_graphs(seed, g):
    rng = np.random.default_rng(seed)
    _assert_equiv(g, rng.uniform(1, 10, (g.n, 4)), _machine(4))


@pytest.mark.parametrize("seed,g", [
    (201, gaussian_elimination(6)),
    (202, molecular_dynamics()),
    (203, star_fan_in(33)),
])
def test_transposed_graphs(seed, g):
    """The edge-reversed graphs rank_ceft_up sweeps (paper §8.2)."""
    gt = g.transpose()
    rng = np.random.default_rng(seed)
    _assert_equiv(gt, rng.uniform(1, 10, (gt.n, 3)), _machine(3))


def test_tie_breaking_matches_reference():
    """Exactly-tied candidates (integer weights, homogeneous machine): the
    first maximal parent in ascending-id order must win, as in Algorithm 1."""
    # two parents of 3 with identical values and identical edges, twice over
    g = from_edges(4, [(0, 3, 1.0), (1, 3, 1.0), (2, 3, 1.0)])
    comp = np.array([[2.0, 2.0], [2.0, 2.0], [2.0, 2.0], [1.0, 1.0]])
    m = uniform_machine(2, bw=1.0, L=0.0)
    ref = ceft_reference(g, comp, m)
    csr = ceft_jax_csr(g, comp, m)
    assert csr.path == ref.path
    np.testing.assert_array_equal(csr.pred_task, ref.pred_task)
    np.testing.assert_array_equal(csr.pred_proc, ref.pred_proc)


def _tied_segment_graph(masked: bool):
    """A graph whose exactly-tied parents reach the segment-layout body with
    several children per level (W_b > 1).

    A 20-level chain fills the first fused run.  Then a row of ``S`` tasks
    hangs off the chain's tail, alternating comp profiles A = [3, 9] and
    B = [9, 2].  With unit data on a homogeneous machine, an A and a B
    parent tie for class 0 with different arg-min classes (0 and 1).  Tie
    level 1 gives its five children 12, 3, 3, 3 and 3 such parents (24
    edges).  With ``masked``, S = 12 and a second tie level follows: its
    children have 8, 2, 2, 2 and 3 parents, all tie level 1 plus dominated
    row tasks (17 edges).  The run then holds one level without padded edge
    slots and two with them.  Without it, S = 24 and every level of the run
    fills its 24 edge slots.  Returns (graph, comp, first tie-level row)."""
    chain = 20
    S = 12 if masked else 24
    row = list(range(chain, chain + S))
    l1 = list(range(chain + S, chain + S + 5))
    edges = [(i, i + 1, 1.0) for i in range(chain - 1)]
    edges += [(chain - 1, t, 1.0) for t in row]
    for child, parents in zip(l1, [row[:12], row[:3], row[3:6], row[6:9],
                                   row[9:12]]):
        edges += [(p, child, 1.0) for p in parents]
    n = l1[-1] + 1
    if masked:
        l2 = list(range(n, n + 5))
        for child, parents in zip(l2, [l1 + row[:3], l1[:2], l1[1:3],
                                       l1[2:4], l1[2:]]):
            edges += [(p, child, 1.0) for p in sorted(parents)]
        n = l2[-1] + 1
    comp = np.ones((n, 2))
    comp[row] = [[3.0, 9.0] if i % 2 == 0 else [9.0, 2.0]
                 for i in range(S)]
    return from_edges(n, edges), comp, l1[0]


@pytest.mark.parametrize("masked", [True, False])
def test_tie_breaking_on_segment_path_matches_reference(masked):
    """Exactly-tied parents of several children per level, read through the
    segment layout's back-pointer selection (W_b > 1), in a masked and an
    unmasked run: the first maximal parent in ascending-id order wins, in a
    single sweep, a batched sweep and a plan-cache resume alike."""
    from repro.core.ceft_jax import ceft_batch_csr_results
    from repro.sched import PlanCache

    g, comp, l1 = _tied_segment_graph(masked)
    m = uniform_machine(2, bw=1.0, L=0.0)
    runs = csr_device_inputs(g, comp, m)[0]
    tie_run = runs[-1]
    assert len(runs) == 2 and tie_run.layout == "seg"
    assert tie_run.masked == masked and tie_run.tables[0].shape[-1] > 1

    comp2 = comp.copy()
    comp2[l1:l1 + 5] += 1.0  # tie level 1 only: its run resumes
    pc = PlanCache()
    pc.plan(g, comp, m)
    resumed, status, _ = pc.plan(g, comp2, m)
    assert status == "partial"
    batched = ceft_batch_csr_results(
        g, np.stack([comp, comp2]), np.stack([m.L, m.L]),
        np.stack([m.bw, m.bw]))
    for c, got in [(comp, ceft_jax_csr(g, comp, m)), (comp, batched[0]),
                   (comp2, batched[1]), (comp2, resumed)]:
        ref = ceft_reference(g, c, m)
        pad = ceft_jax(g, c, m)
        for want in (ref, pad):
            np.testing.assert_array_equal(got.pred_task, want.pred_task)
            np.testing.assert_array_equal(got.pred_proc, want.pred_proc)
            assert got.path == want.path
        # class 0 of the first child ties across all twelve parents, A (via
        # class 0) and B (via class 1): the first, an A, must win
        assert (got.pred_task[l1, 0], got.pred_proc[l1, 0]) == (
            g.parents(l1)[0], 0)


@given(st.integers(0, 10_000))
def test_csr_matches_reference_random(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 12))
    P = int(rng.integers(1, 5))
    g = make_random_dag(n, 0.4, rng)
    comp = rng.uniform(1, 10, size=(n, P))
    m = random_machine(P, rng, bw_range=(0.5, 2.0), L_range=(0.0, 1.0))
    _assert_equiv(g, comp, m)


# --------------------------------------------------------------- CSR structure
def test_csr_level_segments_roundtrip():
    rng = np.random.default_rng(7)
    g = make_random_dag(30, 0.3, rng)
    segs = csr_level_segments(g)
    seen = []
    for k in range(segs.n_levels):
        tasks = segs.level_tasks(k)
        seen.extend(tasks.tolist())
        assert (g.level[tasks] == k).all()
        esrc, edat, eseg = segs.level_edges(k)
        # per-child segments are contiguous, parents ascending within a segment
        assert (np.diff(eseg) >= 0).all()
        for slot, t in enumerate(tasks):
            sel = eseg == slot
            np.testing.assert_array_equal(np.sort(esrc[sel]), esrc[sel])
            np.testing.assert_array_equal(esrc[sel], g.parents(int(t)))
            np.testing.assert_array_equal(edat[sel], g.parent_data(int(t)))
    assert sorted(seen) == list(range(g.n))
    assert segs.edge_bounds[-1] == g.n_edges


# --------------------------------------------------------- bounded compilation
def test_bucketed_jit_shapes_bounded():
    """Sweeping 10 random graphs of varying size must trigger at most an
    O(log)-sized set of distinct per-level traces (pow2 buckets on vertex
    count, level width, and level edge count) -- not one trace per graph."""
    rng = np.random.default_rng(11)
    P = 4
    ns = [70, 95, 120, 150, 180, 210, 240, 300, 380, 450]
    wls = [rgg("high", n, P, rng, o=4, alpha=0.75, beta=50) for n in ns]
    before = set(CSR_TRACES)
    for wl in wls:
        ceft_jax_csr(wl.graph, wl.comp, wl.machine)
    new = set(CSR_TRACES) - before
    # naive shape handling would compile >= one sweep per graph (and the
    # per-level formulation, one per level: hundreds); buckets keep it
    # O(log).  Fused super-steps (ISSUE 4) add a pow2 run-length axis to the
    # jit key -- a further log(depth) factor (empirically ~4 distinct run
    # buckets here), still far below one shape per level
    bound = 8 * int(np.ceil(np.log2(max(ns))))
    assert 0 < len(new) <= bound, (len(new), bound)

    # re-planning shape: sweeping the same graphs again (new costs) retraces
    # nothing -- every bucketed level shape is already compiled
    before = set(CSR_TRACES)
    for wl in wls:
        comp2 = wl.comp * rng.uniform(1.0, 2.0, size=wl.comp.shape[1])[None, :]
        ceft_jax_csr(wl.graph, comp2, wl.machine)
    assert len(set(CSR_TRACES) - before) == 0


# ------------------------------------------------------- fused super-steps (ISSUE 4)
def test_fused_superstep_equivalence_chain():
    """64 relaxation levels, all in one (W, E) bucket: the whole chain must
    sweep as fused super-steps and still match Algorithm 1 exactly."""
    rng = np.random.default_rng(40)
    g = linear_chain(65, data=1.5)
    _assert_equiv(g, rng.uniform(1, 10, (65, 3)), _machine(3))


def test_fused_superstep_equivalence_ge_like():
    """GE graphs are deep with slowly shrinking widths: runs break only at
    pow2 bucket boundaries, exercising multi-run sweeps."""
    rng = np.random.default_rng(41)
    g = gaussian_elimination(9)
    _assert_equiv(g, rng.uniform(1, 10, (g.n, 4)), _machine(4))


def test_fused_superstep_equivalence_single_level():
    """A graph with a single level (no edges at all): the fused sweep runs
    zero super-steps and the result is pure comp."""
    rng = np.random.default_rng(42)
    g = from_edges(6, [])
    comp = rng.uniform(1, 10, (6, 3))
    _assert_equiv(g, comp, _machine(3))
    res = ceft_jax_csr(g, comp, _machine(3))
    np.testing.assert_allclose(res.ceft, comp.astype(np.float32), rtol=1e-6)
    assert (res.pred_task == -1).all()


def test_superstep_fns_keyed_by_backend(monkeypatch):
    """Regression (ISSUE 5): ``jax.default_backend()`` was read once when the
    jitted super-step closures were first built, so a backend selected
    afterwards (tests forcing CPU, a GPU coming up mid-process) inherited the
    wrong donation policy.  The cache must key by backend and re-read it per
    call."""
    import jax

    from repro.core import ceft_jax as cj

    cur = jax.default_backend()
    fns_cur = cj._superstep_fns(cj.xla_edge_relax)
    assert fns_cur["donate"] == (() if cur == "cpu" else (0, 1, 2))
    # a different backend becoming default gets fresh closures + donation
    monkeypatch.setattr(cj.jax, "default_backend", lambda: "faketpu")
    fns_tpu = cj._superstep_fns(cj.xla_edge_relax)
    assert fns_tpu is not fns_cur
    assert fns_tpu["donate"] == (0, 1, 2)
    # switching back re-serves the original backend's cached entry
    monkeypatch.setattr(cj.jax, "default_backend", lambda: cur)
    assert cj._superstep_fns(cj.xla_edge_relax) is fns_cur


def test_fusion_reduces_dispatch_count_on_deep_chain():
    """A 64-level chain used to dispatch one jitted step per level from
    Python; fused same-bucket super-steps collapse it to O(1) scanned
    dispatches (and at most O(log) traces across chain depths)."""
    rng = np.random.default_rng(43)
    g = linear_chain(65)
    comp = rng.uniform(1, 10, (65, 3))
    m = _machine(3)
    inputs = csr_device_inputs(g, comp, m)
    runs = inputs[0]  # one DeviceRun per fused run, tables led by tasks
    n_dispatch = len(runs)
    n_levels_covered = sum(int(r.tables[0].shape[0]) for r in runs)
    assert n_dispatch <= 2, f"chain not fused: {n_dispatch} dispatches"
    assert n_levels_covered >= 64  # every relaxation level is inside a run
    # the fused sweep itself still matches the unfused semantics
    _assert_equiv(g, comp, m)

    # more chains in the same (v, W, E, run-length) buckets (vertex counts
    # 58..64 all bucket to v_b=64, depths 57..63 to a run of 64): one compiled
    # super-step serves them all -- zero new traces after the first
    ceft_jax_csr(linear_chain(64), rng.uniform(1, 10, (64, 3)), m)
    before = set(CSR_TRACES)
    for n in (58, 61, 63):
        ceft_jax_csr(linear_chain(n), rng.uniform(1, 10, (n, 3)), m)
    assert len(set(CSR_TRACES) - before) == 0


def test_fuse_levels_noop_padding_rows():
    """Padded no-op levels (e_real == 0) carry only padding ids, so a scanned
    super-step can execute them without touching real DP rows."""
    g = linear_chain(8)  # 7 relaxation levels -> padded to a pow2 run of 8
    segs = csr_level_segments(g)
    from repro.core.taskgraph import fuse_levels
    widths = [8] * (segs.n_levels - 1)
    ecaps = [8] * (segs.n_levels - 1)
    runs = fuse_levels(segs, widths, ecaps, pad_vertex=99,
                       pad_run=lambda r: 8)
    (run,) = runs
    assert run.tasks.shape == (8, 8) and run.e_real[-1] == 0
    assert (run.tasks[-1] == 99).all() and (run.edge_src[-1] == 99).all()
    assert (run.edge_seg[-1] == run.width - 1).all()
    # real rows reproduce the per-level segments exactly
    for r in range(7):
        t = segs.level_tasks(r + 1)
        es, ed, eg = segs.level_edges(r + 1)
        np.testing.assert_array_equal(run.tasks[r, : len(t)], t)
        np.testing.assert_array_equal(run.edge_src[r, : len(es)], es)
        np.testing.assert_array_equal(run.edge_data[r, : len(es)], ed)
        np.testing.assert_array_equal(run.edge_seg[r, : len(es)], eg)


def test_hybrid_layout_choice():
    """The per-run layout policy: no within-level in-degree skew (chain, GE)
    -> run-local dense (R, W, D) tables; skewed fan-in (heavy tail) -> the
    O(e) segment layout.  Both are bit-identical to ceft_jax (asserted by
    the equivalence suite); this pins the policy itself."""
    rng = np.random.default_rng(50)
    m = _machine(3)

    def layouts(g):
        comp = rng.uniform(1, 10, (g.n, 3))
        return [r[0] for r in csr_device_inputs(g, comp, m)[0]]

    assert set(layouts(linear_chain(40))) == {"dense"}
    assert set(layouts(gaussian_elimination(8))) == {"dense"}
    assert "seg" in layouts(heavy_tail_fan_in(150, np.random.default_rng(51)))


def test_fuse_levels_dense_run_local_buckets():
    """Dense-layout runs are built from the CSR segments at *run-local*
    (W, D) buckets — the star graph's sink level must not pay for the
    40-wide source level, and the slot order must match the
    padded_level_tables convention (k-th slot = k-th parent ascending)."""
    from repro.core.taskgraph import fuse_levels_dense, padded_level_tables
    g = star_fan_in(41)  # level 1 = the sink: W=1, D=40
    segs = csr_level_segments(g)
    run = fuse_levels_dense(segs, 1, 2, 1, 48, pad_run=lambda r: 2)
    assert run.tasks.shape == (2, 1) and run.par.shape == (2, 1, 48)
    assert run.tasks[0, 0] == 40 and (run.tasks[1] == -1).all()  # no-op pad row
    np.testing.assert_array_equal(run.par[0, 0, :40], np.arange(40))
    assert (run.par[0, 0, 40:] == -1).all() and (run.par[1] == -1).all()
    # same slot convention as the global padded tables
    tables = padded_level_tables(g)
    np.testing.assert_array_equal(run.par[0, 0, :40], tables["par"][1, 0, :40])
    np.testing.assert_array_equal(run.pdata[0, 0, :40], tables["pdata"][1, 0, :40])
    with pytest.raises(ValueError):  # real parents must fit the caps
        fuse_levels_dense(segs, 1, 2, 1, 8)


# ------------------------------------------------------- batched CSR (ISSUE 4)
def _batch_inputs(g, B, P, rng):
    comps = rng.uniform(1, 10, (B, g.n, P)).astype(np.float32)
    Ls = rng.uniform(0, 1, (B, P)).astype(np.float32)
    bws = rng.uniform(0.5, 2, (B, P, P)).astype(np.float32)
    return comps, Ls, bws


@pytest.mark.parametrize("seed,g", [
    (301, linear_chain(33)),
    (302, gaussian_elimination(6)),
    (303, star_fan_in(40)),
    (304, heavy_tail_fan_in(60, np.random.default_rng(304))),
    (305, epigenomics(5)),
])
def test_batch_csr_bit_identical_to_batch_padded(seed, g):
    """ceft_jax_batch_csr must be bit-identical (values AND predecessor
    tables) to the vmapped padded sweep on the adversarial suite."""
    from repro.core.ceft_jax import ceft_jax_batch, ceft_jax_batch_csr
    rng = np.random.default_rng(seed)
    comps, Ls, bws = _batch_inputs(g, 3, 4, rng)
    pad = ceft_jax_batch(g, comps, Ls, bws)
    csr = ceft_jax_batch_csr(g, comps, Ls, bws)
    for a, b, name in zip(pad, csr, ["ceft", "ptask", "pproc"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_batch_csr_paths_match_reference():
    """Each batched plane, finalized, backtracks the same critical path as
    Algorithm 1 run on that plane alone."""
    from repro.core.ceft_jax import ceft_batch_csr_results
    rng = np.random.default_rng(310)
    g = gaussian_elimination(5)
    B, P = 3, 3
    comps, Ls, bws = _batch_inputs(g, B, P, rng)
    results = ceft_batch_csr_results(g, comps, Ls, bws)
    from repro.core.machine import Machine
    for b in range(B):
        m = Machine(L=np.asarray(Ls[b], np.float64),
                    bw=np.asarray(bws[b], np.float64),
                    counts=np.ones(P, np.int64))
        ref = ceft_reference(g, np.asarray(comps[b], np.float64), m)
        assert results[b].path == ref.path
        assert results[b].cpl == pytest.approx(ref.cpl, rel=2e-5)


def test_csr_batch_segments_shared_structure():
    """The segment arrays are batch-invariant; cost planes stack to (B,v,P)
    float32 and shape mismatches are rejected."""
    from repro.core.taskgraph import csr_batch_segments
    rng = np.random.default_rng(311)
    g = linear_chain(10)
    planes = [rng.uniform(1, 10, (10, 2)) for _ in range(4)]
    segs, comps = csr_batch_segments(g, planes)
    single = csr_level_segments(g)
    np.testing.assert_array_equal(segs.task_ids, single.task_ids)
    np.testing.assert_array_equal(segs.edge_src, single.edge_src)
    assert comps.shape == (4, 10, 2) and comps.dtype == np.float32
    with pytest.raises(ValueError):
        csr_batch_segments(g, rng.uniform(1, 10, (4, 9, 2)))


# ------------------------------------------------------------------- bench JSON
def test_throughput_bench_emits_json_rows(monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_SCALE", "0.02")
    import io
    from contextlib import redirect_stdout
    from benchmarks import ceft_throughput
    rows: list = []
    buf = io.StringIO()
    with redirect_stdout(buf):
        ceft_throughput.run(json_rows=rows)
    impls = {r["impl"] for r in rows}
    assert {"reference", "vectorized", "jax_padded", "jax_csr"} <= impls
    assert any(r["bench"] == "ceft_irregular" for r in rows)
    for r in rows:
        assert r["ms"] > 0 and r["n"] > 0 and r["P"] > 0
    # CSV stays well-formed alongside the JSON mirror
    lines = buf.getvalue().strip().splitlines()
    header = lines[0].split(",")
    assert all(len(l.split(",")) == len(header) for l in lines[1:])
