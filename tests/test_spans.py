"""The planner call's spans (repro.core.spans) in a profiler trace: which
spans a call records, how they nest, what their stats count, and that
tracing leaves the results unchanged."""
import numpy as np

from repro.core.ceft_jax import (
    _fused_runs,
    _padded_sources,
    ceft_jax_csr,
    read_plans,
)
from repro.core.taskgraph import from_edge_arrays
from repro.graphs.rgg import rgg

N, P = 256, 8
SEG_TABLES = ("tasks", "edge_src", "edge_data", "edge_seg", "e_real")
DENSE_TABLES = ("tasks", "par", "pdata")


def _workload(seed=3):
    wl = rgg("high", N, P, np.random.default_rng(seed))
    g = wl.graph
    src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.cindptr))
    return (src, g.cindices, g.cdata), wl.comp, wl.machine


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2] and inner != outer


def _hand_counts(g):
    """(run table bytes + padded sources, edge slots, real edges, v_b) of a
    graph's fused runs, counted from their fields."""
    runs, v_b, _ = _fused_runs(g)
    nbytes = _padded_sources(g, v_b).nbytes
    slots = real = 0
    for r in runs:
        if hasattr(r, "par"):
            nbytes += sum(getattr(r, f).nbytes for f in DENSE_TABLES)
            slots += r.tasks.shape[0] * r.par.shape[1] * r.par.shape[2]
            real += int((r.par >= 0).sum())
        else:
            nbytes += sum(getattr(r, f).nbytes for f in SEG_TABLES)
            slots += r.tasks.shape[0] * r.edge_src.shape[1]
            real += int(r.e_real.sum())
    return nbytes, slots, real, v_b


def _two_calls(ceft_trace):
    (src, dst, data), comp, m = _workload()

    def calls():
        g = from_edge_arrays(N, src, dst, data)
        return g, ceft_jax_csr(g, comp, m), ceft_jax_csr(g, comp * 2, m)

    return ceft_trace(calls)


def test_two_calls_record_every_span_nested(ceft_trace):
    (g, _, _), spans = _two_calls(ceft_trace)
    names = [s[0] for s in spans]
    assert names == [
        "ceft.graph",
        # first call: the device state is built inside its ceft.state span
        "ceft.state", "ceft.levels", "ceft.fuse", "ceft.upload",
        "ceft.upload", "ceft.sweep", "ceft.wait", "ceft.readback",
        "ceft.finalize",
        # second call: the state is resident
        "ceft.state",
        "ceft.upload", "ceft.sweep", "ceft.wait", "ceft.readback",
        "ceft.finalize",
    ]
    state0, state1 = (s for s in spans if s[0] == "ceft.state")
    children = [s for s in spans if _inside(s, state0)]
    assert [s[0] for s in children] == ["ceft.levels", "ceft.fuse",
                                        "ceft.upload"]
    # every other span nests in no other ceft span, and a call's steps
    # follow one another
    top = [s for s in spans if s not in children]
    assert not any(_inside(a, b) for a in top for b in top)
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    assert not any(_inside(s, state1) for s in spans)


def test_span_stats_count_the_call(ceft_trace):
    (g, _, _), spans = _two_calls(ceft_trace)
    table_bytes, slots, real, v_b = _hand_counts(g)
    by_name = {}
    for name, _, _, stats in spans:
        by_name.setdefault(name, []).append(stats)
    assert by_name["ceft.graph"] == [{}]
    assert [st["hit"] for st in by_name["ceft.state"]] == [0, 1]
    cost_plane = (v_b + 1) * P * 4 + P * 4 + P * P * 4
    assert [st["bytes"] for st in by_name["ceft.upload"]] == [
        table_bytes, cost_plane, cost_plane]
    # three (v_b + 1, P) tables of four-byte entries come back per call
    assert [st["bytes"] for st in by_name["ceft.readback"]] == [
        3 * (v_b + 1) * P * 4] * 2
    # every graph edge below level 0 is relaxed once, in some slot
    assert real == g.cindices.size
    assert by_name["ceft.sweep"] == [
        {"edge_slots": slots, "real_edges": real}] * 2


def test_results_identical_with_profiler_on_and_off(ceft_trace):
    (src, dst, data), comp, m = _workload(seed=4)
    g = from_edge_arrays(N, src, dst, data)
    off = ceft_jax_csr(g, comp, m)
    on, spans = ceft_trace(lambda: ceft_jax_csr(g, comp, m))
    assert spans
    np.testing.assert_array_equal(on.ceft, off.ceft)
    np.testing.assert_array_equal(on.pred_task, off.pred_task)
    np.testing.assert_array_equal(on.pred_proc, off.pred_proc)
    assert (on.cpl, on.path) == (off.cpl, off.path)


def test_read_plans_finalizes_each_plane(ceft_trace):
    """One (v_b+1, P) carry gives one result; a batched carry one per
    plane, each equal to its own single-plane read."""
    import jax.numpy as jnp

    (src, dst, data), comp, m = _workload(seed=5)
    g = from_edge_arrays(N, src, dst, data)
    v_b = _fused_runs(g)[1]
    rng = np.random.default_rng(6)
    carries = [tuple(jnp.asarray(a) for a in (
        rng.uniform(1, 9, (v_b + 1, P)).astype(np.float32),
        rng.integers(-1, N, (v_b + 1, P)).astype(np.int32),
        rng.integers(-1, P, (v_b + 1, P)).astype(np.int32)))
        for _ in range(2)]
    singles = [read_plans(g, c) for c in carries]
    assert all(len(s) == 1 for s in singles)
    batched = tuple(jnp.stack(parts) for parts in zip(*carries))
    both, spans = ceft_trace(lambda: read_plans(g, batched))
    assert len(both) == 2
    for [one], got in zip(singles, both):
        np.testing.assert_array_equal(got.ceft, one.ceft)
        assert got.ceft.dtype == np.float64 and got.ceft.shape == (N, P)
        assert (got.sink, got.sink_proc, got.cpl) == (
            one.sink, one.sink_proc, one.cpl)
    assert [(s[0], s[3]) for s in spans] == [
        ("ceft.wait", {}),
        ("ceft.readback", {"bytes": 2 * 3 * (v_b + 1) * P * 4}),
        ("ceft.finalize", {})]


def test_batched_sweep_reads_back_inside_spans(ceft_trace):
    """``ceft_jax_batch_csr`` reads its tables through the same spans: the
    sweep counts each plane's edge work, the read-back every plane's bytes,
    and no finalize runs."""
    from repro.core.ceft_jax import ceft_jax_batch_csr

    (src, dst, data), comp, m = _workload(seed=8)
    g = from_edge_arrays(N, src, dst, data)
    _, slots, real, v_b = _hand_counts(g)
    B = 3
    comps = np.stack([comp * (1 + b) for b in range(B)])
    Ls = np.stack([m.L] * B)
    bws = np.stack([m.bw] * B)
    ceft_jax_batch_csr(g, comps, Ls, bws)  # device state resident
    out, spans = ceft_trace(lambda: ceft_jax_batch_csr(g, comps, Ls, bws))
    assert [o.shape for o in out] == [(B, N, P)] * 3
    assert [(s[0], s[3]) for s in spans] == [
        ("ceft.state", {"hit": 1}),
        ("ceft.upload", {"bytes": 4 * B * ((v_b + 1) * P + P + P * P)}),
        ("ceft.sweep", {"edge_slots": B * slots, "real_edges": B * real}),
        ("ceft.wait", {}),
        ("ceft.readback", {"bytes": 3 * B * (v_b + 1) * P * 4})]
