"""The reduction of the program's own ceft.* spans to per-layer metrics,
on synthetic traces (plus one real profiler trace on the CPU)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_support  # noqa: E402,F401  (puts bench/ on the path)

from harness import program_spans as ps  # noqa: E402

# the benchmark's own spans: two plans around a client step
BENCH_SPANS = [("plan", 0, 100), ("client", 100, 200), ("plan", 200, 300)]
# device busy: the first plan's sweep, a copy in the client step, the
# second plan's sweep
OPS = [("while", 46, 78), ("copy", 120, 130), ("while", 211, 258)]
PROGRAM = [
    # plan 1: a graph never seen before (device-state miss)
    ("plan", 0, 100, {}),
    ("ceft.graph", 2, 12, {}),
    ("ceft.state", 14, 40, {"hit": 0}),
    ("ceft.levels", 15, 20, {}),
    ("ceft.fuse", 20, 30, {}),
    ("ceft.upload", 31, 39, {"bytes": 100}),
    ("ceft.upload", 41, 45, {"bytes": 10}),
    ("ceft.sweep", 46, 50, {"edge_slots": 400, "real_edges": 100}),
    ("ceft.wait", 50, 80, {}),
    ("ceft.readback", 81, 90, {"bytes": 30}),
    ("ceft.finalize", 90, 95, {}),
    # outside every plan span: not the planner call's, so never counted
    ("ceft.upload", 150, 160, {"bytes": 10}),
    ("ceft.sweep", 160, 170, {"edge_slots": 10, "real_edges": 10}),
    # plan 2: the resident graph re-planned (device-state hit)
    ("plan", 200, 300, {}),
    ("ceft.state", 202, 204, {"hit": 1}),
    ("ceft.upload", 205, 210, {"bytes": 10}),
    ("ceft.sweep", 210, 212, {"edge_slots": 360, "real_edges": 100}),
    ("ceft.wait", 212, 260, {}),
    ("ceft.readback", 262, 277, {"bytes": 30}),
    ("ceft.finalize", 277, 290, {}),
]


def record(spans=BENCH_SPANS):
    return {"trace": {"ops": OPS, "spans": spans, "lo": 0, "hi": 300}}


@pytest.fixture
def traced(monkeypatch):
    """The newest trace holds ``PROGRAM`` (or what the test puts there)."""
    held = {"spans": tuple(PROGRAM)}
    monkeypatch.setattr(ps, "load", lambda trace_dir: held["spans"])
    return held


def test_self_time_subtracts_nested_children():
    _, _, mine = ps.inside_plans(PROGRAM)
    # ceft.state [14, 40) holds levels, fuse and upload: 26 - (5 + 10 + 8);
    # the hit at [202, 204) holds nothing
    assert ps.self_ns(mine, {"ceft.state"}) == 3 + 2
    # a grandchild inside a child is not subtracted twice, and a child that
    # overlaps another still counts once
    nested = [("a", 0, 100, {}), ("b", 10, 50, {}), ("c", 20, 30, {}),
              ("d", 40, 60, {})]
    assert ps.self_ns(nested, {"a"}) == 100 - 50
    assert ps.self_ns(nested, {"b"}) == 40 - 10
    assert ps.self_ns(nested, {"c", "d"}) == 10 + 20


def test_only_spans_inside_plans_count():
    plans, n, mine = ps.inside_plans(PROGRAM)
    assert plans == [(0, 100), (200, 300)] and n == 2
    assert len(mine) == len(PROGRAM) - 4
    assert all(not (100 <= s < 200) for _, s, _, _ in mine)


def test_per_plan_division(traced):
    rec = record()
    # graph 10 + levels 5 + fuse 10 over two plans, in ms
    assert ps.host_prep_ms(rec) == pytest.approx(25 / 2 / 1e6)
    # uploads 8 + 4 + 5 (the one in the client step left out)
    assert ps.upload_ms(rec) == pytest.approx(17 / 2 / 1e6)
    # read-back and finalize, 9 + 5 and 15 + 13
    assert ps.readback_ms(rec) == pytest.approx((14 + 28) / 2 / 1e6)


def test_rates_from_byte_stats(traced):
    # 100 + 10 + 10 bytes staged in 8 + 4 + 5 ns of upload self time; 30 +
    # 30 bytes read back in 9 + 15 ns (finalize is not part of the copy)
    assert ps.upload_gbps(record()) == pytest.approx(120 / 17)
    assert ps.readback_gbps(record()) == pytest.approx(60 / 24)


def test_state_rebuild_share_from_hits(traced):
    assert ps.state_rebuild_share(record()) == pytest.approx(50.0)
    traced["spans"] = tuple(s for s in PROGRAM if s[1] >= 200)
    assert ps.state_rebuild_share(record([("plan", 200, 300)])) == 0.0


def test_host_prep_reads_zero_on_a_resident_graph(traced):
    traced["spans"] = tuple(s for s in PROGRAM if s[1] >= 200)
    rec = record([("plan", 200, 300)])
    assert ps.host_prep_ms(rec) == 0.0
    assert ps.upload_ms(rec) == pytest.approx(5 / 1e6)


def test_useful_share_from_sweep_stats(traced):
    # (100 + 100) real edges over (400 + 360) slots; the sweep outside the
    # plans is left out
    assert ps.sweep_useful_share(record()) == pytest.approx(
        100 * 200 / 760)


def test_unattributed_idle_inside_between_and_outside_spans(traced):
    # idle in plan 1: 100 - 32 busy = 68, of which [0,2) [12,14) [40,41)
    # [45,46) [80,81) [95,100) = 12 lie between program spans; plan 2: 53
    # idle, [200,202) [204,205) [260,262) [290,300) = 15 between.  Idle in
    # the client step lies outside every plan and counts for neither.
    assert ps.idle_unattributed(record()) == pytest.approx(
        100 * (12 + 15) / (68 + 53))


def test_idle_inside_waits_per_plan(traced):
    # waits [50, 80) and [212, 260) hold the idle [78, 80) and [258, 260)
    assert ps.idle_in_wait_ms(record()) == pytest.approx((2 + 2) / 2 / 1e6)
    # a wait clipped by the traced window counts only its part inside
    rec = record()
    rec["trace"]["hi"] = 259
    assert ps.idle_in_wait_ms(rec) == pytest.approx((2 + 1) / 2 / 1e6)


def test_nothing_to_read(traced):
    # an untraced run
    assert ps.host_prep_ms({"trace": None}) is None
    # another run's trace: plan spans differ in count or first start
    for spans in ([("plan", 0, 100)], [("plan", 1, 100), ("plan", 200, 300)]):
        rec = record(spans)
        assert ps.spans_of(rec) is None
        assert ps.sweep_useful_share(rec) is None
        assert ps.idle_unattributed(rec) is None
    # a program that records no ceft.* span (this run's trace all the same)
    traced["spans"] = tuple(s for s in PROGRAM if s[0] == "plan")
    rec = record()
    assert ps.spans_of(rec) is not None
    for read in (ps.host_prep_ms, ps.upload_ms, ps.readback_ms,
                 ps.sweep_useful_share, ps.idle_unattributed,
                 ps.upload_gbps, ps.readback_gbps, ps.state_rebuild_share,
                 ps.idle_in_wait_ms):
        assert read(rec) is None
    # no trace file at all
    traced["spans"] = None
    assert ps.upload_ms(record()) is None


def test_load_reads_names_times_and_stats(tmp_path):
    """A real profiler trace: the plan span and the program's spans come back
    with their integer stats, and nested spans lie inside their parent."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("plan"):
            with jax.profiler.TraceAnnotation("ceft.state", hit=0):
                with jax.profiler.TraceAnnotation("ceft.upload", bytes=4096):
                    jax.numpy.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("other"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = {n: (s, e, st) for n, s, e, st in ps.load(tmp_path)}
    assert set(got) == {"plan", "ceft.state", "ceft.upload"}
    assert got["ceft.state"][2] == {"hit": 0}
    assert got["ceft.upload"][2] == {"bytes": 4096}
    (ps0, pe0, _), (ss, se, _) = got["plan"], got["ceft.state"]
    assert ps0 <= ss <= got["ceft.upload"][0] <= got["ceft.upload"][1] \
        <= se <= pe0
