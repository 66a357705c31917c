"""The benchmark's yardstick: trace reduction, operation and byte counts."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_support  # noqa: E402,F401  (puts bench/ on the path)

from harness import common, counts, readers, trace, traffic  # noqa: E402

# device ops: busy [0,10) [20,30) [25,40) [60,70) -> union 10 + 20 + 10 = 40
OPS = [("fusion", 0, 10), ("dot", 20, 30), ("dot", 25, 40),
       ("copy", 60, 70)]
# host spans: plan [0, 35), client [35, 50), plan [50, 80), tick [90, 100)
SPANS = [("plan", 0, 35), ("client", 35, 50), ("plan", 50, 80),
         ("tick", 90, 100)]


def test_union_merges_overlaps():
    assert trace.union([(20, 30), (0, 10), (25, 40)]) == [(0, 10), (20, 40)]
    assert trace.length(trace.union([(s, e) for _, s, e in OPS])) == 40


def test_busy_and_idle_share():
    assert trace.busy_ns(OPS, 0, 100) == 40
    assert trace.busy_ns(OPS, 5, 65) == 5 + 20 + 5
    rec = {"trace": {"ops": OPS, "lo": 0, "hi": 100}}
    assert readers.idle_share(rec) == pytest.approx(60.0)


def test_device_time_inside_spans():
    # plan spans cover [0,35) and [50,80): busy 10 + 15 + 10 = 35
    assert trace.device_ns_in(OPS, SPANS, "plan") == 35
    assert trace.device_ns_in(OPS, SPANS, "client") == 5
    assert trace.device_ns_in(OPS, SPANS, "tick") == 0


def test_idle_split_by_span():
    # idle gaps in [0,100): [10,20) [40,60) [70,100) = 60 ns; plan holds
    # [10,20) [50,60) [70,80), client [40,50), tick [90,100), none [80,90)
    got = dict(trace.idle_by_span(OPS, SPANS, 0, 100))
    assert got == pytest.approx({"plan": 30e-9, "client": 10e-9,
                                 "tick": 10e-9, "none": 10e-9})
    assert sum(got.values()) == pytest.approx(60e-9)


def test_top_ops_and_modules():
    assert trace.top_ops(OPS, 2) == [["dot", 25e-9], ["fusion", 10e-9]]
    mods = [("jit_decode", 0, 30), ("jit_prefill", 30, 40),
            ("jit_decode", 50, 80)]
    assert trace.count_modules(mods, "jit_decode") == (2, 60)


def test_percentile_matches_statistics():
    import statistics

    vals = [float(v) for v in range(1, 38)]
    for i in (50, 90, 95):
        want = statistics.quantiles(vals, n=100)[i - 1]
        assert common.percentile(vals, i) == pytest.approx(want)
    # a request that never completed sits at infinity and can set the tail
    assert common.percentile([1.0] * 9 + [math.inf] * 3, 90) == math.inf
    assert common.percentile([1.0] * 30 + [math.inf], 90) == 1.0


def test_ceft_counts_by_hand():
    # 10 edges, 2 classes: 10*2*2 candidates x 4 ops + 10*2 comparisons
    assert counts.ceft_ops(10, 2) == 180
    # n=3, e=2, P=2: in 24 (comp) + 24 (edges) + 24 (L, bw); out 72
    assert counts.ceft_bytes(3, 2, 2) == 144


def test_least_seconds_picks_the_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000, 10, peak) == (10.0, "compute")
    assert counts.least_seconds(100, 50, peak) == (5.0, "memory")


CHAT = {"rate_per_s": 1.1, "tenants": {"a": 0.5, "b": 0.25, "c": 0.25},
        "prompt_len": {"128": 0.75, "192": 0.25},
        "max_new": {"16": 0.75, "64": 0.25}}


def test_exact_counts_by_largest_remainder():
    # 27.5, 13.75, 13.75: the two spare go to the largest remainders
    assert traffic.exact_counts({"a": 0.5, "b": 0.25, "c": 0.25}, 55) == {
        "a": 27, "b": 14, "c": 14}
    assert sum(traffic.exact_counts({"x": 1, "y": 2}, 7).values()) == 7


def test_open_loop_is_fixed_by_the_seed():
    a = traffic.open_loop(CHAT, 50, 1000, 3_000_000_017)
    b = traffic.open_loop(CHAT, 50, 1000, 3_000_000_017)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 5, 7)])
def test_every_seed_offers_the_same_work(seeds):
    """Two seeds give the same sizes, tenants and gaps, in other orders."""
    runs = [traffic.open_loop(CHAT, 50, 1000, s) for s in seeds]
    for key in ("tenant", "max_new"):
        assert sorted(r[key] for r in runs[0]) == sorted(r[key]
                                                         for r in runs[1])
    assert sorted(r["prompt"].size for r in runs[0]) == sorted(
        r["prompt"].size for r in runs[1])
    assert len(runs[0]) == round(1.1 * 50)
    dues = [np.array([r["due"] for r in run]) for run in runs]
    assert all((0 <= d).all() and (d < 50).all() and (np.diff(d) >= 0).all()
               for d in dues)
    assert not np.allclose(dues[0], dues[1])
    gaps = [np.sort(np.diff(np.concatenate([[0.0], d, [50.0]])))
            for d in dues]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)


def test_pattern_seed_fixes_the_arrangement_not_the_prompts():
    """With ``pattern_seed`` every run seed replays one arrangement of
    arrival times, tenants and sizes; the prompts' tokens still follow the
    run's seed."""
    fixed = dict(CHAT, pattern_seed=0)
    a, b = (traffic.open_loop(fixed, 50, 1000, s) for s in (1, 2**31 + 5))
    for key in ("due", "tenant", "max_new"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [r["prompt"].size for r in a] == [r["prompt"].size for r in b]
    assert not all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # the arrangement is the one a run of the pattern's own seed draws
    plain = traffic.open_loop(CHAT, 50, 1000, 0)
    assert [r["due"] for r in a] == [r["due"] for r in plain]


def test_shapes_cover_every_batch_and_size():
    assert sorted(traffic.shapes(CHAT, 2)) == sorted(
        (b, p, m) for b in (1, 2) for p in (128, 192) for m in (16, 64))


CFG = {"d_model": 4, "d_ff": 6, "vocab": 10, "n_layers": 2, "n_heads": 2,
       "n_kv_heads": 1, "head_dim": 2, "tie_embeddings": True}


def test_dense_lm_counts_by_hand():
    p = counts.dense_lm_params(CFG)
    # attention 4*2*(2+2) + 2*2*4 = 48; mlp 3*4*6 = 72; norms 8
    assert p["per_layer"] == 128
    assert p["total"] == 2 * 128 + 40 + 4
    # one token at context 3 with logits: 2*(2*(128-8)) mats + 4*3*2*2*2
    # attention + 2*10*4 head
    assert counts.dense_lm_token_ops(CFG, 3, True) == 480 + 96 + 80
    # decode at batch 2, cache 5: weights at 2 bytes + 2 * (2*2*5*1*2*2)
    assert counts.dense_lm_decode_bytes(CFG, 2, 5) == 300 * 2 + 2 * 80
    # prompt 2, 2 new: tokens at context 1 (no logits), 2, 3 (logits)
    assert counts.dense_lm_request_ops(CFG, 2, 2) == (
        counts.dense_lm_token_ops(CFG, 1, False)
        + counts.dense_lm_token_ops(CFG, 2, True)
        + counts.dense_lm_token_ops(CFG, 3, True))


def test_serving_readers_by_hand():
    """Due-to-done tails count a request never completed as infinite;
    tokens per second take every completed request of the window over the
    time until the last of them completed."""
    def req(due, done, new, rejected=False):
        return {"due": due, "done": done, "max_new": new,
                "rejected": rejected}
    recs = [req(0.0, 1.0, 16), req(1.0, 4.0, 64), req(2.0, 12.0, 16),
            req(9.0, None, 64), req(9.5, 9.9, 16, rejected=True)]
    rec = {"requests": recs, "window_s": 10.0}
    assert readers.latencies_s(rec)[:3] == [1.0, 3.0, 10.0]
    assert readers.latencies_s(rec)[3:] == [math.inf, math.inf]
    assert readers.req_p90_ms(rec) == math.inf
    # 16 + 64 + 16 tokens over the 12 s until the last completion
    assert readers.tokens_per_s(rec) == pytest.approx(96 / 12.0)
    # all done inside the window: the window's length is the time
    rec = {"requests": recs[:2], "window_s": 10.0}
    assert readers.tokens_per_s(rec) == pytest.approx(80 / 10.0)
    import statistics

    assert readers.req_p90_ms(rec) == pytest.approx(
        1e3 * statistics.quantiles([1.0, 3.0], n=100)[89])
    assert readers.req_p50_ms(rec) == pytest.approx(2000.0)


def test_reduce_counts_only_planes_that_ran_operations():
    """A TPU trace's planes, as the chip writes them: one TPU with its
    operation and program lines, an empty custom device plane, and the host
    with the benchmark's spans.  Busy time is averaged over one device."""
    from types import SimpleNamespace as NS

    def line(name, *events):
        return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                     for n, s, d in events])
    planes = [
        NS(name="/device:TPU:0", lines=[
            line("XLA Modules", ("jit_decode(17)", 0, 30)),
            line("XLA Ops", ("convert", 0, 20), ("while", 20, 10)),
            line("TC Overlay")]),
        NS(name="/device:CUSTOM:Megascale Trace", lines=[]),
        NS(name="/host:CPU", lines=[
            line("", ("tick", 0, 40), ("other", 5, 1))])]
    tr = trace.reduce(planes, ("tick",))
    assert tr["n_devices"] == 1
    assert tr["ops"] == [("convert", 0, 20), ("while", 20, 30)]
    assert tr["modules"] == [("jit_decode(17)", 0, 30)]
    assert tr["spans"] == [("tick", 0, 40)]
    assert trace.count_modules(tr["modules"], "jit_decode") == (1, 30)


@pytest.mark.parametrize("background", [False, True])
def test_tracer_keeps_the_spans_of_its_window(tmp_path, background):
    """The trace holds the spans from its start to its stop, whether the
    stop writes it in place or on a thread that ``wait`` joins."""
    import jax.numpy as jnp

    tracer = common.Tracer("test", 1.0)
    tracer.dir = tmp_path / "trace"
    spans = common.Spans()
    tracer.start()
    for _ in range(2):
        with spans("tick"):
            jnp.ones(8).sum().block_until_ready()
    tracer.stop(background=background)
    with spans("tick"):
        jnp.ones(8).sum().block_until_ready()
    tracer.wait()
    assert len(trace.load(str(tracer.dir), ("tick",))["spans"]) == 2
    assert len(spans.items) == 3
