"""The placement plane (ISSUE 7): EnginePool lifecycle, measured comm plane,
bit-identity of pool-routed plans to the direct-engine Router, failure as
degradation (worker loss -> degraded column -> failover re-plan), autoscale,
and the subprocess worker backend."""
import os
import signal
import time

import numpy as np
import pytest

from conftest import run_isolated_script
from repro.core.ceft_jax import plan_request_dag
from repro.sched.straggler import LOST_SLOWDOWN, EwmaCostTable, StragglerMonitor
from repro.serve import (
    EnginePool,
    EngineSlot,
    Request,
    Router,
    ServeConfig,
    WorkerLost,
    WorkerSpec,
    router_machine,
)


class FakeEngine:
    def __init__(self):
        self.calls = []

    def generate(self, prompts, scfg):
        B, P = prompts.shape
        self.calls.append((B, P))
        return np.full((B, P + scfg.max_new_tokens), 7, np.int32)


class DyingEngine(FakeEngine):
    """Serves ``survive`` calls, then dies like a crashed worker process."""

    def __init__(self, name, index, survive=0):
        super().__init__()
        self.name, self.index, self.survive = name, index, survive

    def generate(self, prompts, scfg):
        if len(self.calls) >= self.survive:
            raise WorkerLost(self.name, self.index, "killed under load")
        return super().generate(prompts, scfg)


def _slots(P, engine_cls=FakeEngine):
    return [EngineSlot(f"e{i}", engine_cls(), "baseline") for i in range(P)]


def _submit_mixed(router, rng, per_class=4, classes=(8, 16), max_new=4):
    for t, plen in enumerate(classes):
        for _ in range(per_class):
            prompt = rng.integers(2, 100, plen).astype(np.int32)
            assert router.submit(Request(f"t{t}", prompt, max_new))


def _seed_rates(router, rng, classes=((8, 4), (16, 4)), P=2):
    for wc in classes:
        for e in range(P):
            router.costs.update(wc, e, float(rng.uniform(0.5e-3, 3e-3)))


# -------------------------------------------------------------- static plane
def test_from_slots_static_machine_matches_proxy():
    """The compat path keeps PR 5's proxy plane byte-for-byte: a fixed
    snapshot over from_slots equals router_machine exactly."""
    pool = EnginePool.from_slots(_slots(3))
    proxy = router_machine(3)
    m = pool.machine()
    assert np.array_equal(m.L, proxy.L)
    assert np.array_equal(m.bw, proxy.bw)
    assert np.array_equal(m.counts, proxy.counts)
    assert pool.machine() is m          # snapshot is cached, not rebuilt


def test_pool_routed_plans_bit_identical_to_direct_router():
    """Acceptance (ISSUE 7): for a fixed pool snapshot, plans routed through
    EnginePool are bit-identical to the direct-engine Router — same dispatch
    decisions, same swept plan, and both equal the unbatched reference sweep
    on the router's own DAG."""
    results = []
    for wrap in (False, True):
        slots = _slots(2)
        router = Router(EnginePool.from_slots(slots) if wrap else slots)
        rng = np.random.default_rng(11)
        _seed_rates(router, rng)
        _submit_mixed(router, rng)
        ds = router.tick()
        results.append((router, [(d.engine, d.wclass, len(d.requests),
                                  d.on_critical_path) for d in ds]))
    (r_direct, seq_direct), (r_pool, seq_pool) = results
    assert seq_direct == seq_pool
    assert np.array_equal(r_direct.last_plan.ceft, r_pool.last_plan.ceft)
    assert r_direct.last_plan.path == r_pool.last_plan.path
    n, src, dst, data, comp = r_pool.last_dag
    ref = plan_request_dag(n, src, dst, data, comp, r_pool.machine)
    assert np.array_equal(r_pool.last_plan.ceft, ref.ceft)
    assert r_pool.last_plan.path == ref.path


# ------------------------------------------------------------ measured plane
def test_measured_probes_build_quantized_machine():
    """Injected deterministic probes: class-pair bandwidth composes the two
    measured legs (parent-relayed handoff) and lands on the sqrt2 grid; the
    snapshot object is stable until a measurement crosses a bucket."""
    legs = {0: 2.0 ** 18, 1: 2.0 ** 18}   # tokens/s per worker leg

    def probe(member, payload):
        i = int(member.spec.name[1:])
        return (len(payload) // 4) / legs[i]

    pool = EnginePool([WorkerSpec(f"e{i}", engine=FakeEngine())
                       for i in range(2)], probe=probe, bw_alpha=1.0)
    pool.refresh_probes()
    m1 = pool.machine()
    # pair rate = 1/(1/2^18 + 1/2^18) = 2^17, exactly on the grid
    assert m1.bw[0, 1] == pytest.approx(2.0 ** 17)
    assert m1.bw[1, 0] == pytest.approx(2.0 ** 17)
    # re-probing identical legs keeps the SAME snapshot object
    pool.refresh_probes()
    assert pool.machine() is m1
    # a 4x faster leg crosses the quantization bucket: new snapshot, and
    # listeners get the superseded one (the plan-cache invalidation hook)
    events = []
    pool.add_listener(lambda ev, payload: events.append((ev, payload)))
    legs[1] = 2.0 ** 20
    pool.refresh_probes()
    m2 = pool.machine()
    assert m2 is not m1
    assert m2.bw[0, 1] > m1.bw[0, 1]
    assert ("machine", m1) in events


def test_measured_probe_delta_triggers_router_replan():
    """A comm-plane delta that moves the Machine snapshot must invalidate the
    cached plan (machine-fingerprint scope) and force a re-plan on the next
    tick — stale-machine plans may never short-circuit."""
    legs = {0: 2.0 ** 18, 1: 2.0 ** 18}

    def probe(member, payload):
        return (len(payload) // 4) / legs[int(member.spec.name[1:])]

    pool = EnginePool([WorkerSpec(f"e{i}", engine=FakeEngine())
                       for i in range(2)], probe=probe, bw_alpha=1.0)
    pool.refresh_probes()
    router = Router(pool)
    rng = np.random.default_rng(12)
    _seed_rates(router, rng)
    _submit_mixed(router, rng)
    router.tick()
    assert router.stats["plans"] == 1
    # steady state: same mix, unchanged plane -> cache hit, no new plan
    _submit_mixed(router, rng)
    router.tick()
    assert router.stats["plans"] == 1 and router.stats["cache_hits"] >= 1
    # the measured plane moves a bucket: the next tick must re-plan
    legs[0] = 2.0 ** 22
    pool.refresh_probes()
    inv_before = router.stats["invalidations"]
    _submit_mixed(router, rng)
    router.tick()
    assert router.stats["plans"] == 2
    assert router.stats["invalidations"] > inv_before


def test_topology_reported_through_substrate_seam():
    from repro.substrate import process_topology

    pool = EnginePool.from_slots(_slots(2))
    topo = pool.topology()
    here = process_topology()
    assert len(topo) == 2
    for t in topo:
        assert t["host"] == here["host"] and t["pid"] == os.getpid()


def test_topology_raises_on_backend_failure(monkeypatch):
    """A backend that fails to initialize surfaces from the topology probe
    instead of being reported as a device-less process."""
    import jax

    from repro.substrate import process_topology

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        process_topology()


# -------------------------------------------------------- failure semantics
def test_worker_loss_degrades_column_and_fails_over():
    """Acceptance (ISSUE 7): killing a worker under load completes the
    in-flight workload via the degraded-plane re-plan — the lost worker's
    pending requests requeue, its class column goes fully degraded, and the
    survivors serve everything; the loss carries per-engine context."""
    slots = [EngineSlot("e0", FakeEngine(), "baseline"),
             EngineSlot("e1", DyingEngine("e1", 1, survive=1), "baseline")]
    router = Router(slots, max_batch=1)   # one request per dispatch
    rng = np.random.default_rng(13)
    # e1 is the cheap engine: the single-class critical path pins to it, so
    # the whole workload is genuinely in flight on the worker that dies
    router.costs.update((16, 4), 0, 2e-3)
    router.costs.update((16, 4), 1, 1e-3)
    for _ in range(4):
        router.submit(Request("t", rng.integers(2, 100, 16).astype(np.int32), 4))
    done = router.serve()
    assert len(done) == 4, "in-flight workload must complete on survivors"
    # e1 finished exactly one dispatch before dying; that result was KEPT
    # and the survivor served the three requeued requests
    assert len(slots[1].engine.calls) == 1
    assert len(slots[0].engine.calls) == 3
    assert router.pool.state(1) == "lost"
    assert [name for name, _ in router.failures] == ["e1"]
    (name, err), = router.failures
    assert isinstance(err, WorkerLost) and err.index == 1
    assert "e1" in str(err) and "killed under load" in str(err)
    assert router.stats["requeued"] > 0
    # the lost column is fully degraded -> degraded-plane re-plans fired
    assert router._slow[1] >= LOST_SLOWDOWN
    assert router.stats["degraded_plans"] >= 1
    # and the next planned tick maps the critical path off the lost worker
    _submit_mixed(router, rng, per_class=2)
    ds = router.tick()
    assert ds and all(d.engine == 0 for d in ds)
    assert set(dict(router.last_plan.path).values()) == {0}


def test_all_workers_lost_raises_with_context():
    slots = [EngineSlot(f"e{i}", DyingEngine(f"e{i}", i, survive=0), "baseline")
             for i in range(2)]
    router = Router(slots)
    rng = np.random.default_rng(14)
    _seed_rates(router, rng)
    _submit_mixed(router, rng, per_class=2)
    with pytest.raises(RuntimeError, match="no live pool workers") as ei:
        router.serve()
    assert {name for name, _ in ei.value.failures} == {"e0", "e1"}


def test_generate_on_lost_worker_raises_worker_lost():
    pool = EnginePool.from_slots(_slots(2))
    pool.mark_lost(1)
    with pytest.raises(WorkerLost, match="e1"):
        pool.generate(1, np.zeros((1, 4), np.int32), ServeConfig(max_new_tokens=2))
    # index 0 still serves
    out = pool.generate(0, np.zeros((1, 4), np.int32), ServeConfig(max_new_tokens=2))
    assert out.shape == (1, 6)


def test_launch_revives_freed_slot_in_place():
    """Lost/drained workers keep their class column; a launch reuses the
    freed slot (index-stable columns) and revives the straggler column."""
    pool = EnginePool.from_slots(_slots(3))
    router = Router(pool)
    pool.mark_lost(1)
    assert router._slow[1] >= LOST_SLOWDOWN       # listener degraded it
    assert pool.size == 3 and pool.live_indices() == [0, 2]
    idx = pool.launch(WorkerSpec("e1b", engine=FakeEngine()))
    assert idx == 1 and pool.live_indices() == [0, 1, 2]
    assert pool.slots[1].name == "e1b"
    router._sync_pool()
    assert router._slow[1] == 1.0                 # revived column is nominal
    assert pool.machine().P == 3


# ---------------------------------------------------------------- autoscale
def test_autoscale_scales_out_and_drains_on_queue_depth():
    pool = EnginePool([WorkerSpec("e0", engine=FakeEngine())],
                      autoscale=True, max_size=3, high_water=4, low_water=0)
    events = []
    pool.add_listener(lambda ev, payload: events.append((ev, payload)))
    assert pool.maybe_autoscale(40) == "out"
    assert pool.maybe_autoscale(40) == "out"
    assert pool.maybe_autoscale(40) is None       # at max_size
    assert len(pool.live_indices()) == 3
    assert pool.machine().P == 3
    assert pool.stats["scale_out"] == 2
    # backlog gone: autoscaled workers drain back to min_size, last first
    assert pool.maybe_autoscale(0) == "in"
    assert pool.maybe_autoscale(0) == "in"
    assert pool.maybe_autoscale(0) is None        # at min_size
    assert len(pool.live_indices()) == 1
    assert [e for e, _ in events].count("launch") == 2
    assert [e for e, _ in events].count("drain") == 2


def test_router_tick_drives_autoscale():
    pool = EnginePool([WorkerSpec("e0", engine=FakeEngine())],
                      autoscale=True, max_size=2, high_water=2, low_water=0)
    router = Router(pool)
    rng = np.random.default_rng(15)
    _submit_mixed(router, rng, per_class=8)       # 16 pending > high_water
    router.tick()
    assert len(pool.live_indices()) == 2
    assert router.costs.n_classes == 2            # cost table grew with P


# ------------------------------------- straggler/cost-table elastic (bugfix)
def test_straggler_report_for_unseen_engine_registers_degraded_column():
    """Regression (ISSUE 7): a slowdown report for an engine the monitor has
    never seen (just-launched / just-lost worker) must register a degraded
    column instead of raising."""
    mon = StragglerMonitor(2, threshold=1.3)
    mon.observe(np.ones(2))
    slow = mon.report(4, 3.0)                     # index 4 never seen
    assert len(slow) == 5 and slow[4] == pytest.approx(3.0)
    assert slow[0] == 1.0 and slow[1] == 1.0      # existing columns untouched
    slow = mon.mark_lost(7)                       # loss of an unseen worker
    assert len(slow) == 8 and slow[7] >= LOST_SLOWDOWN
    # observing a prefix keeps the wider columns' estimates
    slow = mon.observe(np.asarray([1.0, 1.0]))
    assert len(slow) == 8 and slow[7] >= LOST_SLOWDOWN
    assert slow[4] == pytest.approx(3.0)


def test_cost_table_update_for_unseen_engine_grows_rows():
    """Regression (ISSUE 7): a measured rate for an engine index beyond the
    table's width (a just-launched worker) widens every row instead of
    raising IndexError."""
    t = EwmaCostTable(2, default=1e-3)
    t.update((8, 4), 0, 2e-3)
    t.update((8, 4), 5, 4e-3)                     # engine 5 never existed
    assert t.n_classes == 6
    row = t.row((8, 4))
    assert len(row) == 6
    assert row[0] == pytest.approx(2e-3) and row[5] == pytest.approx(4e-3)
    # pre-existing rows widened too: unobserved tail falls back to row mean
    assert np.isfinite(t.row((8, 4))).all()
    t2 = EwmaCostTable(2)
    t2.update((1, 1), 1, 1.0)
    t2.ensure_classes(4)
    assert len(t2.row((1, 1))) == 4


def test_cost_table_reset_class_forgets_one_column():
    t = EwmaCostTable(2, default=1e-3)
    t.update((8, 4), 0, 2e-3)
    t.update((8, 4), 1, 8e-3)
    t.reset_class(1)
    row = t.row((8, 4))
    assert row[0] == pytest.approx(2e-3)
    assert row[1] == pytest.approx(2e-3)          # falls back to observed mean


# --------------------------------------------------------- subprocess backend
def test_subprocess_worker_roundtrip_and_measured_plane():
    pool = EnginePool(
        [WorkerSpec("w0", factory="repro.serve.pool:null_engine_factory",
                    backend="subprocess")], probe="measure")
    try:
        out = pool.generate(0, np.ones((2, 4), np.int32),
                            ServeConfig(max_new_tokens=3))
        assert out.shape == (2, 7) and (out == 0).all()
        # the child reports its own process identity through the seam
        topo = pool.topology()[0]
        assert topo["pid"] != os.getpid()
        pool.refresh_probes()
        m = pool.machine()
        assert np.isfinite(m.bw).all() and (m.bw > 0).all()
        assert pool.stats["probes"] >= 1
    finally:
        pool.close()


def test_subprocess_backend_refuses_accelerator_parent(monkeypatch):
    """A parent on an accelerator must not start JAX-loading children that
    would race it for the chip: the pool refuses before spawning any."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="inproc backend"):
        EnginePool([WorkerSpec("w0", factory="repro.serve.pool:null_engine_factory",
                               backend="subprocess")])


def test_subprocess_worker_death_surfaces_as_worker_lost():
    pool = EnginePool(
        [WorkerSpec("w0", factory="repro.serve.pool:null_engine_factory",
                    backend="subprocess")])
    pid = pool.worker_pid(0)
    assert pid is not None
    os.kill(pid, signal.SIGKILL)
    time.sleep(0.2)
    with pytest.raises(WorkerLost, match="w0"):
        pool.generate(0, np.ones((1, 4), np.int32),
                      ServeConfig(max_new_tokens=2))
    assert pool.state(0) == "lost"
    assert pool.stats["lost"] == 1


POOL_E2E = """
    import numpy as np
    from repro.serve import EnginePool, Request, Router, WorkerSpec

    specs = [WorkerSpec(f"w{i}", factory="repro.serve.pool:null_engine_factory",
                        backend="subprocess") for i in range(2)]
    pool = EnginePool(specs, probe="measure")
    pool.refresh_probes()
    router = Router(pool)
    rng = np.random.default_rng(0)
    for plen in (8, 16):
        for _ in range(3):
            router.submit(Request("t", rng.integers(2, 100, plen).astype(np.int32), 4))
    done = router.serve()
    assert len(done) == 6, len(done)
    assert router.stats["plans"] >= 1
    pool.close()
    assert pool.live_indices() == []
    print("POOL_OK")
"""


def test_subprocess_pool_end_to_end():
    """Two subprocess workers behind the Router, probed comm plane, full
    serve loop — run through the shared isolated-script bootstrap (the same
    helper the elastic-reshard test uses)."""
    run_isolated_script(POOL_E2E, marker="POOL_OK", timeout=300)


# ------------------------------------------- shutdown + protocol (ISSUE 8)
def test_close_escalates_sigkill_on_stopped_child_and_reaps():
    """Regression (ISSUE 8 satellite): close() on a SIGSTOP'd child must
    escalate to SIGKILL, reap the process (no zombie) and close both pipe
    fds — a hung worker cannot leak across drain+relaunch cycles."""
    pool = EnginePool(
        [WorkerSpec("w0", factory="repro.serve.pool:null_engine_factory",
                    backend="subprocess")])
    handle = pool._members[0].handle
    handle.close_timeout = 0.3          # keep the graceful grace short
    pid = pool.worker_pid(0)
    os.kill(pid, signal.SIGSTOP)        # the child can never reply or exit
    t0 = time.monotonic()
    pool.drain(0)                       # -> handle.close()
    assert time.monotonic() - t0 < 5.0, "close blocked on a stopped child"
    assert handle.proc.returncode is not None, "child was not reaped"
    assert handle.proc.returncode < 0   # killed by signal, not clean exit
    assert handle.proc.stdin.closed and handle.proc.stdout.closed
    # reaped: the pid no longer exists (or is at worst a different process)
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)


def test_recv_msg_rejects_malformed_frames():
    """Satellite (ISSUE 8): garbage fed straight into the framing layer must
    surface as typed errors (FrameError / EOFError), never a hang or a
    misparse."""
    import io

    from repro.serve.pool import FrameError, _recv_msg, _send_msg

    # short header -> EOF
    with pytest.raises(EOFError, match="pipe closed"):
        _recv_msg(io.BytesIO(b"\x01\x02"))
    # absurd length header (random corrupt bytes) -> FrameError via the cap
    with pytest.raises(FrameError, match="exceeds cap"):
        _recv_msg(io.BytesIO(b"\xde\xad\xbe\xef\xde\xad\xbe\xef"))
    # valid header, truncated body -> EOF with byte counts
    import struct
    with pytest.raises(EOFError, match="truncated frame: 3/9"):
        _recv_msg(io.BytesIO(struct.pack("<Q", 9) + b"abc"))
    # full-length garbage payload -> FrameError, not a raw pickle error
    with pytest.raises(FrameError, match="corrupt frame payload"):
        _recv_msg(io.BytesIO(struct.pack("<Q", 4) + b"\x00\x01\x02\x03"))
    # a well-formed frame still round-trips
    buf = io.BytesIO()
    _send_msg(buf, ("ok", 42))
    buf.seek(0)
    assert _recv_msg(buf) == ("ok", 42)


def test_corrupt_stream_surfaces_as_worker_lost_with_context():
    """Satellite (ISSUE 8): a corrupt protocol stream (garbage written into
    the live pipe) surfaces as WorkerLost naming the engine — not a hang,
    not a raw EOFError."""
    pool = EnginePool(
        [WorkerSpec("w0", factory="repro.serve.pool:null_engine_factory",
                    backend="subprocess")])
    try:
        handle = pool._members[0].handle
        handle.proc.stdin.write(b"\xde\xad\xbe\xef" * 4)
        handle.proc.stdin.flush()
        with pytest.raises(WorkerLost, match="w0"):
            pool.generate(0, np.ones((1, 4), np.int32),
                          ServeConfig(max_new_tokens=2))
        assert pool.state(0) == "lost"
    finally:
        pool.close()


def test_reply_matching_drops_stale_lower_seq_frames():
    """Satellite (ISSUE 8): the parent matches replies by sequence id — a
    duplicated/late reply frame (lower seq) is dropped and counted, a
    skipped-ahead seq is a desync and raises."""
    import io

    from repro.serve.pool import FrameError, _SubprocWorker, _send_msg

    w = object.__new__(_SubprocWorker)
    w.stats = {"stale_replies": 0}
    w.proc = type("P", (), {})()
    buf = io.BytesIO()
    _send_msg(buf, (1, "ok", "stale"))      # duplicate of an old reply
    _send_msg(buf, (1, "ok", "stale2"))     # ...twice
    _send_msg(buf, (3, "ok", "fresh"))
    buf.seek(0)
    w.proc.stdout = buf
    assert w._reply_for(3) == (3, "ok", "fresh")
    assert w.stats["stale_replies"] == 2
    buf2 = io.BytesIO()
    _send_msg(buf2, (9, "ok", "from the future"))
    buf2.seek(0)
    w.proc.stdout = buf2
    with pytest.raises(FrameError, match="protocol desync"):
        w._reply_for(4)


# ------------------------------------------------- relaunch budget (ISSUE 8)
def test_relaunch_budget_backoff_and_exhaustion():
    """Tentpole (ISSUE 8): a crash-looping worker is relaunched under
    bounded exponential backoff at most relaunch_budget times, then
    converges to permanently-degraded (stays LOST, column routed around)."""
    pool = EnginePool.from_slots(_slots(2), relaunch_budget=2,
                                 relaunch_backoff=10.0)
    pool.mark_lost(0)
    assert pool.relaunchable() == [0]
    assert pool.maybe_relaunch(0, now=0.0)          # attempt 1: immediate
    assert pool.live_indices() == [0, 1]
    assert pool.stats["relaunches"] == 1
    pool.mark_lost(0)
    assert not pool.maybe_relaunch(0, now=5.0)      # inside backoff window
    assert pool.state(0) == "lost"
    assert pool.maybe_relaunch(0, now=25.0)         # attempt 2 (= budget)
    assert pool.stats["relaunch_exhausted"] == 1
    pool.mark_lost(0)
    assert pool.relaunchable() == []                # budget spent
    assert not pool.maybe_relaunch(0, now=1e9)
    assert pool.state(0) == "lost"                  # permanently degraded
    assert pool.live_indices() == [1]


def test_failed_relaunch_consumes_budget_and_stays_lost():
    # build the pool around a live fake, then make its spec unbuildable
    pool2 = EnginePool.from_slots(_slots(1), relaunch_budget=1)
    pool2._members[0].spec = WorkerSpec("w0", factory="nosuch.module:nothing")
    pool2.mark_lost(0)
    assert not pool2.maybe_relaunch(0, now=0.0)     # factory import fails
    assert pool2.state(0) == "lost"
    assert pool2.stats["relaunches"] == 0
    assert pool2.relaunchable() == []               # the attempt was spent


def test_router_serve_relaunches_lost_worker_between_ticks():
    """The armed serve loop revives budget-eligible lost slots each tick."""
    pool = EnginePool.from_slots(_slots(2), relaunch_backoff=0.01)
    router = Router(pool, deadline_factor=50.0, min_deadline=10.0)
    pool.mark_lost(1)
    rng = np.random.default_rng(31)
    _submit_mixed(router, rng, per_class=2)
    done = router.serve(max_ticks=50)
    assert len(done) == 4
    assert pool.stats["relaunches"] >= 1
    assert pool.live_indices() == [0, 1]
