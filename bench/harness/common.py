"""What every runner shares: the device guard, the compilation cache, the
compile counter, host spans, the traced window and the result line."""
from __future__ import annotations

import contextlib
import json
import shutil
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoDevice(RuntimeError):
    """No accelerator, too few of them, or one the peak table lacks."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def device_info(chips: int) -> tuple[dict, dict]:
    """(device description, its peaks) for the accelerator this run uses.
    Raises NoDevice on the CPU, with fewer chips than asked, or on a device
    the peak table does not list."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform == "cpu":
        raise NoDevice("JAX found no accelerator, only the CPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if dev.device_kind not in peaks:
        raise NoDevice(f"device kind {dev.device_kind!r} is not in "
                       "bench/peaks.json")
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": chips}, peaks[dev.device_kind])


def memory_peak(dev) -> int:
    """Peak bytes in use on the device so far (0 where it keeps no count)."""
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


class Compiles:
    """Counts backend compilations; ``window`` marks the measured part."""

    def __init__(self):
        import jax

        self.setup = 0
        self.window = 0
        self.in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            if self.in_window:
                self.window += 1
            else:
                self.setup += 1


class Spans:
    """Host spans: named in the profiler's trace, kept in memory as
    (name, start_ns, end_ns) on the host clock."""

    def __init__(self):
        self.items: list[tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, time.perf_counter_ns()))


class Tracer:
    """The profiler around the first ``seconds`` of the window (off when
    ``seconds`` is 0).  Writing the trace takes the host tens of seconds.
    A closed loop may pause for it between calls; an open loop's client
    would stop submitting while requests fall due, so ``stop(background=
    True)`` writes on a thread of its own, and ``wait`` joins it."""

    def __init__(self, workload: str, seconds: float):
        self.dir = TRACE_DIR / workload
        self.seconds = seconds
        self.on = False
        self.t1 = 0
        self.writer = None

    def start(self) -> None:
        import jax

        if self.seconds <= 0:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        # no Python function tracer: host spans (TraceAnnotation) and device
        # operations are all the readers take, and tracing every Python
        # call lengthens the host's gaps between a tick's decode steps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.on = True

    def due(self, elapsed_s: float) -> bool:
        return self.on and self.t1 == 0 and elapsed_s >= self.seconds

    def stop(self, background: bool = False) -> None:
        import jax

        if self.on and self.t1 == 0:
            self.t1 = time.perf_counter_ns()
            if not background:
                jax.profiler.stop_trace()
                return
            self.writer = threading.Thread(target=jax.profiler.stop_trace)
            self.writer.start()

    def wait(self) -> None:
        if self.writer is not None:
            self.writer.join()


def percentile(values, i: int) -> float:
    """The i-th percentile as ``statistics.quantiles(values, n=100)[i - 1]``
    computes it (the exclusive method), extended to infinite entries, which
    stand for requests that never completed."""
    data = sorted(float(v) for v in values)
    m, n = len(data), 100
    if m == 0:
        return float("inf")
    if m == 1:
        return data[0]
    j = min(max(i * (m + 1) // n, 1), m - 1)
    delta = i * (m + 1) - j * n
    lo, hi = data[j - 1], data[j]
    if delta == 0 or lo == hi:
        return lo
    if hi == float("inf"):
        return hi
    return (lo * (n - delta) + hi * delta) / n



def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list, breakdown: dict | None) -> str:
    """The last stdout line; the compared numbers come last under
    ``checks``, each with its limit."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    return json.dumps(out)

