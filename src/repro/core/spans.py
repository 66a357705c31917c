"""Named spans of the CEFT planner call, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
running it records the span's name, start, end and integer stats on the
calling thread, beside the device's own operations; otherwise it is a no-op
annotation of about a microsecond.  Spans nest on the calling thread, so the
nesting records which span caused which.  There is no switch and no
exporter: whoever starts the profiler gets the spans.

Spans and their stats, in the order one planner call meets them:

``ceft.graph`` (``taskgraph.from_edge_arrays``)
    building a task graph from edge arrays.
``ceft.state`` (``plancache.device_state``)
    fetching a graph's device-side sweep state.  ``hit`` is 1 when the
    store held it, 0 when it was built inside this span.
``ceft.levels`` (inside a ``ceft.state`` miss)
    level segmentation (``csr_level_segments``).
``ceft.fuse`` (inside a ``ceft.state`` miss)
    fusing levels into super-step runs and their host tables
    (``ceft_jax._fused_runs``).
``ceft.upload``
    staging host arrays for the device: the run tables and padded sources
    (inside a ``ceft.state`` miss), and on every call the cost plane,
    latencies and bandwidths (``csr_device_inputs``).  ``bytes`` is the
    host bytes handed over.  The span ends when the runtime has taken the
    arrays; the copy to the device may finish later.
``ceft.sweep`` (``ceft_jax.csr_sweep``, ``csr_batch_sweep``)
    dispatching the fused super-steps.  ``edge_slots`` is the padded edge
    slots the dispatched runs relax and ``real_edges`` the graph edges
    among them, times the batch for a batched sweep.  A resumed sweep
    counts only the runs it executes.  ``real_edges / edge_slots`` is the
    useful share of the relax work.
``ceft.wait`` (``ceft_jax.read_tables``)
    waiting for the sweep's result tables on the device.
``ceft.readback`` (``ceft_jax.read_tables``)
    copying the result tables to the host.  ``bytes`` is the device bytes
    read.
``ceft.finalize`` (``ceft_jax.read_plans``)
    widening the CEFT table to float64 and finalizing the critical path.
"""
from __future__ import annotations

import jax


def span(name: str, **stats: int) -> jax.profiler.TraceAnnotation:
    """Context manager recording ``name`` with integer ``stats`` in a
    running profiler trace (see the module docstring for the names)."""
    return jax.profiler.TraceAnnotation(name, **stats)
