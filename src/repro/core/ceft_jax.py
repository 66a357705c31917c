"""Level-vectorized CEFT in JAX (the TPU-native reformulation; DESIGN.md §2).

The paper's Algorithm 1 is a 4-deep scalar loop.  On TPU we sweep the DAG one
*topological level* at a time: a whole level's relaxation

    cand[w, k, l, j] = CEFT[par[w,k], l] + comm(l, j | data[w,k])
    CEFT[task_w, j]  = comp[task_w, j] + max_k min_l cand[w, k, l, j]

is a dense, batched max-min-plus contraction (a tropical matmul) -- exactly the
shape the MXU/VPU wants.  Two device formulations:

  * ``ceft_jax`` — the padded dense sweep: ``lax.scan`` over fixed-size
    (n_levels, Wmax, Dmax) level tables.  Simple, but its work is
    O(levels · Wmax · Dmax · P²): on irregular fan-in graphs that is
    overwhelmingly padding.
  * ``ceft_jax_csr`` — the fused hybrid sweep (ISSUE 3 + 4): adjacent levels
    are fused into super-step runs, each ``lax.scan``ned in one dispatch
    (level-0 init folded into the first).  Per run the layout adapts: no
    within-level in-degree skew -> run-local dense (R, W, D) tables driven
    through the same body as ``ceft_jax``; skewed fan-in -> the edge-centric
    segment layout (gather parent CEFT values per *edge*, form only
    (E_level, P, P) candidates, min over the parent class, then
    ``jax.ops.segment_max`` over each child's contiguous parent segment —
    O(e·P²) total, the paper's §5 bound).  All shapes are bucketed so sweeps
    compile a bounded O(log) set of traces across graphs instead of one per
    (n_levels, Wmax, Dmax, v) tuple.
  * ``ceft_jax_batch_csr`` — the batched re-planning form (ISSUE 4): a
    module-level jitted vmap over cost planes / machines with the fused
    segment tables shared across the batch (the straggler loop's shape).

``relax_fn`` plugs in the Pallas kernels (repro.kernels) in place of the XLA
edge contraction (segment-layout runs; dense-layout runs use the XLA dense
relax); all formulations compute identical values (tests assert this).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .ceft import CeftResult, _finalize
from .machine import Machine
from .spans import span
from .taskgraph import (
    TaskGraph,
    csr_level_segments,
    fuse_levels,
    fuse_levels_dense,
    padded_level_tables,
    stack_cost_planes,
)

NEG = jnp.float32(-3.4e38)


def xla_relax(pv, pdata, validp, L, bw):
    """Reference relaxation in pure XLA.

    pv: (W, D, P) parent CEFT values; pdata: (W, D); validp: (W, D) bool;
    L: (P,), bw: (P, P).  Returns (maxk (W,P), argk (W,P), argl_sel (W,P)).
    """
    P = L.shape[0]
    off = 1.0 - jnp.eye(P, dtype=pv.dtype)
    comm = (L[:, None] + pdata[..., None, None] / bw) * off       # (W,D,P,P)
    cand = pv[..., :, None] + comm                                 # (W,D,Pl,Pj)
    argl = jnp.argmin(cand, axis=2).astype(jnp.int32)              # (W,D,Pj)
    minl = jnp.min(cand, axis=2)                                   # (W,D,Pj)
    minl = jnp.where(validp[..., None], minl, NEG)
    argk = jnp.argmax(minl, axis=1).astype(jnp.int32)              # (W,Pj)
    maxk = jnp.max(minl, axis=1)                                   # (W,Pj)
    argl_sel = jnp.take_along_axis(argl, argk[:, None, :], axis=1)[:, 0, :]
    return maxk, argk, argl_sel


def _dense_level_body(v: int, comp_pad, L, bw, relax: Callable):
    """The dense per-level scan body, shared verbatim by the whole-graph
    padded sweep (``_sweep``) and the run-local dense-layout super-steps
    (``_dense_superstep_impl``) so the two lower identically — the fused
    hybrid sweep stays bit-identical to ``ceft_jax`` by construction."""
    def body(carry, xs):
        ceft_arr, ptask, pproc = carry
        tasks, par, pdata = xs
        validt = tasks >= 0
        tt = jnp.where(validt, tasks, v)
        validp = par >= 0
        pp = jnp.where(validp, par, v)
        pv = ceft_arr[pp]                                          # (W,D,P)
        maxk, argk, argl_sel = relax(pv, pdata, validp, L, bw)
        has_par = validp.any(axis=1)
        relaxed = jnp.where(has_par[:, None], maxk, 0.0)
        newv = comp_pad[tt] + relaxed
        pt = jnp.take_along_axis(pp, argk, axis=1)                 # (W,P)
        pt = jnp.where(has_par[:, None], pt, -1)
        pl = jnp.where(has_par[:, None], argl_sel, -1)
        keep = validt[:, None]
        ceft_arr = ceft_arr.at[tt].set(jnp.where(keep, newv, ceft_arr[tt]))
        ptask = ptask.at[tt].set(jnp.where(keep, pt, ptask[tt]))
        pproc = pproc.at[tt].set(jnp.where(keep, pl, pproc[tt]))
        return (ceft_arr, ptask, pproc), None

    return body


def _sweep_impl(tables, comp_pad, L, bw, relax: Callable = xla_relax):
    v = comp_pad.shape[0] - 1  # last row is the padding scratch slot
    P = comp_pad.shape[1]
    body = _dense_level_body(v, comp_pad, L, bw, relax)
    init = (
        jnp.zeros((v + 1, P), comp_pad.dtype),
        jnp.full((v + 1, P), -1, jnp.int32),
        jnp.full((v + 1, P), -1, jnp.int32),
    )
    (ceft_arr, ptask, pproc), _ = jax.lax.scan(body, init, tables)
    return ceft_arr[:v], ptask[:v], pproc[:v]


_sweep = jax.jit(_sweep_impl, static_argnames=("relax",))

# module-level cached vmapped sweep: building a fresh jax.vmap closure per
# ceft_jax_batch call forced a retrace each invocation (the straggler loop
# calls this repeatedly) -- one jitted callable retraces only on shape change
_sweep_batch = jax.jit(
    jax.vmap(_sweep_impl, in_axes=(None, 0, 0, 0)),
)


def device_inputs(g: TaskGraph, comp: np.ndarray, m: Machine, dtype=jnp.float32):
    t = padded_level_tables(g)
    tables = (
        jnp.asarray(t["tasks"]),
        jnp.asarray(t["par"]),
        jnp.asarray(t["pdata"], dtype),
    )
    comp_pad = jnp.concatenate(
        [jnp.asarray(comp, dtype), jnp.zeros((1, comp.shape[1]), dtype)], axis=0
    )
    return tables, comp_pad, jnp.asarray(m.L, dtype), jnp.asarray(m.bw, dtype)


def ceft_jax(
    g: TaskGraph, comp: np.ndarray, m: Machine, *, relax: Callable = xla_relax
) -> CeftResult:
    tables, comp_pad, L, bw = device_inputs(g, comp, m)
    ceft_arr, ptask, pproc = _sweep(tables, comp_pad, L, bw, relax=relax)
    return _finalize(
        g,
        np.asarray(ceft_arr, np.float64),
        np.asarray(ptask),
        np.asarray(pproc),
    )


def ceft_jax_batch(g: TaskGraph, comps: np.ndarray, Ls: np.ndarray, bws: np.ndarray):
    """vmap over machines that share P (batched re-planning / straggler sweeps).

    comps: (B, v, P); Ls: (B, P); bws: (B, P, P).  Returns the (B, v, P) CEFT
    arrays and predecessor tables (device arrays).
    """
    t = padded_level_tables(g)
    tables = (
        jnp.asarray(t["tasks"]),
        jnp.asarray(t["par"]),
        jnp.asarray(t["pdata"], jnp.float32),
    )
    pad = jnp.zeros((comps.shape[0], 1, comps.shape[2]), jnp.float32)
    comp_pad = jnp.concatenate([jnp.asarray(comps, jnp.float32), pad], axis=1)
    return _sweep_batch(
        tables, comp_pad, jnp.asarray(Ls, jnp.float32), jnp.asarray(bws, jnp.float32)
    )


# ------------------------------------------------------------ CSR / edge-centric
def xla_edge_relax(pv, pdata, L, bw):
    """Edge-centric relaxation: per-edge min over the parent class.

    pv: (E, P) gathered parent CEFT values; pdata: (E,); L: (P,); bw: (P, P).
    Returns (minl (E, P), argl (E, P) int32): for each edge and child class j,
    min_l pv[e, l] + comm(l, j | pdata[e]) and the arg-min class.
    """
    P = L.shape[0]
    off = 1.0 - jnp.eye(P, dtype=pv.dtype)
    comm = (L[:, None] + pdata[:, None, None] / bw) * off          # (E,Pl,Pj)
    cand = pv[:, :, None] + comm                                    # (E,Pl,Pj)
    return jnp.min(cand, axis=1), jnp.argmin(cand, axis=1).astype(jnp.int32)


# --- bucket policy (single owner: this module; ci.sh greps the invariant) ---
# fusion waste budget: adjacent levels fuse into one scanned super-step as
# long as the run's padded work (R · (W_b + E_b) at the run-max buckets) stays
# within this factor of the real work -- trading a little padded compute for
# far fewer dispatches (the Python-dispatch overhead is what made deep narrow
# graphs lose to the dense scan)
CSR_FUSE_WASTE = 4.0

# hybrid layout threshold: a fused run takes the dense (R, W, D) layout when
# its width·fan-in bucket is within this factor of its edge bucket (no
# within-level in-degree skew — chains, GE, layered DAGs); skewed runs (star
# fan-in, heavy tails) keep the O(e) segment layout
CSR_DENSE_SKEW = 1.5


def _geo_bucket(r: int) -> int:
    """The jit-shape bucket: the √2-spaced grid {1,2,3,4,6,8,12,16,24,...}.

    Still O(log) distinct values (bounded traces), but padding wastes <= 1/3
    extra work instead of pow2's almost-2x.  Used for every bucketed axis:
    vertex count, per-level width / edge cap, fan-in depth, source count,
    and fused run length."""
    b = 1
    while b < r:
        if b < 2:
            b = 2
        elif (b & (b - 1)) == 0:  # pow2 -> pow2 * 1.5
            b += b // 2
        else:                     # pow2 * 1.5 -> next pow2
            b = (b // 3) * 4
    return b


# trace counters, keyed by the traced shape tuple -- the bounded-compilation
# acceptance test reads these (tracing executes the Python body once per shape)
CSR_TRACES: dict[tuple, int] = {}


def _superstep_impl(
    ceft_arr,      # (v_b + 1, P) running DP table (donated; row v_b is scratch)
    ptask,         # (v_b + 1, P) int32 predecessor task (donated)
    pproc,         # (v_b + 1, P) int32 predecessor class (donated)
    comp_pad,      # (v_b + 1, P) execution times (scratch row zero)
    tasks,         # (R, W_b) int32 vertex ids, padded with v_b
    edge_src,      # (R, E_b) int32 parent vertex ids, padded with v_b
    edge_data,     # (R, E_b) data volume per edge (0 where padded)
    edge_seg,      # (R, E_b) int32 within-level child slot, padded with W_b - 1
    e_real,        # (R,)     int32 real edges per level (device array: no retrace)
    L, bw,
    *,
    relax: Callable = xla_edge_relax,
    tag: str = "csr",
    masked: bool = True,
):
    """One fused super-step of the edge-centric CEFT sweep: ``lax.scan`` over
    a run of R adjacent levels sharing one (W_b, E_b) padded shape, in ONE
    dispatch.

    Per level the work is O(E_b · P²); summed over a sweep's runs that is
    O(e · P²) within the CSR_FUSE_WASTE factor (the paper's §5 bound).
    Levels inside a run depend on each other through the carried DP table,
    exactly as the per-level formulation did — the scan only removes the
    Python-level dispatch per level, not the sequential dependence.  No-op
    padding levels (``e_real == 0``, all-padding tasks) write only the
    scratch row v_b.

    ``masked`` is False when no *real* level in the run carries padded edges
    (0 < e_real < E_b never happens): the NEG-masking then folds away.  No-op
    levels stay safe unmasked — all their ids are the scratch row, so they
    compute garbage into scratch and touch nothing real.

    Back-pointers (W_b > 1): each (child, class) winner is the first maximal
    parent edge in edge order (``arg_edge``).  The winner is broadcast back
    to the edges with a row gather, and the one selected edge per (child,
    class) hands its parent id and arg-min class to one ``segment_max`` over
    a payload that is -1 on every other edge.  Indexing the winners directly
    (``edge_src[arg_edge]``, ``argl[arg_edge, cols]``) is a gather of W_b · P
    single elements, which a TPU v5e runs element by element: at the
    rgg16k-p64 buckets that cost about 15 times the relaxation it reads.
    Child slots with no edges are padding: whatever they read lands in the
    scratch row.
    """
    key = (tag, masked, ceft_arr.shape, tasks.shape, edge_src.shape)
    CSR_TRACES[key] = CSR_TRACES.get(key, 0) + 1
    W_b = tasks.shape[-1]
    E_b = edge_src.shape[-1]
    P = L.shape[0]

    def body(carry, xs):
        ceft_arr, ptask, pproc = carry
        tasks, edge_src, edge_data, edge_seg, e_real = xs
        pv = ceft_arr[edge_src]                                    # (E,P) gather
        minl, argl = relax(pv, edge_data, L, bw)                   # (E,P) each
        if masked:
            valid = jnp.arange(E_b, dtype=jnp.int32) < e_real
            minl = jnp.where(valid[:, None], minl, NEG)
        cols = jnp.arange(P, dtype=jnp.int32)[None, :]
        # per-child max over its contiguous parent segment, first-max tie-break
        # in edge order (== ascending parent id, matching the dense argmax)
        if W_b == 1:
            # single segment (deep narrow runs: chains, GE tails) -- the
            # segmented reduction collapses to a plain max/argmax, whose
            # first-max tie-break equals first-max-in-edge-order
            maxk = jnp.max(minl, axis=0, keepdims=True)            # (1,P)
            arg_edge = jnp.argmax(minl, axis=0)[None, :]           # (1,P)
            pt = edge_src[arg_edge].astype(jnp.int32)              # (1,P)
            pl = argl[arg_edge, cols]                              # (1,P)
        else:
            maxk = jax.ops.segment_max(minl, edge_seg, num_segments=W_b)
            hit = minl == maxk[edge_seg]
            if masked:
                hit &= valid[:, None]
            edge_ids = jnp.arange(E_b, dtype=jnp.int32)[:, None]
            is_first = jnp.where(hit, edge_ids, jnp.int32(E_b))
            arg_edge = jax.ops.segment_min(is_first, edge_seg, num_segments=W_b)
            # the winning edge of each (child, class), read through one
            # segmented max over [parent id | arg-min class] (E, 2P) rows
            sel = arg_edge[edge_seg] == edge_ids                   # (E,P)
            src = jnp.broadcast_to(edge_src[:, None], sel.shape)
            payload = jnp.where(
                jnp.concatenate([sel, sel], axis=1),
                jnp.concatenate([src.astype(jnp.int32), argl], axis=1),
                jnp.int32(-1),
            )
            back = jax.ops.segment_max(payload, edge_seg, num_segments=W_b)
            pt, pl = back[:, :P], back[:, P:]                      # (W,P)
        newv = comp_pad[tasks] + maxk
        ceft_arr = ceft_arr.at[tasks].set(newv, mode="drop")
        ptask = ptask.at[tasks].set(pt, mode="drop")
        pproc = pproc.at[tasks].set(pl, mode="drop")
        return (ceft_arr, ptask, pproc), None

    carry, _ = jax.lax.scan(
        body, (ceft_arr, ptask, pproc),
        (tasks, edge_src, edge_data, edge_seg, e_real),
    )
    return carry


def _superstep_init_impl(
    comp_pad, srcs_pad, tasks, edge_src, edge_data, edge_seg, e_real, L, bw,
    *, relax: Callable = xla_edge_relax, tag: str = "csr", masked: bool = True,
):
    """First super-step of a sweep with the level-0 init folded in: a whole
    deep-chain sweep is then ONE dispatch, matching the dense scan's."""
    carry = _init_impl(comp_pad, srcs_pad, tag=tag + "+init")
    return _superstep_impl(
        *carry, comp_pad, tasks, edge_src, edge_data, edge_seg, e_real, L, bw,
        relax=relax, tag=tag, masked=masked,
    )


def _dense_superstep_impl(
    ceft_arr, ptask, pproc, comp_pad,
    tasks,   # (R, W_b) int32 vertex ids, -1 padded
    par,     # (R, W_b, D_b) int32 parent ids, -1 padded
    pdata,   # (R, W_b, D_b) data volume per parent edge
    L, bw,
    *, relax: Callable = xla_relax, tag: str = "csr_dense",
):
    """Dense-layout super-step: the run's levels scanned through the same
    per-level body as the whole-graph padded sweep, but over *run-local*
    (W_b, D_b) buckets.  The hybrid sweep picks this for runs without
    within-level in-degree skew (W·D ≈ E), where the dense contraction beats
    the segmented reduction; the work bound is preserved because the buckets
    are the run's own, not the graph-global (Wmax, Dmax)."""
    key = (tag, ceft_arr.shape, tasks.shape, par.shape)
    CSR_TRACES[key] = CSR_TRACES.get(key, 0) + 1
    v = comp_pad.shape[0] - 1
    body = _dense_level_body(v, comp_pad, L, bw, relax)
    carry, _ = jax.lax.scan(body, (ceft_arr, ptask, pproc), (tasks, par, pdata))
    return carry


def _dense_superstep_init_impl(
    comp_pad, srcs_pad, tasks, par, pdata, L, bw,
    *, relax: Callable = xla_relax, tag: str = "csr_dense",
):
    carry = _init_impl(comp_pad, srcs_pad, tag=tag + "+init")
    return _dense_superstep_impl(
        *carry, comp_pad, tasks, par, pdata, L, bw, relax=relax, tag=tag
    )


def _superstep_fns(relax: Callable, keep: bool = False):
    """Module-level cached jitted super-steps for one edge relax_fn, keyed
    (batched, layout, masked, with_init) with layout in {"seg", "dense"}.
    Dense-layout runs always use the XLA dense relax (a custom ``relax``
    plugs into the segment layout only).  Carry buffers are donated off-CPU —
    the DP table then updates in place; on CPU donation is unsupported and
    each donated call pays a fallback copy, so it is disabled there.

    ``keep=True`` selects non-donating variants even off-CPU: a sweep that
    snapshots its per-run carries for later resume (the plan cache's dirty-
    frontier path) must not hand those snapshots to a donating dispatch, or
    the cached buffers would be invalidated in place.  On CPU donation is
    already off, so keep is normalized away and the same compiled closures
    serve both paths (no extra traces).

    The backend is read per *call*, not once at closure-build time: the cache
    is keyed (relax, backend, keep), so a backend selected after the first
    sweep (tests forcing CPU, a GPU picked up mid-process) gets its own
    jitted closures with the right donation policy instead of inheriting
    whichever backend happened to be default first (ISSUE 5 regression)."""
    backend = jax.default_backend()
    if backend == "cpu":
        keep = False  # donation already disabled: one closure set for both
    return _superstep_fns_for(relax, backend, keep)


@functools.lru_cache(maxsize=None)
def _superstep_fns_for(relax: Callable, backend: str, keep: bool = False):
    donate = () if (backend == "cpu" or keep) else (0, 1, 2)
    fns = {}
    for batched in (False, True):
        tag = "csr_batch" if batched else "csr"
        for masked in (False, True):
            cont = functools.partial(
                _superstep_impl, relax=relax, masked=masked, tag=tag
            )
            init = functools.partial(
                _superstep_init_impl, relax=relax, masked=masked, tag=tag
            )
            if batched:
                cont = jax.vmap(
                    cont,
                    in_axes=(0, 0, 0, 0, None, None, None, None, None, 0, 0),
                )
                init = jax.vmap(
                    init, in_axes=(0, None, None, None, None, None, None, 0, 0)
                )
            fns[(batched, "seg", masked, False)] = jax.jit(
                cont, donate_argnums=donate
            )
            fns[(batched, "seg", masked, True)] = jax.jit(init)
        dtag = tag + "_dense" if batched else "csr_dense"
        dcont = functools.partial(_dense_superstep_impl, tag=dtag)
        dinit = functools.partial(_dense_superstep_init_impl, tag=dtag)
        if batched:
            dcont = jax.vmap(
                dcont, in_axes=(0, 0, 0, 0, None, None, None, 0, 0)
            )
            dinit = jax.vmap(dinit, in_axes=(0, None, None, None, None, 0, 0))
        fns[(batched, "dense", False, False)] = jax.jit(
            dcont, donate_argnums=donate
        )
        fns[(batched, "dense", False, True)] = jax.jit(dinit)
    fns["donate"] = donate  # introspectable: tests assert the policy matches
    return fns


def _init_impl(comp_pad, srcs_pad, *, tag: str = "init"):
    """Jitted sweep prologue — level 0: CEFT(src, j) = comp(src, j), no
    predecessors.  ``srcs_pad`` is the source-id list padded with the scratch
    row v_b (whose comp row is zero, so padded writes are no-ops).  Keeping
    the init on device, bucketed, makes a whole deep-chain sweep two
    dispatches (init + one scanned super-step) instead of host-built
    transfers per call."""
    key = (tag, comp_pad.shape, srcs_pad.shape)
    CSR_TRACES[key] = CSR_TRACES.get(key, 0) + 1
    v1, P = comp_pad.shape
    ceft0 = jnp.zeros((v1, P), comp_pad.dtype).at[srcs_pad].set(
        comp_pad[srcs_pad]
    )
    return (
        ceft0,
        jnp.full((v1, P), -1, jnp.int32),
        jnp.full((v1, P), -1, jnp.int32),
    )


_csr_init = jax.jit(_init_impl)
_csr_init_batch = jax.jit(
    jax.vmap(
        functools.partial(_init_impl, tag="init_batch"), in_axes=(0, None)
    )
)


def _fused_runs(g: TaskGraph, segs=None):
    """Host-side bucketed super-step tables — the bucket policy lives here,
    not in taskgraph.

    Greedy fusion: extend each run of adjacent levels while the padded work
    at the run-max buckets stays within CSR_FUSE_WASTE of the real work.
    Per-run *layout* choice: runs whose width·fan-in bucket is within
    CSR_DENSE_SKEW of the edge bucket (no within-level in-degree skew:
    chains, GE, layered DAGs) take the dense (R, W, D) layout built from
    run-local buckets (``fuse_levels_dense``); skewed runs (star fan-in,
    heavy tails) keep the segment layout (``fuse_levels``).  All shape axes
    use the √2 ``_geo_bucket`` grid and run lengths are padded with no-op
    levels, so neither depth nor exact widths leak into the jit key.
    Returns (runs, v_b, spans) with runs a level-ordered list of
    FusedLevelRun / FusedDenseRun and spans the aligned [lo, hi) level range
    of each run (level 0, the folded init, belongs to no run) — the dirty
    frontier of an incremental re-sweep resolves to a run through spans."""
    if segs is None:
        segs = csr_level_segments(g)
    v_b = _geo_bucket(g.n)
    tb, eb = segs.task_bounds, segs.edge_bounds
    ws = [int(tb[k + 1] - tb[k]) for k in range(1, segs.n_levels)]
    es = [int(eb[k + 1] - eb[k]) for k in range(1, segs.n_levels)]
    groups: list[tuple[int, int, int, int]] = []  # (lo, hi, W_b, E_b), levels [lo, hi)
    start = 0
    cur_w = cur_e = real = 0
    for k in range(len(ws)):
        if k == start:
            cur_w, cur_e = _geo_bucket(ws[k]), _geo_bucket(es[k])
            real = ws[k] + es[k]
            continue
        new_w = max(cur_w, _geo_bucket(ws[k]))
        new_e = max(cur_e, _geo_bucket(es[k]))
        r = k - start + 1
        if r * (new_w + new_e) <= CSR_FUSE_WASTE * (real + ws[k] + es[k]):
            cur_w, cur_e = new_w, new_e
            real += ws[k] + es[k]
        else:  # close the run: waste budget exceeded
            groups.append((start + 1, k + 1, cur_w, cur_e))
            start = k
            cur_w, cur_e = _geo_bucket(ws[k]), _geo_bucket(es[k])
            real = ws[k] + es[k]
    if len(ws) > start:
        groups.append((start + 1, len(ws) + 1, cur_w, cur_e))

    indeg = g.in_degree
    widths = [0] * len(ws)
    ecaps = [0] * len(ws)
    run_ids = [-1] * len(ws)
    layouts = []
    for i, (lo, hi, W_b, E_b) in enumerate(groups):
        run_tasks = segs.task_ids[tb[lo] : tb[hi]]
        D_b = _geo_bucket(int(indeg[run_tasks].max()))
        if W_b * D_b <= CSR_DENSE_SKEW * E_b:
            layouts.append(("dense", lo, hi, W_b, D_b))
        else:
            layouts.append(("seg", lo, hi))
            for k in range(lo - 1, hi - 1):
                widths[k], ecaps[k], run_ids[k] = W_b, E_b, i
    seg_runs = iter(
        fuse_levels(segs, widths, ecaps, pad_vertex=v_b,
                    pad_run=_geo_bucket, run_ids=run_ids)
    )
    runs = []
    spans = []
    for lay in layouts:
        if lay[0] == "dense":
            _, lo, hi, W_b, D_b = lay
            runs.append(fuse_levels_dense(
                segs, lo, hi, W_b, D_b, pad_run=_geo_bucket))
        else:
            _, lo, hi = lay
            runs.append(next(seg_runs))
        spans.append((lo, hi))
    return runs, v_b, tuple(spans)


def _host_tables(r) -> tuple:
    """The host arrays a fused run scans over, in dispatch order."""
    if hasattr(r, "par"):  # FusedDenseRun
        return r.tasks, r.par, r.pdata
    return r.tasks, r.edge_src, r.edge_data, r.edge_seg, r.e_real


class DeviceRun(NamedTuple):
    """One fused super-step on the device: its layout ("dense" or "seg"),
    the scanned tables, whether some real level has padded edge slots (the
    segment runs' host-known ``masked`` flag; no-op run-padding levels are
    safe unmasked, they only touch the scratch row), the [lo, hi) level span
    it covers, and the padded edge slots it relaxes against the graph edges
    among them, both counted on the host."""
    layout: str
    tables: tuple
    masked: bool
    levels: tuple[int, int]
    edge_slots: int
    real_edges: int


def _device_runs(runs, spans) -> list[DeviceRun]:
    """Move fused super-step tables to device (the scanned xs arrays).  A
    dense run relaxes one slot per (child, parent) cell, a segment run every
    padded slot of every level row, run-padding rows included."""
    out = []
    for r, levels in zip(runs, spans):
        tables = tuple(jnp.asarray(a) for a in _host_tables(r))
        if hasattr(r, "par"):  # FusedDenseRun
            out.append(DeviceRun("dense", tables, False, levels,
                                 int(r.par.size),
                                 int(np.count_nonzero(r.par >= 0))))
        else:
            E_b = r.edge_src.shape[-1]
            masked = bool(np.any((r.e_real > 0) & (r.e_real < E_b)))
            out.append(DeviceRun("seg", tables, masked, levels,
                                 int(r.edge_src.size), int(r.e_real.sum())))
    return out


def _padded_sources(g: TaskGraph, v_b: int) -> np.ndarray:
    """Source ids padded with the scratch row v_b to a bucketed length (so
    the jitted init does not retrace per source count)."""
    srcs = g.sources
    s_b = _geo_bucket(len(srcs))
    out = np.full(s_b, v_b, np.int32)
    out[: len(srcs)] = srcs
    return out


def _build_device_state(g: TaskGraph, segs=None):
    """Uncached build of a graph's device-side sweep state: (device runs,
    padded sources, v_b).  The *store* for this state lives in
    :mod:`repro.sched.plancache` (the unified plan cache); this module
    only knows how to build it — callers go through
    :func:`_graph_device_state` so repeated sweeps of one graph hit the
    cache.  Each run's edge work is counted here, once, on the host."""
    with span("ceft.levels"):
        if segs is None:
            segs = csr_level_segments(g)
    with span("ceft.fuse"):
        fused, v_b, spans = _fused_runs(g, segs=segs)
        srcs = _padded_sources(g, v_b)
    nbytes = srcs.nbytes + sum(a.nbytes for r in fused for a in _host_tables(r))
    with span("ceft.upload", bytes=nbytes):
        runs = _device_runs(fused, spans)
        srcs = jnp.asarray(srcs)
    return runs, srcs, v_b


def _graph_device_state(g: TaskGraph, segs=None):
    """(device runs, padded sources, v_b) for one graph — a thin view over
    the plan cache's identity-keyed device-state store."""
    from ..sched import plancache

    return plancache.device_state(g, segs=segs)


def csr_device_inputs(g: TaskGraph, comp: np.ndarray, m: Machine, dtype=jnp.float32):
    """Bucketed fused super-step device arrays for :func:`ceft_jax_csr`.

    Returns (runs, comp_pad, srcs_pad, L, bw, v_b) where ``runs`` is a list
    of :class:`DeviceRun` — one scanned dispatch each — and comp_pad is the
    (v_b+1, P) execution-time table (vertex count bucketed too, so graph
    size does not leak into the jit key).
    """
    runs, srcs_pad, v_b = _graph_device_state(g)
    v, P = comp.shape
    nbytes = np.dtype(dtype).itemsize * ((v_b + 1) * P + P + P * P)
    with span("ceft.upload", bytes=nbytes):
        comp_pad = np.zeros((v_b + 1, P), np.float32)
        comp_pad[:v] = comp
        return (
            runs,
            jnp.asarray(comp_pad, dtype),
            srcs_pad,
            jnp.asarray(m.L, dtype),
            jnp.asarray(m.bw, dtype),
            v_b,
        )


def csr_sweep(
    inputs, *, relax: Callable = xla_edge_relax,
    keep_carries: list | None = None,
    resume: tuple | None = None,
):
    """Run the fused CSR sweep over prebuilt :func:`csr_device_inputs`
    (which carries everything the sweep needs -- no graph/cost re-reads, so
    stale-argument mismatches are impossible by construction).

    One jitted dispatch for the init plus one per fused run (a 64-level chain
    is TWO dispatches, not 64+).  Re-runnable per call because the super-step
    donates its carry buffers (the DP table is updated in place on device).
    Returns the *padded* (v_b+1, P) device arrays (ceft, pred_task,
    pred_proc); rows >= g.n are scratch — slice after the host transfer
    (slicing on device would add a per-call dispatch per output).

    Incremental re-sweep hooks (the plan cache's dirty-frontier path):

    * ``keep_carries`` — a list the sweep appends each executed run's output
      carry to.  The carry after run r-1 depends only on comp rows of levels
      below run r (levels are longest-path depth, so each vertex is written
      exactly once, in its own run), which is what makes run-granular resume
      bit-identical to a full sweep.
    * ``resume=(start, carry)`` — skip runs ``< start`` and continue from the
      snapshot ``carry`` (the keep_carries entry for run start-1) with the
      *current* comp_pad.  Rows for vertices in runs >= start are unwritten
      init state in the snapshot and are fully recomputed, so the result is
      bit-identical to a from-scratch sweep.  The caller guarantees no
      changed comp row lies below run start (level 0 or run 0 dirty => full
      sweep, there is no cheaper prefix to keep).

    Either hook switches to the non-donating keep fns so snapshots are never
    invalidated in place; the resumed runs reuse the exact per-run tables (and
    thus the exact ``_geo_bucket``-bucketed shapes) of the full sweep, so no
    new jit traces are minted by resuming."""
    runs, comp_pad, srcs_pad, L, bw, v_b = inputs
    keep = keep_carries is not None or resume is not None
    fns = _superstep_fns(relax, keep=keep)
    start, carry = resume if resume is not None else (0, None)
    with _sweep_span(runs[start:]):
        for run in runs[start:]:
            if carry is None:  # level-0 init folded into the first dispatch
                carry = fns[(False, run.layout, run.masked, True)](
                    comp_pad, srcs_pad, *run.tables, L, bw
                )
            else:
                carry = fns[(False, run.layout, run.masked, False)](
                    *carry, comp_pad, *run.tables, L, bw
                )
            if keep_carries is not None:
                keep_carries.append(carry)
        if carry is None:  # single-level graph: no relaxation levels at all
            carry = _csr_init(comp_pad, srcs_pad)
    return carry


def _sweep_span(runs: list[DeviceRun], batch: int = 1):
    """The ``ceft.sweep`` span over dispatching ``runs``, with their
    host-counted edge slots and real edges (times the batch)."""
    return span("ceft.sweep",
                edge_slots=batch * sum(r.edge_slots for r in runs),
                real_edges=batch * sum(r.real_edges for r in runs))


def read_tables(carry, v: int) -> tuple[np.ndarray, ...]:
    """Wait for a sweep's padded (ceft, pred_task, pred_proc) carry and read
    it back to the host, keeping the first ``v`` rows (the rest are
    scratch) of each (v_b+1, P) or batched (B, v_b+1, P) table.  The copies
    start once the host has seen the sweep end, which keeps the wait and the
    copies apart and costs about 0.3 ms per n=16384, P=64 plan on a TPU v5e
    over letting the first copy wait on the device."""
    with span("ceft.wait"):
        jax.block_until_ready(carry)
    with span("ceft.readback", bytes=sum(int(a.nbytes) for a in carry)):
        return tuple(np.asarray(a)[..., :v, :] for a in carry)


def read_plans(g: TaskGraph, carry) -> list[CeftResult]:
    """Read a sweep's carry back (:func:`read_tables`), widen the CEFT table
    to float64 and finalize one :class:`CeftResult` per cost plane: one for
    a (v_b+1, P) carry, B for a batched (B, v_b+1, P) one."""
    ceft_arr, ptask, pproc = read_tables(carry, g.n)
    with span("ceft.finalize"):
        ceft_arr = ceft_arr.astype(np.float64)
        if ceft_arr.ndim == 2:
            ceft_arr, ptask, pproc = ceft_arr[None], ptask[None], pproc[None]
        return [_finalize(g, c, pt, pp)
                for c, pt, pp in zip(ceft_arr, ptask, pproc)]


def ceft_jax_csr(
    g: TaskGraph, comp: np.ndarray, m: Machine, *, relax: Callable = xla_edge_relax
) -> CeftResult:
    """Edge-centric CSR CEFT sweep: O(e·P²) work, bucketed jit shapes, fused
    same-bucket super-steps.

    Produces values bit-identical to :func:`ceft_jax` (same float32 arithmetic
    per candidate, same tie-breaking) while doing only real-edge work.
    """
    inputs = csr_device_inputs(g, comp, m)
    [result] = read_plans(g, csr_sweep(inputs, relax=relax))
    return result


# ------------------------------------------------------- batched CSR re-planning
def csr_batch_device_inputs(g: TaskGraph, comps, Ls, bws, dtype=jnp.float32):
    """Device arrays for :func:`csr_batch_sweep`: the fused segment tables are
    shared (batch-invariant); cost planes / machines are stacked per scenario.

    Returns (runs, comp_pad (B, v_b+1, P), srcs_pad, Ls (B, P),
    bws (B, P, P), v_b)."""
    # hot re-planning path (same graph object): the plan cache's identity-
    # keyed device-state store makes the shared-segment rebuild a hit, only
    # the cost planes change per call
    comps = stack_cost_planes(g, comps)
    runs, srcs_pad, v_b = _graph_device_state(g)
    B, v, P = comps.shape
    nbytes = np.dtype(dtype).itemsize * B * ((v_b + 1) * P + P + P * P)
    with span("ceft.upload", bytes=nbytes):
        comp_pad = np.zeros((B, v_b + 1, P), np.float32)
        comp_pad[:, :v] = comps
        return (
            runs,
            jnp.asarray(comp_pad, dtype),
            srcs_pad,
            jnp.asarray(np.asarray(Ls, np.float32), dtype),
            jnp.asarray(np.asarray(bws, np.float32), dtype),
            v_b,
        )


def csr_batch_sweep(inputs, *, relax: Callable = xla_edge_relax):
    """Run the batched fused CSR sweep over prebuilt
    :func:`csr_batch_device_inputs` (self-contained, like :func:`csr_sweep`): a module-level jitted vmap over the
    scenario axis with the segment tables passed unbatched (in_axes=None).
    Returns the *padded* (B, v_b+1, P) device arrays (ceft, pred_task,
    pred_proc); rows >= g.n are scratch (see :func:`csr_sweep`)."""
    runs, comp_pad, srcs_pad, Ls, bws, v_b = inputs
    fns = _superstep_fns(relax)
    carry = None
    with _sweep_span(runs, batch=comp_pad.shape[0]):
        for run in runs:
            if carry is None:  # level-0 init folded into the first dispatch
                carry = fns[(True, run.layout, run.masked, True)](
                    comp_pad, srcs_pad, *run.tables, Ls, bws
                )
            else:
                carry = fns[(True, run.layout, run.masked, False)](
                    *carry, comp_pad, *run.tables, Ls, bws
                )
        if carry is None:  # single-level graph: no relaxation levels at all
            carry = _csr_init_batch(comp_pad, srcs_pad)
    return carry


def ceft_jax_batch_csr(
    g: TaskGraph, comps: np.ndarray, Ls: np.ndarray, bws: np.ndarray,
    *, relax: Callable = xla_edge_relax,
):
    """Batched re-planning on the CSR formulation: vmap over machines that
    share P, segment tables shared across the batch (ISSUE 4 — the straggler
    loop's O(e·P²) bound).

    comps: (B, v, P); Ls: (B, P); bws: (B, P, P).  Returns the (B, v, P)
    arrays (host-sliced from the padded carries), bit-identical to
    :func:`ceft_jax_batch`.
    """
    inputs = csr_batch_device_inputs(g, comps, Ls, bws)
    return read_tables(csr_batch_sweep(inputs, relax=relax), g.n)


def ceft_batch_csr_results(
    g: TaskGraph, comps: np.ndarray, Ls: np.ndarray, bws: np.ndarray,
    *, relax: Callable = xla_edge_relax,
) -> list[CeftResult]:
    """Finalized :class:`CeftResult` per batched scenario (paper lines 19-26
    applied to each plane) — the form the re-planning schedulers consume."""
    inputs = csr_batch_device_inputs(g, comps, Ls, bws)
    return read_plans(g, csr_batch_sweep(inputs, relax=relax))


# ------------------------------------------------------ in-memory request DAGs
def request_graph(n: int, src, dst, data) -> TaskGraph:
    """TaskGraph for an in-memory request DAG — a thin view over the plan
    cache's content-keyed graph store: structurally-equal edge arrays map to
    the SAME TaskGraph object, so the identity-keyed device-state store hits
    and the fused segment tables are not rebuilt per tick.

    ``src``/``dst`` must already be topological (src < dst), the natural
    shape for prefill->decode chains.  A steady-state router whose pending
    mix keeps the same DAG structure across ticks pays the host-side
    segment/fusion build exactly once."""
    from ..sched import plancache

    return plancache.graph_for(n, src, dst, data)


def plan_request_dag(
    n: int, src, dst, data, comp: np.ndarray, m: Machine,
    *, relax: Callable = xla_edge_relax,
) -> CeftResult:
    """Plan one in-memory request DAG through the fused CSR sweep.

    The public entry point for online dispatchers (repro.serve.router): edge
    arrays in, mapped critical path out, without the caller owning TaskGraph
    construction or the device-state caching."""
    return ceft_jax_csr(request_graph(n, src, dst, data), comp, m, relax=relax)


def plan_request_dags(
    n: int, src, dst, data, comps: np.ndarray, Ls: np.ndarray, bws: np.ndarray,
    *, relax: Callable = xla_edge_relax,
) -> list[CeftResult]:
    """Batched scenario planning over one request DAG (nominal + degraded
    cost planes in a single vmapped dispatch — the straggler loop's shape,
    reused by the router when a degraded engine must shed work)."""
    return ceft_batch_csr_results(
        request_graph(n, src, dst, data), comps, Ls, bws, relax=relax
    )
