"""Fused CEFT relaxation Pallas kernels.

All three kernels share one VMEM-resident tile contraction
(:func:`_relax_tile`): for a tile of ``rows`` parent values ``pv`` (rows, P)
with per-row data volumes ``pdata`` (rows, 1),

    minl[r, j] = min_l  pv[r, l] + comm(l, j | pdata[r])
    comm(l, j | x) = (L[l] + x / bw[l, j]) * (l != j)

The parent class ``l`` is the loop axis, so the working set is 2-D (rows on
sublanes, the child class ``j`` on lanes) and the (rows, P, P) candidate
tensor of the XLA formulation is never built.  Every tensor the kernels touch
is 2-D or indexed only on its leading axis, which is what Mosaic lowers:

* ``pv[:, l]`` is read as a masked lane-min (exact: ``min`` over one real
  entry), not a dynamic lane slice;
* ``bw[l, :]`` and ``L[l]`` are dynamic sublane slices of their refs
  (``L`` arrives broadcast along the child axis as a (P, P) table, since
  Mosaic does not broadcast a (1, 1) value over sublanes and lanes at once);
* per-row scalars (data volumes, validity masks) arrive as (rows, 1) columns,
  never as rank-1 blocks.

The running min uses a strict ``<`` in ascending ``l``, so ties resolve to
the first class exactly as ``jnp.argmin`` does in the oracles (``ref.py``).

TPU notes: P is the lane dimension -- the ``ops.py`` wrappers pad classes to
a multiple of 128; the row tile (edges or tasks per grid step) is the sublane
dimension, a multiple of 8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.0e38  # plain float: jnp scalars would be captured as consts by pallas_call
INF = float("inf")


def _relax_tile(pv, pdata, L_ref, bw_ref):
    """(minl, argl) of one (rows, P) tile; see the module docstring."""
    rows, P = pv.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, P), 1)
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)

    def body(l, carry):
        run_min, run_arg = carry
        pvl = jnp.min(jnp.where(lane == l, pv, INF), axis=1, keepdims=True)
        bwl = bw_ref[pl.ds(l, 1), :]                          # (1, P)
        Ll = L_ref[pl.ds(l, 1), :]                            # (1, P)
        off = jnp.where(lane_row == l, 0.0, 1.0).astype(pv.dtype)
        cand = pvl + (Ll + pdata / bwl) * off                 # (rows, P)
        upd = cand < run_min
        return jnp.where(upd, cand, run_min), jnp.where(upd, l, run_arg)

    init = (jnp.full((rows, P), INF, pv.dtype), jnp.zeros((rows, P), jnp.int32))
    return jax.lax.fori_loop(0, P, body, init)


def _edge_relax_kernel(pv_ref, pdata_ref, L_ref, bw_ref, min_ref, argl_ref):
    """Segment-tiled edge relaxation: one tile = block_e contiguous edges of
    a level's CSR segment run.  The per-child ``segment_max`` stays in XLA
    where the scatter is native."""
    minl, argl = _relax_tile(pv_ref[...], pdata_ref[...], L_ref, bw_ref)
    min_ref[...] = minl
    argl_ref[...] = argl


def _edge_relax_superstep_kernel(pv_ref, pdata_ref, L_ref, bw_ref, min_ref, argl_ref):
    """Stacked super-step tile: one grid step relaxes one (level, edge-block)
    tile of a fused run's stacked (R, E, P) edge tables, with the run (or
    batch) axis as an outer grid dimension so a whole super-step's
    relaxation is one ``pallas_call``."""
    minl, argl = _relax_tile(pv_ref[0], pdata_ref[0], L_ref, bw_ref)
    min_ref[0] = minl
    argl_ref[0] = argl


def _relax_kernel(pv_ref, pdata_ref, valid_ref, L_ref, bw_ref, max_ref, argk_ref, argl_ref):
    """One topological level: the tile contraction per parent slot ``d``,
    folded into a running masked max with argmax/argmin bookkeeping for the
    path backtrack.  Parent slots are the leading (untiled) axis of the
    (D, bw_, ...) blocks, so slot ``d`` is a plain leading-axis read."""
    D, W, P = pv_ref.shape

    def body(d, carry):
        run_max, run_argk, run_argl = carry
        minl, argl = _relax_tile(pv_ref[d], pdata_ref[d], L_ref, bw_ref)
        minl = jnp.where(valid_ref[d] > 0, minl, -BIG)
        upd = minl > run_max  # strict: first maximal parent wins, like argmax
        return (
            jnp.where(upd, minl, run_max),
            jnp.where(upd, d, run_argk),
            jnp.where(upd, argl, run_argl),
        )

    init = (
        jnp.full((W, P), -BIG, pv_ref.dtype),
        jnp.zeros((W, P), jnp.int32),
        jnp.zeros((W, P), jnp.int32),
    )
    run_max, run_argk, run_argl = jax.lax.fori_loop(0, D, body, init)
    max_ref[...] = run_max
    argk_ref[...] = run_argk
    argl_ref[...] = run_argl


def _shared_specs(P: int):
    """BlockSpecs of the grid-invariant (P, P) L table and bw."""
    zero = lambda *_: (0, 0)  # noqa: E731  (any grid rank)
    return [pl.BlockSpec((P, P), zero), pl.BlockSpec((P, P), zero)]


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def edge_relax_superstep_pallas(
    pv: jnp.ndarray,      # (R, E, P) stacked gathered parent CEFT values
    pdata: jnp.ndarray,   # (R, E, 1) data volume per edge
    L: jnp.ndarray,       # (P, P)    L[l] broadcast along the child axis
    bw: jnp.ndarray,      # (P, P)
    *,
    block_e: int = 128,
    interpret: bool = False,
):
    R, E, P = pv.shape
    assert E % block_e == 0, "pad via ops.edge_relax_superstep"
    tile = pl.BlockSpec((1, block_e, P), lambda r, i: (r, i, 0))
    return pl.pallas_call(
        _edge_relax_superstep_kernel,
        grid=(R, E // block_e),
        in_specs=[tile, pl.BlockSpec((1, block_e, 1), lambda r, i: (r, i, 0)),
                  *_shared_specs(P)],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((R, E, P), pv.dtype),
            jax.ShapeDtypeStruct((R, E, P), jnp.int32),
        ],
        interpret=interpret,
    )(pv, pdata, L, bw)


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def edge_relax_pallas(
    pv: jnp.ndarray,      # (E, P) gathered parent CEFT values
    pdata: jnp.ndarray,   # (E, 1) data volume per edge
    L: jnp.ndarray,       # (P, P) L[l] broadcast along the child axis
    bw: jnp.ndarray,      # (P, P)
    *,
    block_e: int = 128,
    interpret: bool = False,
):
    E, P = pv.shape
    assert E % block_e == 0, "pad via ops.edge_relax"
    tile = pl.BlockSpec((block_e, P), lambda i: (i, 0))
    return pl.pallas_call(
        _edge_relax_kernel,
        grid=(E // block_e,),
        in_specs=[tile, pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
                  *_shared_specs(P)],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((E, P), pv.dtype),
            jax.ShapeDtypeStruct((E, P), jnp.int32),
        ],
        interpret=interpret,
    )(pv, pdata, L, bw)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def ceft_relax_pallas(
    pv: jnp.ndarray,      # (D, W, P) parent CEFT values, parent slot leading
    pdata: jnp.ndarray,   # (D, W, 1) data volume per parent edge
    validp: jnp.ndarray,  # (D, W, 1) mask (1 real parent / 0 padding)
    L: jnp.ndarray,       # (P, P)    L[l] broadcast along the child axis
    bw: jnp.ndarray,      # (P, P)
    *,
    block_w: int = 8,
    interpret: bool = False,
):
    D, W, P = pv.shape
    assert W % block_w == 0, "pad via ops.ceft_relax"
    col = pl.BlockSpec((D, block_w, 1), lambda i: (0, i, 0))
    out = pl.BlockSpec((block_w, P), lambda i: (i, 0))
    return pl.pallas_call(
        _relax_kernel,
        grid=(W // block_w,),
        in_specs=[pl.BlockSpec((D, block_w, P), lambda i: (0, i, 0)), col, col,
                  *_shared_specs(P)],
        out_specs=[out, out, out],
        out_shape=[
            jax.ShapeDtypeStruct((W, P), pv.dtype),
            jax.ShapeDtypeStruct((W, P), jnp.int32),
            jax.ShapeDtypeStruct((W, P), jnp.int32),
        ],
        interpret=interpret,
    )(pv, pdata, validp, L, bw)
