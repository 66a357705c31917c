"""Jit'd public wrappers around the Pallas kernels: padding to block and
lane multiples, the kernels' 2-D operand layouts, and adapters matching
``repro.core.ceft_jax``'s relax_fn signature.

Every wrapper compiles its kernel for the default backend unless called with
``interpret=True`` (the Pallas interpreter, for validation on the CPU).  There
is no silent fallback: on a backend without a Pallas compiler the call fails.
"""
from __future__ import annotations

import jax.numpy as jnp

from .ceft_relax import (
    ceft_relax_pallas,
    edge_relax_pallas,
    edge_relax_superstep_pallas,
)
from .minplus import BIG, minplus_pallas

LANES = 128


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value) -> jnp.ndarray:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


def _pad_classes(pv, L, bw):
    """Pad the class axis (last of ``pv``) to the 128-lane tile: padded
    classes get +BIG values and startups so they are never selected, and
    unit bandwidth so no division produces a NaN.  ``L`` comes back as the
    (P, P) table ``L[l]`` broadcast along the child axis, which the kernels
    slice one parent class (row) at a time."""
    pv = _pad_to(pv, pv.ndim - 1, LANES, BIG)
    bw = _pad_to(_pad_to(bw, 0, LANES, 1.0), 1, LANES, 1.0)
    L = jnp.broadcast_to(_pad_to(L, 0, LANES, BIG)[:, None], bw.shape)
    return pv, L, bw


def minplus(a, b, *, bm: int = 256, bk: int = 128, bn: int = 256,
            interpret: bool = False):
    """Tropical matmul C[i,j] = min_k A[i,k]+B[k,j], padded to block multiples
    with +BIG (the (min,+) identity) and sliced back."""
    m, n = a.shape[0], b.shape[1]
    a = _pad_to(_pad_to(a, 0, bm, BIG), 1, bk, BIG)
    b = _pad_to(_pad_to(b, 0, bk, BIG), 1, bn, BIG)
    out = minplus_pallas(a, b, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return out[:m, :n]


def ceft_relax(pv, pdata, validp, L, bw, *, block_w: int = 8,
               interpret: bool = False):
    """Fused CEFT level relaxation (see ceft_relax.py).  Pads the task axis to
    a block multiple (padding rows carry validp=0) and the class axis to the
    lane tile, and moves the parent slot to the leading axis."""
    W, D, P = pv.shape
    pv, L, bw = _pad_classes(pv, L, bw)

    def slot_major(x):  # (W, D, ...) -> (D, W padded, ...), per-row columns
        x = _pad_to(x, 0, block_w, 0.0)
        return x.transpose(1, 0, 2) if x.ndim == 3 else x.T[:, :, None]

    maxk, argk, argl = ceft_relax_pallas(
        slot_major(pv), slot_major(pdata), slot_major(validp), L, bw,
        block_w=block_w, interpret=interpret,
    )
    maxk, argk, argl = maxk[:W, :P], argk[:W, :P], argl[:W, :P]
    # tasks with no valid parent have undefined argk/argl: pin them to -1
    has = (validp > 0).any(axis=1)[:, None]
    return maxk, jnp.where(has, argk, -1), jnp.where(has, argl, -1)


def pallas_relax(pv, pdata, validp, L, bw, *, interpret: bool = False):
    """Drop-in ``relax_fn`` for repro.core.ceft_jax._sweep: same contract as
    ``xla_relax`` (validp arrives as bool)."""
    return ceft_relax(pv, pdata, validp.astype(pv.dtype), L, bw,
                      interpret=interpret)


def edge_relax(pv, pdata, L, bw, *, block_e: int = 128, interpret: bool = False):
    """Segment-tiled fused edge relaxation (see ceft_relax.py).  Pads the edge
    axis to a block multiple (padded rows are sliced off; the CSR sweep masks
    them anyway) and the class axis to the lane tile."""
    E, P = pv.shape
    pv, L, bw = _pad_classes(pv, L, bw)
    minl, argl = edge_relax_pallas(
        _pad_to(pv, 0, block_e, 0.0), _pad_to(pdata, 0, block_e, 0.0)[:, None],
        L, bw, block_e=block_e, interpret=interpret,
    )
    return minl[:E, :P], argl[:E, :P]


def edge_relax_superstep(pv, pdata, L, bw, *, block_e: int = 128,
                         interpret: bool = False):
    """Stacked super-step edge relaxation (see ceft_relax.py): the fused-run
    (R, E, P) form with the run/batch axis as an outer grid dimension.  Pads
    the edge axis to a block multiple (padded rows are sliced off; the CSR
    sweep masks them anyway) and the class axis to the lane tile."""
    R, E, P = pv.shape
    pv, L, bw = _pad_classes(pv, L, bw)
    minl, argl = edge_relax_superstep_pallas(
        _pad_to(pv, 1, block_e, 0.0), _pad_to(pdata, 1, block_e, 0.0)[..., None],
        L, bw, block_e=block_e, interpret=interpret,
    )
    return minl[:, :E, :P], argl[:, :E, :P]


def pallas_edge_relax(pv, pdata, L, bw, *, interpret: bool = False):
    """Drop-in ``relax_fn`` for repro.core.ceft_jax.ceft_jax_csr: same contract
    as ``xla_edge_relax``."""
    return edge_relax(pv, pdata, L, bw, interpret=interpret)
