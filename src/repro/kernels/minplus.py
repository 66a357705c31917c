"""Blocked tropical (min-plus) matmul Pallas kernel.

TPU adaptation of the paper's relaxation hot-spot (DESIGN.md §2): the classic
(i, j, k) matmul grid with BlockSpec VMEM tiling, accumulating with ``min``
instead of ``+`` and combining with ``+`` instead of ``*``.  Every block is
(8, 128)-aligned (bm, bn, bk multiples of 128), and inside a block the
contraction index is a loop: each step folds ``a[:, k] + b[k, :]`` into the
2-D (bm, bn) accumulator, so no (bm, bk, bn) candidate tensor is built.
``a[:, k]`` is read as a masked lane-min (exact), ``b[k, :]`` as a dynamic
sublane slice of its ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.0e38  # plain float: jnp scalars would be captured as consts by pallas_call


def _minplus_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, BIG)

    a = a_ref[...]                                         # (bm, bk)
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)

    def body(k, acc):
        ak = jnp.min(jnp.where(lane == k, a, float("inf")), axis=1, keepdims=True)
        return jnp.minimum(acc, ak + b_ref[pl.ds(k, 1), :])

    o_ref[...] = jax.lax.fori_loop(0, a.shape[1], body, o_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def minplus_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 256,
    bk: int = 128,
    bn: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """C[i,j] = min_k A[i,k] + B[k,j] with (bm, bk, bn) VMEM tiles.

    Shapes must be multiples of the block sizes (ops.py pads with +BIG, which
    is the identity of the (min, +) semiring).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, "pad via ops.minplus"
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a, b)
