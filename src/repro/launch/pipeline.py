"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis (shard_map +
ppermute), for the dense decoder family.

The CEFT partitioner (repro.sched) decides *where* stages go on a
heterogeneous fleet; this module is the *execution* of a contiguous-stage
plan: each pipe-axis device holds layers [i*L/S, (i+1)*L/S); microbatches
stream through with the classic (n_micro + n_stages - 1)-tick schedule.  The
SPMD formulation computes every stage every tick (bubble ticks process
garbage that is masked at the boundaries) -- the standard trade for a single
fused program.

Forward-only here (serving / prefill pipelining); training composes this with
jax.grad through shard_map (ppermute is differentiable) at the cost of
stashing per-tick activations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..models.layers import mlp, rmsnorm, rope_cos_sin
from ..models.transformer import _period_fwd
from ..substrate import shard_map


def _stage_fwd(cfg: ArchConfig, stage_params, x, cos_sin):
    """Apply this device's layers (stacked on axis 0) to x."""
    def body(h, pp):
        h2, _, _, _ = _period_fwd(cfg, pp, h, cos_sin)
        return h2, None
    out, _ = jax.lax.scan(body, x, stage_params)
    return out


def pipeline_forward(cfg: ArchConfig, blocks, x, mesh, *, n_micro: int,
                     axis: str = "pipe"):
    """blocks: stacked per-layer params (leading dim n_layers, reshaped to
    (n_stages, layers_per_stage, ...)); x: (B, S, D) embedded inputs.
    Returns (B, S, D) hidden states after all layers.

    B must divide into n_micro microbatches.
    """
    n_stages = mesh.shape[axis]
    L = cfg.n_layers // cfg.period
    assert L % n_stages == 0, (L, n_stages)
    B, S, D = x.shape
    assert B % n_micro == 0
    mb = B // n_micro
    ticks = n_micro + n_stages - 1

    staged = jax.tree.map(
        lambda a: a.reshape((n_stages, L // n_stages) + a.shape[1:]), blocks)
    xm = x.reshape(n_micro, mb, S, D)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
    cos_sin = rope_cos_sin(cfg, positions) if cfg.use_rope and cfg.n_heads else None

    def per_stage(stage_params, xm_local):
        sid = jax.lax.axis_index(axis)

        def tick(carry, t):
            buf = carry                           # activation received last tick
            m = jnp.clip(t - sid, 0, n_micro - 1)
            inp0 = jax.lax.dynamic_index_in_dim(xm_local, m, 0, keepdims=False)
            inp = jnp.where(sid == 0, inp0, buf)
            out = _stage_fwd(cfg, stage_params, inp, cos_sin)
            # pass to the next stage (ring; last->first carries garbage)
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return nxt, out

        _, outs = jax.lax.scan(tick, jnp.zeros((mb, S, D), x.dtype),
                               jnp.arange(ticks))
        # keep only the last stage's valid ticks: tick t emits microbatch
        # t - (n_stages-1); zero elsewhere so a psum over the axis selects it
        valid = (jnp.arange(ticks) >= n_stages - 1)[:, None, None, None]
        is_last = (sid == n_stages - 1)
        contrib = jnp.where(valid & is_last, outs, 0.0)
        contrib = contrib[n_stages - 1:]          # (n_micro, mb, S, D)
        return jax.lax.psum(contrib, axis)

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),   # stage params sharded; inputs replicated
        out_specs=P(),
    )
    out = fn(staged, xm)
    return out.reshape(B, S, D)
