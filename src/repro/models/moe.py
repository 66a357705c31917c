"""Token-choice MoE: GShard-style einsum dispatch with capacity drops, or
dropless, over the experts this chip holds, plus an optional shared expert.

Tokens are processed in *groups* (a sequence slice) so the dispatch/combine
tensors stay O(tokens x E x C) with C = cf * group * k / E -- the group size
bounds the quadratic dispatch-einsum cost to a few percent of expert FLOPs
(group 256: E*C ~ 2.5 * 256 vs d_ff contraction; see EXPERIMENTS.md §Roofline
"useful-FLOPs ratio").

Routing is dropless when the capacity reaches the group size, since no
expert can then receive more slots than the group has tokens: always in
decode (a group of one token), and in serving prefill (``dropless=True``,
which ``Model.prefill`` passes).  Training keeps the GShard capacity, and a
(token, expert) slot past it is dropped.  A dropless layer skips the
dispatch: every held expert runs on every token of the group, weighted by
its gate (zero where the token did not choose it).

Held experts: the router scores all ``n_experts`` and picks ``top_k``; the
layer computes only ``cfg.n_held`` of them, ``expert_first`` onwards, so its
output is this chip's share of the routed sum (expert parallelism, with the
exchange between chips left to the chips that hold the rest).  A shared
expert (``cfg.shared_ff``) runs on every token beside them.

Expert placement: true EP (experts sharded over the model axis) when the
expert count divides it (dbrx/jamba: 16); otherwise tensor-parallel experts
(d_ff over model; mixtral: 8 experts on a 16-way axis).  The divisibility
degradation in common.resolve_spec picks this automatically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import PSpec, constrain
from .layers import mlp, mlp_specs

AUX_COEF = 0.01
GROUP = 256


def moe_specs(cfg: ArchConfig) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_held
    assert cfg.mlp_style == "swiglu", "MoE experts are SwiGLU"
    out = {
        "router": PSpec((d, cfg.n_experts), ("embed", "experts")),
        "wg": PSpec((e, d, ff), ("experts", "embed", "ffn")),
        "wu": PSpec((e, d, ff), ("experts", "embed", "ffn")),
        "wd": PSpec((e, ff, d), ("experts", "ffn", "embed")),
    }
    if cfg.shared_ff:
        out["shared"] = mlp_specs(cfg, cfg.shared_ff)
    return out


def moe(p, x, cfg: ArchConfig, dropless: bool = False):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar, slots (3,) int32).

    ``slots`` counts (token, expert) slots: those routed to a held expert,
    all routed (tokens x top_k, over every expert), and held ones dropped
    by the capacity."""
    B, S, D = x.shape
    E, K, Eh = cfg.n_experts, cfg.top_k, cfg.n_held
    gs = min(GROUP, S)
    nG = S // gs
    assert S % gs == 0, (S, gs)
    C = gs if dropless else max(1, int(cfg.capacity_factor * gs * K / E))

    xg = x.reshape(B, nG, gs, D)
    logits = (xg @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (B,nG,gs,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                               # (B,nG,gs,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # one-hot over the held experts: all zero for an expert held elsewhere
    mask = jax.nn.one_hot(idx - cfg.expert_first, Eh, dtype=jnp.float32)
    wg = p["wg"].astype(x.dtype)
    wu = p["wu"].astype(x.dtype)
    wd = p["wd"].astype(x.dtype)
    if C >= gs:
        # dropless: every held expert on every token, weighted by its gate
        w = jnp.einsum("bgsk,bgske->bgse", gate, mask).astype(x.dtype)
        h = jax.nn.silu(jnp.einsum("bgsd,edf->bgsef", xg, wg))
        h = h * jnp.einsum("bgsd,edf->bgsef", xg, wu)
        h = constrain(h, "batch", "seq", None, "experts", "ffn")
        out = jnp.einsum("bgsef,efd->bgsd", h * w[..., None], wd)
        dropped = jnp.zeros((), jnp.int32)
    else:
        # position of each (token, k) within its expert's capacity, per group
        flat = mask.reshape(B, nG, gs * K, Eh)
        pos = jnp.cumsum(flat, axis=2) - 1.0
        pos = pos.reshape(B, nG, gs, K, Eh)
        keep = (pos < C) & (mask > 0)
        pos = jnp.clip(pos, 0, C - 1).astype(jnp.int32)

        # combine[b,g,s,e,c] = sum_k gate_k * keep * onehot(pos, C)
        poh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]  # (B,nG,gs,K,Eh,C)
        combine = jnp.einsum("bgsk,bgskec->bgsec", gate, poh)
        dispatch = (combine > 0).astype(x.dtype)                           # (B,nG,gs,Eh,C)

        xe = jnp.einsum("bgsec,bgsd->begcd", dispatch, xg)                 # (B,Eh,nG,C,D)
        # experts shard the model axis when the count divides (true EP); otherwise
        # the group axis keeps it, so the dispatched tensor never de-shards
        xe = constrain(xe, "batch", "experts", "seq", None, None)
        h = jax.nn.silu(jnp.einsum("begcd,edf->begcf", xe, wg))
        h = h * jnp.einsum("begcd,edf->begcf", xe, wu)
        h = constrain(h, "batch", "experts", "seq", None, "ffn")
        ye = jnp.einsum("begcf,efd->begcd", h, wd)                         # (B,Eh,nG,C,D)
        # pin ye to the dispatched layout so the combine-einsum backward does not
        # hit SPMD's involuntary-full-rematerialization path (XLA b/433785288)
        ye = constrain(ye, "batch", "experts", "seq", None, None)
        out = jnp.einsum("bgsec,begcd->bgsd", combine.astype(x.dtype), ye)
        dropped = (mask.sum() - keep.sum()).astype(jnp.int32)
    out = out.reshape(B, S, D)
    if "shared" in p:
        out = out + mlp(p["shared"], x, cfg)

    # Switch-style load-balance loss: E * sum_e f_e * p_e (per group, meaned),
    # over every expert the router scores
    routed = mask if Eh == E else jax.nn.one_hot(idx, E, dtype=jnp.float32)
    f = routed.sum(3).mean(2)        # (B,nG,E): fraction routed (pre-drop)
    pbar = probs.mean(2)             # (B,nG,E)
    aux = AUX_COEF * E * jnp.mean(jnp.sum(f * pbar, axis=-1))
    slots = jnp.stack([mask.sum().astype(jnp.int32),
                       jnp.asarray(B * S * K, jnp.int32), dropped])
    return constrain(out, "batch", "seq", None), aux, slots
