"""Decoder-LM stack: scanned period-blocks covering dense / MoE / SSM / hybrid
/ VLM families with one code path.

The layer pattern (configs.base.layer_pattern) gives the (sequence-mixer,
channel-mixer) pair per *period position*; parameters are stacked over periods
and the stack runs as one ``lax.scan`` -> HLO size is O(period), not O(layers)
(llama3-405b compiles as a 126-iteration scan; jamba as 4 periods of 8
heterogeneous layers unrolled).

Granite-style multipliers (``embedding_mult``, ``residual_mult``,
``logits_div``; ``attn_scale`` in layers.py) apply only where a
configuration sets them, so other models trace to the programs they had.
A model with an expert layer carries ``SLOTS`` in its cache: the (token,
expert) slot counts of ``moe`` (held, routed, dropped) summed since the
prefill, counted on the device.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import PSpec, constrain, tree_map_pspec
from .layers import (
    attn_decode,
    attn_prefill,
    attn_specs,
    mlp,
    mlp_specs,
    rmsnorm,
    rmsnorm_spec,
    rope_cos_sin,
)
from .moe import moe, moe_specs
from .ssm import ssd_decode, ssd_prefill, ssm_specs

SLOTS = "moe_slots"


def stack_specs(tree, n: int):
    return tree_map_pspec(
        lambda _, p: PSpec((n,) + p.shape, ("layers",) + p.logical, p.init), tree
    )


def block_specs(cfg: ArchConfig) -> dict:
    """One period's parameters, keyed pos{i}."""
    out: dict[str, Any] = {}
    for i, (mixer, channel) in enumerate(cfg.layer_pattern()):
        b: dict[str, Any] = {"norm1": rmsnorm_spec(cfg.d_model)}
        if mixer == "attn":
            b["attn"] = attn_specs(cfg)
        else:
            b["ssm"] = ssm_specs(cfg)
        if channel != "none":
            b["norm2"] = rmsnorm_spec(cfg.d_model)
            b["mlp" if channel == "mlp" else "moe"] = (
                mlp_specs(cfg) if channel == "mlp" else moe_specs(cfg)
            )
        out[f"pos{i}"] = b
    return out


def model_specs(cfg: ArchConfig) -> dict:
    d, V = cfg.d_model, cfg.vocab
    specs: dict[str, Any] = {
        "embed": PSpec((V, d), ("vocab", "embed_d"), init="embed"),
        "final_norm": rmsnorm_spec(d),
        "blocks": stack_specs(block_specs(cfg), cfg.n_layers // cfg.period),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = PSpec((d, V), ("embed_d", "vocab"))
    return specs


def cache_specs(cfg: ArchConfig, batch: int, seq: int) -> dict:
    """Decode-cache pytree as PSpecs (shared by init-zeros / abstract / shardings).

    Attention caches are (periods, B, S, Hkv, hd) with the sequence axis
    sharded over `model` (decode-SP); SWA caches are bounded by the window.
    SSM caches are O(1) in sequence.
    """
    n_per = cfg.n_layers // cfg.period
    out: dict[str, Any] = {}
    for i, (mixer, _) in enumerate(cfg.layer_pattern()):
        if mixer == "attn":
            sc = min(seq, cfg.window) if cfg.window else seq
            kv = PSpec(
                (n_per, batch, sc, cfg.n_kv_heads, cfg.hd),
                ("layers", "cache_batch", "cache_seq", "heads", "cache_hd"),
                init="zeros", dtype=cfg.compute_dtype,
            )
            out[f"pos{i}"] = {"k": kv, "v": kv}
        else:
            H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            out[f"pos{i}"] = {
                "ssm": PSpec((n_per, batch, H, P, N),
                             ("layers", "cache_batch", "ssm_inner", "none", "none"),
                             init="zeros", dtype="float32"),
                "conv": PSpec((n_per, batch, cfg.ssm_conv - 1, cfg.d_inner + 2 * N),
                              ("layers", "cache_batch", "none", "ssm_inner"),
                              init="zeros", dtype=cfg.compute_dtype),
            }
    if any(c == "moe" for _, c in cfg.layer_pattern()):
        out[SLOTS] = PSpec((3,), ("none",), init="zeros", dtype="int32")
    return out


def _residual(cfg: ArchConfig, x, branch):
    if cfg.residual_mult != 1.0:
        branch = branch * cfg.residual_mult
    return x + branch


# ---------------------------------------------------------------------- forward
def embed_tokens(params, cfg: ArchConfig, tokens=None, embeds=None):
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embedding_mult != 1.0:
        x = x * cfg.embedding_mult
    return constrain(x, "batch", "seq", None)


def unembed(params, cfg: ArchConfig, x):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    if cfg.logits_div != 1.0:
        logits = logits / cfg.logits_div
    return constrain(logits, "batch", "seq", "vocab")


def _period_fwd(cfg: ArchConfig, pp, x, cos_sin, dropless: bool = False):
    """Full-seq forward through one period; returns (x, aux, cache_updates,
    slot counts of its expert layers (None without one))."""
    aux = jnp.zeros((), jnp.float32)
    slots = None
    cache_out = {}
    for i, (mixer, channel) in enumerate(cfg.layer_pattern()):
        b = pp[f"pos{i}"]
        h = rmsnorm(b["norm1"], x, cfg.norm_eps)
        if mixer == "attn":
            a, (k, v) = attn_prefill(b["attn"], h, cfg, cos_sin, window=cfg.window)
            cache_out[f"pos{i}"] = {"k": k, "v": v}
        else:
            a, st = ssd_prefill(b["ssm"], h, cfg)
            cache_out[f"pos{i}"] = st
        x = _residual(cfg, x, a)
        if channel != "none":
            h2 = rmsnorm(b["norm2"], x, cfg.norm_eps)
            if channel == "mlp":
                x = _residual(cfg, x, mlp(b["mlp"], h2, cfg))
            else:
                y, a_loss, n = moe(b["moe"], h2, cfg, dropless)
                x = _residual(cfg, x, y)
                aux = aux + a_loss
                slots = n if slots is None else slots + n
        x = constrain(x, "batch", "seq", None)
    return x, aux, cache_out, slots


def forward_full(params, cfg: ArchConfig, *, tokens=None, embeds=None,
                 positions=None, want_cache: bool = False,
                 dropless: bool = False):
    """Training / prefill forward.  Returns (hidden (B,S,D), aux, cache|None).

    ``dropless`` routes every (token, expert) slot (serving prefill; see
    moe.py); the cache then also holds the expert layers' ``SLOTS``."""
    x = embed_tokens(params, cfg, tokens, embeds)
    B, S, _ = x.shape
    has_attn = any(m == "attn" for m, _ in cfg.layer_pattern())
    cos_sin = None
    if has_attn and cfg.use_rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        cos_sin = rope_cos_sin(cfg, positions)

    def body(carry, pp):
        x, aux = carry
        x2, a, cache, slots = _period_fwd(cfg, pp, x, cos_sin, dropless)
        return (x2, aux + a), ((cache if want_cache else 0), slots)

    body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
    (x, aux), (caches, slots) = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if not want_cache:
        return x, aux, None
    if slots is not None:
        caches[SLOTS] = slots.sum(0)
    return x, aux, caches


def decode_step(params, cfg: ArchConfig, cache, *, tokens=None, embeds=None,
                pos=None, positions=None):
    """One-token decode.  tokens: (B, 1); pos: scalar int32 (current position).
    Returns (logits (B, 1, V), new_cache)."""
    x = embed_tokens(params, cfg, tokens, embeds)
    B = x.shape[0]
    has_attn = any(m == "attn" for m, _ in cfg.layer_pattern())
    cos_sin = None
    if has_attn and cfg.use_rope:
        if positions is None:
            positions = jnp.broadcast_to(pos.astype(jnp.int32), (B, 1))
        cos_sin = rope_cos_sin(cfg, positions)

    def body(x, scanned):
        pp, pc = scanned
        new_pc = {}
        slots = None
        for i, (mixer, channel) in enumerate(cfg.layer_pattern()):
            b = pp[f"pos{i}"]
            h = rmsnorm(b["norm1"], x, cfg.norm_eps)
            if mixer == "attn":
                a, nc = attn_decode(b["attn"], h, cfg, pc[f"pos{i}"], pos,
                                    cos_sin, window=cfg.window)
            else:
                a, nc = ssd_decode(b["ssm"], h, cfg, pc[f"pos{i}"])
            new_pc[f"pos{i}"] = nc
            x = _residual(cfg, x, a)
            if channel != "none":
                h2 = rmsnorm(b["norm2"], x, cfg.norm_eps)
                if channel == "mlp":
                    x = _residual(cfg, x, mlp(b["mlp"], h2, cfg))
                else:
                    y, _, n = moe(b["moe"], h2, cfg)
                    x = _residual(cfg, x, y)
                    slots = n if slots is None else slots + n
        return x, (new_pc, slots)

    layers = {k: v for k, v in cache.items() if k != SLOTS}
    x, (new_cache, slots) = jax.lax.scan(body, x, (params["blocks"], layers))
    if SLOTS in cache:
        new_cache[SLOTS] = cache[SLOTS] + slots.sum(0).astype(
            cache[SLOTS].dtype)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return unembed(params, cfg, x), new_cache


# ------------------------------------------------------------------------- loss
def xent_loss(params, cfg: ArchConfig, hidden, labels):
    """Chunked softmax cross-entropy: the (B, S, V) logits are never
    materialized; each sequence chunk computes its own fp32 logits inside a
    rematerialized scan step."""
    B, S, D = hidden.shape
    c = min(cfg.loss_chunk, S)
    pad = (-S) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = (S + pad) // c
    hc = jnp.moveaxis(hidden.reshape(B, n, c, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)

    @jax.checkpoint
    def step(carry, xs):
        h, l = xs
        logits = unembed(params, cfg, h)                       # (B,c,V) fp32
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1
        )[..., 0]
        valid = (l >= 0).astype(jnp.float32)
        loss = ((lse - gold) * valid).sum()
        return (carry[0] + loss, carry[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)
