"""Pure-JAX model layers: norms, RoPE / M-RoPE, memory-linear attention
(online-softmax chunking), GQA/SWA, decode-step attention, MLPs.

Layout conventions:
  activations x : (B, S, D)
  q heads       : (B, Hkv, G, S, hd)  with G = Hq // Hkv (GQA groups)
  kv            : (B, S, Hkv, hd)     (cache layout: seq second for decode-SP)
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import PSpec, constrain

NEG_INF = -1e30


def cast(x, dtype_str):
    return x.astype(jnp.dtype(dtype_str))


# ------------------------------------------------------------------------ norms
def rmsnorm_spec(d: int) -> PSpec:
    return PSpec((d,), ("none",), init="ones")


def rmsnorm(w, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


# ------------------------------------------------------------------------- RoPE
def _rope_angles(positions, n_freq: int, theta: float):
    """positions (..., S) -> cos/sin (..., S, n_freq)."""
    inv = theta ** (-jnp.arange(0, n_freq, dtype=jnp.float32) / n_freq)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def rope_cos_sin(cfg: ArchConfig, positions):
    """positions: (B, S) int32, or (3, B, S) for M-RoPE.

    M-RoPE (Qwen2-VL): the head_dim/2 frequencies are split into
    (temporal, h, w) sections, each rotated by its own position id.
    """
    half = cfg.hd // 2
    if cfg.mrope:
        assert positions.ndim == 3, "M-RoPE wants (3, B, S) positions"
        secs = cfg.mrope_sections
        assert sum(secs) == half, (secs, half)
        # per-frequency position: frequencies [0:t) use temporal ids, etc.
        rep = jnp.repeat(jnp.arange(3), jnp.asarray(secs), total_repeat_length=half)
        pos = positions[rep, :, :]                      # (half, B, S)
        pos = jnp.moveaxis(pos, 0, -1)                  # (B, S, half)
        inv = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32) * inv
        return jnp.cos(ang), jnp.sin(ang)
    return _rope_angles(positions, half, cfg.rope_theta)  # (B, S, half)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2) (split-half convention)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def sinusoidal_embedding(S: int, d: int, offset: int = 0):
    """Whisper-style absolute sinusoidal positions (B-broadcastable (S, d))."""
    pos = jnp.arange(offset, offset + S, dtype=jnp.float32)[:, None]
    inv = 1e4 ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2 - 1 + 1e-9))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# -------------------------------------------------------------------- attention
def attn_specs(cfg: ArchConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "wq": PSpec((d, hq * hd), ("embed", "qkv")),
        "wk": PSpec((d, hkv * hd), ("embed", "qkv")),
        "wv": PSpec((d, hkv * hd), ("embed", "qkv")),
        "wo": PSpec((hq * hd, d), ("qkv", "embed")),
    }


def qkv_proj(p, x, cfg: ArchConfig, cos_sin=None):
    """x (B,S,D) -> q (B,S,Hq,hd), k,v (B,S,Hkv,hd), RoPE applied."""
    B, S, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, hq, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, hkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, hkv, hd)
    if cos_sin is not None:
        cos, sin = cos_sin
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def chunked_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_len: Optional[int] = None,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    scale: Optional[float] = None,
):
    """Flash-style online-softmax attention in pure XLA: O(S) memory.

    q: (B, Hkv, G, Sq, hd); k, v: (B, Sk, Hkv, hd).
    kv_len: number of valid keys (<= Sk) for padded caches.
    scale: softmax scale (default 1/sqrt(hd)).
    Never materializes (Sq, Sk); the working set is (qc, kc) score tiles --
    exactly the shape XLA:TPU fuses into VMEM-resident loops.
    """
    B, Hk, G, Sq, hd = q.shape
    Sk = k.shape[1]
    kv_len = Sk if kv_len is None else kv_len
    qc = min(q_chunk, Sq)
    kc = min(k_chunk, Sk)
    pad_q = (-Sq) % qc
    pad_k = (-Sk) % kc
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (Sq + pad_q) // qc, (Sk + pad_k) // kc
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    kT = jnp.moveaxis(k, 1, 3)  # (B, Hkv, hd, Skp)
    vT = jnp.moveaxis(v, 1, 2)  # (B, Hkv, Skp, hd)

    q_blocks = jnp.moveaxis(q.reshape(B, Hk, G, nq, qc, hd), 3, 0)  # (nq,B,Hk,G,qc,hd)

    def per_q(qi, qb):
        qpos = q_offset + qi * qc + jnp.arange(qc)

        def per_k(carry, ki):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kT, ki * kc, kc, axis=3)
            vb = jax.lax.dynamic_slice_in_dim(vT, ki * kc, kc, axis=2)
            s = jnp.einsum("bhgqd,bhdk->bhgqk", qb, kb) * scale
            s = s.astype(jnp.float32)
            kpos = ki * kc + jnp.arange(kc)
            mask = kpos[None, :] < kv_len
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m2 = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m2)
            pexp = jnp.exp(s - m2[..., None])
            l2 = l * alpha + pexp.sum(axis=-1)
            acc2 = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", pexp.astype(vb.dtype), vb
            ).astype(jnp.float32)
            return (m2, l2, acc2), None

        init = (
            jnp.full((B, Hk, G, qc), NEG_INF, jnp.float32),
            jnp.zeros((B, Hk, G, qc), jnp.float32),
            jnp.zeros((B, Hk, G, qc, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(per_k, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda args: per_q(*args), (jnp.arange(nq), q_blocks))
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hk, G, Sq + pad_q, hd)
    return out[:, :, :, :Sq]


def attn_scale(cfg: ArchConfig) -> float:
    """The softmax scale: the configuration's, else 1/sqrt(head_dim)."""
    return cfg.attn_scale or 1.0 / math.sqrt(cfg.hd)


def attn_prefill(p, x, cfg: ArchConfig, cos_sin, *, window: int = 0, causal=True):
    """Full-sequence attention; returns (out, (k, v)) for cache seeding."""
    B, S, D = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k, v = qkv_proj(p, x, cfg, cos_sin)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "heads", None)
    qh = jnp.moveaxis(q.reshape(B, S, hkv, hq // hkv, hd), 1, 3)  # (B,Hkv,G,S,hd)
    out = chunked_attention(qh, k, v, causal=causal, window=window,
                            scale=attn_scale(cfg))
    out = jnp.moveaxis(out, 3, 1).reshape(B, S, hq * hd)
    out = out @ p["wo"].astype(x.dtype)
    return constrain(out, "batch", "seq", None), (k, v)


def attn_decode(p, x, cfg: ArchConfig, cache, pos, cos_sin, *, window: int = 0):
    """One-token step: update cache at pos (ring slot for SWA), attend.

    x: (B, 1, D); cache: dict(k=(B, Sc, Hkv, hd), v=...); pos: scalar int32.
    """
    B, _, D = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k, v = qkv_proj(p, x, cfg, cos_sin)
    Sc = cache["k"].shape[1]
    # window (static): ring-buffer slot; else absolute position
    slot = (pos % Sc if window > 0 else pos).astype(jnp.int32)
    # align the one-token update with the cache layout BEFORE the
    # dynamic_update_slice: a sharding mismatch here makes SPMD rematerialize
    # the whole cache (measured 292 MB/layer on llama3-405b decode, §Perf it3)
    k = constrain(k.astype(cache["k"].dtype), "cache_batch", None, "heads", "cache_hd")
    v = constrain(v.astype(cache["v"].dtype), "cache_batch", None, "heads", "cache_hd")
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    qh = jnp.moveaxis(q.reshape(B, 1, hkv, hq // hkv, hd), 1, 3)  # (B,Hkv,G,1,hd)
    s = jnp.einsum("bhgqd,bshd->bhgqs", qh, ck.astype(qh.dtype)) * attn_scale(cfg)
    s = s.astype(jnp.float32)
    idx = jnp.arange(Sc)
    valid = idx < jnp.minimum(pos + 1, Sc) if window > 0 else idx <= pos
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhgqs,bshd->bhgqd", w, cv.astype(x.dtype))
    out = jnp.moveaxis(out, 3, 1).reshape(B, 1, hq * hd)
    out = out @ p["wo"].astype(x.dtype)
    return out, {"k": ck, "v": cv}


# ------------------------------------------------------------------------- MLPs
def mlp_specs(cfg: ArchConfig, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_style == "swiglu":
        return {
            "wg": PSpec((d, ff), ("embed", "ffn")),
            "wu": PSpec((d, ff), ("embed", "ffn")),
            "wd": PSpec((ff, d), ("ffn", "embed")),
        }
    return {
        "w1": PSpec((d, ff), ("embed", "ffn")),
        "w2": PSpec((ff, d), ("ffn", "embed")),
    }


def mlp(p, x, cfg: ArchConfig):
    if cfg.mlp_style == "swiglu":
        h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wu"].astype(x.dtype))
        h = constrain(h, "batch", "seq", "ffn")
        return h @ p["wd"].astype(x.dtype)
    h = jax.nn.gelu(x @ p["w1"].astype(x.dtype))
    h = constrain(h, "batch", "seq", "ffn")
    return h @ p["w2"].astype(x.dtype)
