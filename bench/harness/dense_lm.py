"""Weights from a seed, and the plain reference of a llama-style decoder.

The reference is the configuration's forward pass in straightforward
``jax.numpy``: RMSNorm, rotary positions (split-half), causal multi-head
attention, SwiGLU, tied or separate vocabulary head.  It imports nothing of
the program.  It runs in float32 under "highest" matmul precision, one layer
at a time through a scan over the stacked weights; ``quant`` rounds every
matmul operand first, which gives the lower-precision control.

The weights are the benchmark's own: one jitted call draws every leaf from
the seed, on the device, in the configuration's parameter dtype, laid out as
the program's parameter tree expects them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any non-negative seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def param_shapes(cfg: dict) -> dict:
    d, ff, V, L = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    out = {
        "embed": (V, d),
        "final_norm": (d,),
        "blocks": {"pos0": {
            "norm1": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
                     "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d)},
            "norm2": (L, d),
            "mlp": {"wg": (L, d, ff), "wu": (L, d, ff), "wd": (L, ff, d)},
        }},
    }
    if not cfg["tie_embeddings"]:
        out["unembed"] = (d, V)
    return out


def _leaves(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, f"{prefix}/{k}")
        else:
            yield f"{prefix}/{k}", v


def make_params(cfg: dict, seed: int):
    """Every weight from the seed in one jitted call on the default device.

    Matrices ~ N(0, 1/fan_in), the embedding ~ N(0, 0.02^2), norm gains
    ~ 1 + N(0, 0.1^2): random, but at the scales a trained model has."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    paths = [p for p, _ in _leaves(shapes)]

    def build(key):
        def leaf(path, shape):
            k = jax.random.fold_in(key, paths.index(path))
            z = jax.random.normal(k, shape, jnp.float32)
            if path.endswith("norm1") or path.endswith("norm2") \
                    or path.endswith("final_norm"):
                return (1.0 + 0.1 * z).astype(dtype)
            if path == "/embed":
                return (0.02 * z).astype(dtype)
            return (z / math.sqrt(shape[-2])).astype(dtype)

        def walk(tree, prefix=""):
            return {k: (walk(v, f"{prefix}/{k}") if isinstance(v, dict)
                        else leaf(f"{prefix}/{k}", v))
                    for k, v in tree.items()}

        return walk(shapes)

    return jax.jit(build)(jax.random.PRNGKey(jax_seed(seed)))


def fp8_round(x):
    """Per-tensor scaled float8 (e4m3) rounding, back in float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _identity(x):
    return x


def _rmsnorm(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x (S, H, hd), split-half rotation by position."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def forward_logits(cfg: dict, params, tokens, positions_out, quant=_identity):
    """Logits (len(positions_out), vocab) of one sequence at the given
    positions, computed layer by layer in float32 ("highest").

    tokens: (S,) int32; causal, so padding after the last position that is
    read changes nothing."""
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    f32 = jnp.float32

    def mm(a, b):
        return quant(a) @ quant(b)

    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(f32)

    def layer(x, w):
        h = _rmsnorm(w["norm1"].astype(f32), x, eps)
        q = mm(h, w["attn"]["wq"].astype(f32)).reshape(S, H, hd)
        k = mm(h, w["attn"]["wk"].astype(f32)).reshape(S, Hkv, hd)
        v = mm(h, w["attn"]["wv"].astype(f32)).reshape(S, Hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", quant(q), quant(k)) / math.sqrt(hd)
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", quant(p), quant(v)).reshape(S, H * hd)
        x = x + mm(a, w["attn"]["wo"].astype(f32))
        h = _rmsnorm(w["norm2"].astype(f32), x, eps)
        g = jax.nn.silu(mm(h, w["mlp"]["wg"].astype(f32)))
        u = mm(h, w["mlp"]["wu"].astype(f32))
        return x + mm(g * u, w["mlp"]["wd"].astype(f32)), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["blocks"]["pos0"])
        x = _rmsnorm(params["final_norm"].astype(f32), x, eps)[positions_out]
        head = (params["embed"].T if cfg["tie_embeddings"]
                else params["unembed"]).astype(f32)
        return mm(x, head)


def served_gaps(logits, served):
    """Per position: how far the served token's logit lies below the best
    logit.  logits (n, vocab) float32, served (n,) int32."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best - got
