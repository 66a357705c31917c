"""Weights from a seed, and the plain reference of a Mamba-2 / attention
hybrid with routed and shared experts (Granite 4.0-H).

The configuration is read under the published ``config.json``'s own
keys.  Per layer, by ``layer_types``: an RMSNorm, then a Mamba-2 mixer or causal
GQA attention with no positional encoding; an RMSNorm, then the routed
experts plus a shared SwiGLU expert.  It imports nothing of the program,
and runs in float32 under "highest" matmul precision, one period of layers
at a time through a scan over the stacked weights; ``quant`` rounds every
matmul operand first, which gives the lower-precision control.

- Mamba-2 is the plain recurrence, one token after another: a depthwise
  causal conv over (x, B, C), then per head h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t B_t^T and y_t = h_t C_t + D x_t, then RMSNorm(y * silu(z)) with
  one group, and the output projection.
- Attention scores are scaled by ``attention_multiplier`` (1/128), with
  grouped K/V heads and no rotary positions.
- Routing: a softmax over the top-``num_experts_per_tok`` logits of all
  ``router_experts`` (the published ``num_local_experts``).  Every held
  expert (``num_local_experts`` here, from ``expert_first``) runs on every
  token, weighted by its gate, 0 where the token did not choose it, and the
  shared expert is added.
- Embeddings x ``embedding_multiplier``, both residual branches x
  ``residual_multiplier``, logits / ``logits_scaling``; tied head.

Departures from the published model, which the program shares: only
``num_hidden_layers`` of its 40 layers (``layer_types`` cut alike), and of
each layer's 72 experts only the held ones, so a layer's output is this chip's
share of the routed sum (the other chips' shares are left out, not
stood in for).  The weights are random from the seed, at the scales a
trained model has.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .dense_lm import _identity, _leaves, _rmsnorm, jax_seed
from .dense_lm import fp8_round, served_gaps  # noqa: F401  the shared control and gap

MAMBA, ATTENTION = "mamba", "attention"


def kinds(cfg: dict) -> list[str]:
    """The layer kinds of the shortest period of ``layer_types``; the
    weights are stacked over its repeats, as the program stacks them."""
    types = cfg["layer_types"]
    assert len(types) == cfg["num_hidden_layers"], cfg
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and all(t == types[i % p]
                                       for i, t in enumerate(types)):
            return types[:p]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg: dict) -> dict:
    assert cfg["tie_word_embeddings"] and cfg["mamba_n_groups"] == 1, cfg
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    L = cfg["num_hidden_layers"] // len(kinds(cfg))
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    di = cfg["mamba_expand"] * d
    N, Hs, k = cfg["mamba_d_state"], cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    assert Hs * cfg["mamba_d_head"] == di, cfg
    E, Eh = cfg["router_experts"], cfg["num_local_experts"]
    ff, sff = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    blocks = {}
    for i, kind in enumerate(kinds(cfg)):
        b = {"norm1": (L, d), "norm2": (L, d), "moe": {
            "router": (L, d, E),
            "wg": (L, Eh, d, ff), "wu": (L, Eh, d, ff), "wd": (L, Eh, ff, d),
            "shared": {"wg": (L, d, sff), "wu": (L, d, sff),
                       "wd": (L, sff, d)}}}
        if kind == ATTENTION:
            b["attn"] = {"wq": (L, d, H * hd), "wk": (L, d, Hkv * hd),
                         "wv": (L, d, Hkv * hd), "wo": (L, H * hd, d)}
        else:
            b["ssm"] = {"in_proj": (L, d, 2 * di + 2 * N + Hs),
                        "conv_w": (L, k, di + 2 * N),
                        "conv_b": (L, di + 2 * N),
                        "a_log": (L, Hs), "d_skip": (L, Hs),
                        "dt_bias": (L, Hs), "norm": (L, di),
                        "out_proj": (L, di, d)}
        blocks[f"pos{i}"] = b
    return {"embed": (V, d), "final_norm": (d,), "blocks": blocks}


def make_params(cfg: dict, seed: int):
    """Every weight from the seed in one jitted call on the default device.

    Matrices ~ N(0, 1/fan_in), the embedding ~ N(0, 0.02^2), norm gains and
    the skip D ~ 1 + N(0, 0.1^2), the conv bias ~ N(0, 0.1^2); A ~ -U[1, 16]
    and dt's bias the inverse softplus of logU[1e-3, 1e-1], as Mamba-2
    initializes them."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    paths = [p for p, _ in _leaves(shapes)]

    def build(key):
        def leaf(path, shape):
            k = jax.random.fold_in(key, paths.index(path))
            name = path.rsplit("/", 1)[-1]
            if name == "a_log":
                return jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
            if name == "dt_bias":
                u = jax.random.uniform(k, shape, jnp.float32)
                dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                             + math.log(1e-3))
                return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
            z = jax.random.normal(k, shape, jnp.float32)
            if name in ("norm1", "norm2", "final_norm", "norm", "d_skip"):
                return (1.0 + 0.1 * z).astype(dtype)
            if name == "conv_b":
                return (0.1 * z).astype(dtype)
            if path == "/embed":
                return (0.02 * z).astype(dtype)
            return (z / math.sqrt(shape[-2])).astype(dtype)

        def walk(tree, prefix=""):
            return {k: (walk(v, f"{prefix}/{k}") if isinstance(v, dict)
                        else leaf(f"{prefix}/{k}", v))
                    for k, v in tree.items()}

        return walk(shapes)

    return jax.jit(build)(jax.random.PRNGKey(jax_seed(seed)))


def _swiglu(w, h, mm):
    f32 = jnp.float32
    g = jax.nn.silu(mm(h, w["wg"].astype(f32)))
    return mm(g * mm(h, w["wu"].astype(f32)), w["wd"].astype(f32))


def _mamba(cfg, w, h, mm):
    """The Mamba-2 mixer over one sequence h (S, d), token by token."""
    f32 = jnp.float32
    S, d = h.shape
    di = cfg["mamba_expand"] * d
    N, P = cfg["mamba_d_state"], cfg["mamba_d_head"]
    Hs, k = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    zxbcdt = mm(h, w["in_proj"].astype(f32))
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), f32), xbc])
    conv_w = w["conv_w"].astype(f32)
    conv = sum(pad[i:i + S] * conv_w[i] for i in range(k))
    xbc = jax.nn.silu(conv + w["conv_b"].astype(f32))
    x, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    x = x.reshape(S, Hs, P)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(f32))          # (S, Hs)
    A = -jnp.exp(w["a_log"].astype(f32))                         # (Hs,)

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t                                # (Hs, P)

    _, y = jax.lax.scan(token, jnp.zeros((Hs, P, N), f32), (x, Bm, Cm, dt))
    y = y + x * w["d_skip"].astype(f32)[None, :, None]
    y = y.reshape(S, di) * jax.nn.silu(z)
    y = _rmsnorm(w["norm"].astype(f32), y, cfg["rms_norm_eps"])
    return mm(y, w["out_proj"].astype(f32))


def _attention(cfg, w, h, mm, quant):
    """Causal GQA over one sequence h (S, d), no positional encoding."""
    f32 = jnp.float32
    S = h.shape[0]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    q = mm(h, w["wq"].astype(f32)).reshape(S, H, hd)
    k = jnp.repeat(mm(h, w["wk"].astype(f32)).reshape(S, Hkv, hd),
                   H // Hkv, axis=1)
    v = jnp.repeat(mm(h, w["wv"].astype(f32)).reshape(S, Hkv, hd),
                   H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", quant(q), quant(k)) * cfg["attention_multiplier"]
    pos = jnp.arange(S)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", quant(p), quant(v)).reshape(S, H * hd)
    return mm(a, w["wo"].astype(f32))


def _experts(cfg, w, h, mm, quant):
    """The held experts' share of the routed sum plus the shared expert."""
    f32 = jnp.float32
    K, first = cfg["num_experts_per_tok"], cfg["expert_first"]
    Eh = cfg["num_local_experts"]
    logits = mm(h, w["router"].astype(f32))                      # (S, E)
    top, idx = jax.lax.top_k(logits, K)
    gates = jax.nn.softmax(top, axis=-1)                         # (S, K)
    held = first + jnp.arange(Eh)
    weight = jnp.sum(gates[:, :, None] * (idx[:, :, None] == held), axis=1)
    g = jax.nn.silu(jnp.einsum("sd,edf->sef", quant(h),
                               quant(w["wg"].astype(f32))))
    u = jnp.einsum("sd,edf->sef", quant(h), quant(w["wu"].astype(f32)))
    y = jnp.einsum("sef,efd->sed", quant(g * u), quant(w["wd"].astype(f32)))
    routed = jnp.sum(weight[:, :, None] * y, axis=1)
    return routed + _swiglu(w["shared"], h, mm)


def forward_logits(cfg: dict, params, tokens, positions_out, quant=_identity):
    """Logits (len(positions_out), vocab) of one sequence at the given
    positions, computed period by period in float32 ("highest").

    tokens: (S,) int32; causal, so padding after the last position that is
    read changes nothing."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    f32 = jnp.float32
    layer_kinds = kinds(cfg)

    def mm(a, b):
        return quant(a) @ quant(b)

    x = params["embed"][tokens].astype(f32) * cfg["embedding_multiplier"]

    def period(x, ws):
        for i, kind in enumerate(layer_kinds):
            w = ws[f"pos{i}"]
            h = _rmsnorm(w["norm1"].astype(f32), x, eps)
            a = (_attention(cfg, w["attn"], h, mm, quant)
                 if kind == ATTENTION else _mamba(cfg, w["ssm"], h, mm))
            x = x + res * a
            h = _rmsnorm(w["norm2"].astype(f32), x, eps)
            x = x + res * _experts(cfg, w["moe"], h, mm, quant)
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(period, x, params["blocks"])
        x = _rmsnorm(params["final_norm"].astype(f32), x, eps)[positions_out]
        return mm(x, params["embed"].T.astype(f32)) / cfg["logits_scaling"]
