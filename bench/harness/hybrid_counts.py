"""Parameters and least bytes of the hybrid reference's work, from shapes
alone (the configuration's published keys, as ``hybrid_lm`` reads them).

Like ``counts.py`` these are the yardstick: a change to how the program
computes cannot move them.
"""
from __future__ import annotations

from .hybrid_lm import ATTENTION, head_dim

PARAM_BYTES = 2      # bfloat16 weights, K/V and conv state
STATE_BYTES = 4      # the SSM state is kept in float32


def layer_params(cfg: dict, kind: str) -> dict:
    """Parameters of one layer, by part: the sequence mixer, the held
    experts, the shared expert, the router and the two norm gains."""
    d = cfg["hidden_size"]
    if kind == ATTENTION:
        H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      head_dim(cfg))
        mixer = d * hd * (H + 2 * Hkv) + H * hd * d
    else:
        di, N = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
        Hs, k = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
        conv = di + 2 * N
        mixer = (d * (2 * di + 2 * N + Hs) + k * conv + conv + 3 * Hs + di
                 + di * d)
    expert = 3 * d * cfg["intermediate_size"]
    return {"mixer": mixer,
            "experts": cfg["num_local_experts"] * expert,
            "expert": expert,
            "shared": 3 * d * cfg["shared_intermediate_size"],
            "router": d * cfg["router_experts"],
            "norms": 2 * d}


def params(cfg: dict) -> dict:
    """{"layers", "experts", "embed", "total"}: parameters of the stack, of
    its held experts, of the tied embedding, and all of them."""
    layers = experts = 0
    for kind in cfg["layer_types"]:
        p = layer_params(cfg, kind)
        layers += sum(v for k, v in p.items() if k != "expert")
        experts += p["experts"]
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    return {"layers": layers, "experts": experts, "embed": embed,
            "total": layers + embed + cfg["hidden_size"]}


def expected_held_experts(cfg: dict, batch: int) -> float:
    """Held experts of one layer that some of ``batch`` tokens choose, in
    expectation under uniform routing: each token leaves a given expert out
    with probability 1 - top_k / experts."""
    E, K = cfg["router_experts"], cfg["num_experts_per_tok"]
    return cfg["num_local_experts"] * (1.0 - ((E - K) / E) ** batch)


def state_bytes(cfg: dict) -> int:
    """One sequence's SSM state and conv window over every Mamba layer."""
    d = cfg["hidden_size"]
    di, N = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    per = (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * N * STATE_BYTES
           + (cfg["mamba_d_conv"] - 1) * (di + 2 * N) * PARAM_BYTES)
    return per * sum(t != ATTENTION for t in cfg["layer_types"])


def kv_bytes(cfg: dict, length: int) -> int:
    """One sequence's keys and values at ``length`` over every attention
    layer."""
    n = sum(t == ATTENTION for t in cfg["layer_types"])
    return (2 * n * length * cfg["num_key_value_heads"] * head_dim(cfg)
            * PARAM_BYTES)


def decode_bytes(cfg: dict, batch: int, length: int) -> float:
    """Least bytes of one decode step of ``batch`` sequences at cache
    ``length``: every weight outside the routed experts once, the held
    experts the batch is expected to touch, each sequence's SSM state read
    and written, and its K/V read."""
    p = params(cfg)
    per_expert = layer_params(cfg, cfg["layer_types"][0])["expert"]
    touched = expected_held_experts(cfg, batch) * per_expert * len(
        cfg["layer_types"])
    weights = (p["total"] - p["experts"] + touched) * PARAM_BYTES
    return weights + batch * (2 * state_bytes(cfg) + kv_bytes(cfg, length))
