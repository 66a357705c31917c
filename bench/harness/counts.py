"""Operations and bytes of the benchmark's work, from shapes alone.

These are the yardstick: they depend on the sizes of the work (edges,
classes, parameters, tokens), never on how the program does it, so a change
to a kernel cannot move them.
"""
from __future__ import annotations

# --------------------------------------------------------------- CEFT planner
# per relax candidate (edge e, parent class l, child class j): one division
# (data / bw[l, j]), two additions (L[l] + ..., CEFT[parent, l] + ...) and one
# comparison for the min over l
OPS_PER_CANDIDATE = 4


def ceft_ops(n_edges: int, P: int) -> int:
    """Operations of one CEFT sweep: e * P^2 candidates, then one comparison
    per (edge, child class) for the max over parents."""
    return n_edges * P * P * OPS_PER_CANDIDATE + n_edges * P


def ceft_bytes(n: int, n_edges: int, P: int) -> int:
    """Least bytes one plan moves: the float32 cost plane, edge arrays
    (source, target, data: 4 bytes each) and machine (L, bw) in; the float32
    CEFT table and the two int32 predecessor tables out."""
    inputs = 4 * n * P + 12 * n_edges + 4 * (P + P * P)
    outputs = 3 * 4 * n * P
    return inputs + outputs


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least time, bound) on a chip with the given peaks: the larger of
    ops over peak FLOP/s and bytes over peak bandwidth."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# ---------------------------------------------------------- dense decoder LM
def dense_lm_params(cfg: dict) -> dict:
    """Parameter counts of a llama-style decoder (tied or separate head)."""
    d, ff, V, L = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    H, Hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    attn = d * hd * (H + 2 * Hkv) + H * hd * d
    mlp = 3 * d * ff
    per_layer = attn + mlp + 2 * d
    embed = V * d
    head = 0 if cfg["tie_embeddings"] else V * d
    return {"per_layer": per_layer, "layers": L * per_layer,
            "embed": embed, "head": head,
            "total": L * per_layer + embed + head + d}


def dense_lm_token_ops(cfg: dict, context: int, logits: bool) -> int:
    """Model operations for one token at the given context length: two per
    weight of every layer's matrices, the attention scores and weighted sum
    (4 * context * heads * head_dim per layer), and the vocabulary
    projection where the token's logits are computed."""
    p = dense_lm_params(cfg)
    mats = p["layers"] - 2 * cfg["d_model"] * cfg["n_layers"]
    attn = 4 * context * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    head = 2 * cfg["vocab"] * cfg["d_model"] if logits else 0
    return 2 * mats + attn + head


def dense_lm_request_ops(cfg: dict, prompt: int, new: int) -> int:
    """Operations of serving one request: the prompt's tokens (logits for
    the last one only), then new - 1 decoded tokens, each with logits."""
    ops = sum(dense_lm_token_ops(cfg, t + 1, t == prompt - 1)
              for t in range(prompt))
    ops += sum(dense_lm_token_ops(cfg, prompt + i + 1, True)
               for i in range(new - 1))
    return ops


def dense_lm_decode_bytes(cfg: dict, batch: int, cache_len: int,
                          dtype_bytes: int = 2) -> int:
    """Least bytes of one decode step: every weight once at the compute
    dtype, and the key and value cache read for each sequence."""
    p = dense_lm_params(cfg)
    kv = (2 * cfg["n_layers"] * cache_len * cfg["n_kv_heads"]
          * cfg["head_dim"] * dtype_bytes)
    return p["total"] * dtype_bytes + batch * kv
