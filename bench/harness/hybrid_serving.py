"""Runner for a Mamba-2 / attention hybrid with experts (Granite 4.0-H): one
model engine behind the program's CEFT router, as ``serving`` runs it.

The window, the drain and the checks are ``serving.run``'s own, run with
this module's ``build`` and ``compare`` in place of the dense ones: the
weights and the plain reference come from ``hybrid_lm``, and the
configuration is read under the published ``config.json``'s keys.  One
check is added: ``dropped_slots``, the (token, expert) slots the engine's
expert layers dropped over the run (warm-up included), from the engine's
own counter.

And one comparison: ``logit_err``, the engine's own logits against the
reference's at every served position of the checked requests.  With
random weights the tied head and the x12 embedding make each position's
best token stand far above the rest, so no rounding moves the served
token and ``logit_gap`` reads 0 even for a float8 reference; ``logit_err``
reads the logits themselves: per position, the largest difference over
the vocabulary in units of the reference logits' root mean square, the
widest over positions.  The engine's logits come from ``Engine.score``:
its prefill over the prompt, then its decode step through the cache over
the tokens it served, the same programs the window ran, at batch 1.
"""
from __future__ import annotations

import types

import numpy as np

from . import hybrid_lm, serving

# published key -> the program's ArchConfig field
ARCH_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "shared_intermediate_size": "shared_ff",
    "vocab_size": "vocab", "router_experts": "n_experts",
    "num_local_experts": "experts_held", "expert_first": "expert_first",
    "num_experts_per_tok": "top_k", "mamba_d_state": "ssm_state",
    "mamba_expand": "ssm_expand", "mamba_d_head": "ssm_head_dim",
    "mamba_n_heads": "ssm_heads", "mamba_d_conv": "ssm_conv",
    "mamba_chunk_size": "ssm_chunk", "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps", "embedding_multiplier": "embedding_mult",
    "residual_multiplier": "residual_mult",
    "attention_multiplier": "attn_scale", "logits_scaling": "logits_div",
    "param_dtype": "param_dtype", "compute_dtype": "compute_dtype",
}


def arch_view(arch) -> dict:
    """The program's architecture under the published keys."""
    out = {k: getattr(arch, f) for k, f in ARCH_KEYS.items()}
    pattern = arch.layer_pattern()
    out["layer_types"] = [
        "attention" if pattern[i % arch.period][0] == "attn" else "mamba"
        for i in range(arch.n_layers)]
    out["head_dim"] = arch.hd
    out["experts_every_layer"] = all(c == "moe" for _, c in pattern)
    out["position_embedding_type"] = "rope" if arch.use_rope else "nope"
    out["hidden_act"] = "silu" if arch.mlp_style == "swiglu" else "gelu"
    out["mamba_n_groups"] = 1        # the program's B and C are shared
    return out


def check_model(cfg: dict, arch) -> None:
    """The program must run the configuration as this file states it."""
    want = {k: cfg[k] for k in ARCH_KEYS}
    want.update(layer_types=cfg["layer_types"],
                head_dim=hybrid_lm.head_dim(cfg), experts_every_layer=True,
                position_embedding_type=cfg["position_embedding_type"],
                hidden_act=cfg["hidden_act"],
                mamba_n_groups=cfg["mamba_n_groups"])
    got = arch_view(arch)
    diff = {k: (v, got[k]) for k, v in want.items() if v != got[k]}
    if diff:
        raise ValueError(f"the program's {cfg['arch']} differs from "
                         f"the configuration (file, program): {diff}")


def build(ctx, prog):
    cfg = ctx.config
    arch = prog.arch(cfg["arch"])
    check_model(cfg, arch)
    params = hybrid_lm.make_params(cfg, ctx.seed)
    import jax

    jax.block_until_ready(params)
    engine = prog.Engine(arch, params=params, profile=cfg["profile"])
    ctx.engine = engine                  # kept for logit_err after the window
    pool = prog.Pool([prog.Worker(f"{cfg['arch']}:{cfg['profile']}",
                                  profile=cfg["profile"], engine=engine)],
                     probe="static", high_water=cfg["max_batch"])
    router = prog.Router(pool, max_batch=cfg["max_batch"])
    return params, engine, pool, router


def _with(fn, **names):
    """``serving``'s function ``fn`` reading ``names`` in place of its own
    module's."""
    return types.FunctionType(fn.__code__, {**vars(serving), **names},
                              fn.__name__, fn.__defaults__, fn.__closure__)


compare = _with(serving.compare, dense_lm=hybrid_lm)
_serve = _with(serving.run, build=build, compare=compare)


def relative_error(ref, got):
    """Per position (rows), the largest |got - ref| over the vocabulary in
    units of the reference's root mean square."""
    import jax.numpy as jnp

    rms = jnp.sqrt(jnp.mean(ref * ref, axis=-1))
    return jnp.max(jnp.abs(got - ref), axis=-1) / rms


def logit_err(cfg: dict, params, reqs: list, logits_of) -> float:
    """Widest ``relative_error`` over every served position of the given
    requests, of the logits ``logits_of(req, padded tokens, positions)``
    (n_positions, vocab) against the reference's."""
    import jax

    S = max(r["prompt"].size + r["max_new"] for r in reqs)
    N = max(r["max_new"] for r in reqs)
    ref_fn = jax.jit(lambda p, t, q: hybrid_lm.forward_logits(cfg, p, t, q))
    worst = 0.0
    for r in reqs:
        plen, toks = r["prompt"].size, r["tokens"]
        k = serving.served_len(toks, plen)
        padded = np.zeros(S, np.int32)
        padded[:toks.size] = toks
        pos = np.full(N, plen - 1, np.int32)
        pos[:k] = np.arange(plen - 1, plen - 1 + k)
        ref = ref_fn(params, padded, pos)[:k]
        got = logits_of(r, padded, pos)[:k]
        worst = max(worst, float(relative_error(ref, got).max()))
    return worst


def engine_logits(engine):
    """``logits_of`` for ``logit_err``: the engine's own, teacher-forced
    over the tokens it served, at the shapes the window compiled."""
    def logits_of(r, padded, pos):
        plen = r["prompt"].size
        fed = r["tokens"][None, plen:plen + r["max_new"] - 1]
        return engine.score(r["prompt"][None], fed)[0]
    return logits_of


def run(ctx) -> dict:
    out = _serve(ctx)
    engine, limits = ctx.engine, ctx.config["limits"]
    del ctx.engine
    params, checked = out["sample"]
    err = (logit_err(ctx.config, params, checked, engine_logits(engine))
           if checked else float("inf"))
    slots = dict(engine.slots)
    del engine
    out["checks"] += [("logit_err", err, limits["logit_err"]),
                      ("dropped_slots", slots["dropped"],
                       limits["dropped_slots"])]
    out["correct"] = all(v <= lim for _, v, lim in out["checks"])
    out["rec"]["expert_slots"] = slots
    return out
