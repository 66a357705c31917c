"""Batched serving engine: prefill once, decode greedily with per-sequence
EOS stop, KV cache reconciliation between the prefill and decode layouts
(including SWA ring-buffer packing)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models.common import (
    ShardingProfile,
    active_profile,
    init_params,
    resolve_profile,
    sharding_profile,
)
from ..models.model import Model, build


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    eos_id: int = 1


class Engine:
    def __init__(self, cfg: ArchConfig, params=None, seed: int = 0,
                 profile: str | ShardingProfile | None = None):
        self.cfg = cfg
        # Pin the sharding profile at construction (default: whatever is
        # active right now).  Every trace -- init here, prefill/decode in
        # generate() -- re-enters it, so two engines with different profiles
        # in one process each resolve their own rules, never each other's.
        self.profile = (resolve_profile(profile) if profile is not None
                        else active_profile())
        self.model = build(cfg)
        with sharding_profile(self.profile):
            self.params = params if params is not None else self.model.init(
                jax.random.PRNGKey(seed))
        self._decode = jax.jit(self.model.decode)
        self._prefill = jax.jit(self.model.prefill)

    # ------------------------------------------------------------------ cache
    def _seed_cache(self, prefill_cache, B: int, total: int, prompt: int):
        """Pack the prefill K/V (length=prompt) into the decode layout
        (length=total or window); SSM states pass through unchanged."""
        cfg = self.cfg
        target = init_params(self.model.cache_specs(B, total), jax.random.PRNGKey(0))

        def pack(dst, src, window):
            # src: (periods, B, prompt, H, hd) -> dst: (periods, B, Sc, H, hd)
            if window and prompt >= window:
                tail = src[:, :, prompt - window:]
                # ring layout: slot(t) = t % window for t in [prompt-window, prompt)
                idx = (np.arange(prompt - window, prompt) % window)
                return dst.at[:, :, idx].set(tail.astype(dst.dtype))
            s = min(prompt, dst.shape[2])
            return dst.at[:, :, :s].set(src[:, :, :s].astype(dst.dtype))

        out = {}
        for k, sub in target.items():
            if "k" in sub:  # attention cache
                w = min(total, cfg.window) if cfg.window else 0
                out[k] = {n: pack(sub[n], prefill_cache[k][n], w) for n in ("k", "v")}
            else:           # ssm state: copy as-is
                out[k] = {n: prefill_cache[k][n].astype(sub[n].dtype) for n in sub}
        return out

    # --------------------------------------------------------------- generate
    def generate(self, prompts: np.ndarray, scfg: ServeConfig | None = None):
        """prompts: (B, P) int32.  Returns (B, P+new) tokens (greedy)."""
        with sharding_profile(self.profile):
            return self._generate(prompts, scfg)

    def prefill(self, prompts: np.ndarray):
        """prompts: (B, P) int32.  Returns (prefill cache, last-token logits
        (B, 1, vocab))."""
        with sharding_profile(self.profile):
            return self._prefill_batch(prompts)

    def _prefill_batch(self, prompts: np.ndarray):
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (prompts.shape[0], self.cfg.enc_seq, self.cfg.d_model),
                jnp.float32)
        return self._prefill(self.params, batch)

    def _generate(self, prompts: np.ndarray, scfg: ServeConfig | None = None):
        scfg = scfg or ServeConfig()
        cfg = self.cfg
        B, P = prompts.shape
        total = P + scfg.max_new_tokens
        pf_cache, logits = self._prefill_batch(prompts)
        if cfg.family == "encdec":
            cache = {"self": self._seed_cache(
                {"pos0": pf_cache["self"]}, B, total, P)["pos0"],
                "cross": pf_cache["cross"]}
        else:
            cache = self._seed_cache(pf_cache, B, total, P)

        toks = np.zeros((B, total), np.int32)
        toks[:, :P] = prompts
        done = np.zeros(B, bool)
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for t in range(P, total):
            toks[:, t] = np.where(done, scfg.eos_id, np.asarray(cur))
            done |= toks[:, t] == scfg.eos_id
            if done.all() or t == total - 1:
                break
            logits, cache = self._decode(
                self.params, cache, jnp.asarray(toks[:, t:t + 1]), jnp.int32(t))
            cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return toks
