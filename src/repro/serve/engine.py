"""Batched serving engine: prefill once, decode greedily with per-sequence
EOS stop, KV cache reconciliation between the prefill and decode layouts
(including SWA ring-buffer packing).

The cache holds typed kinds of state side by side, one per layer position
of the pattern: attention K/V (packed from the prefill length into the
decode length) and SSM state (carried over as it is), and for a model with
an expert layer its slot counter (``transformer.SLOTS``).

Spans (``repro.core.spans`` convention: a ``TraceAnnotation`` with integer
stats, recorded whenever a profiler trace runs), one of each per
``generate()`` call:

``engine.prefill``
    the prompt's forward pass and the decode cache seeded from it.
``engine.decode``
    the token-by-token decode loop.

Both carry ``batch``, ``prompt`` (prompt tokens per sequence), ``steps``
(decode steps run; 0 on the prefill), ``state_bytes`` (SSM and conv state
of the decode cache) and ``kv_bytes`` (its K/V).  A model with an expert
layer adds ``held_slots``, ``routed_slots`` and ``dropped``: the (token,
expert) slots that went to the experts this chip holds, all routed ones,
and held ones dropped by a capacity, counted on the device through the span
and read once when it ends.  ``Engine.slots`` keeps their running totals.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.spans import span
from ..models.common import (
    ShardingProfile,
    active_profile,
    init_params,
    resolve_profile,
    sharding_profile,
    tree_map_pspec,
)
from ..models.model import Model, build
from ..models.transformer import SLOTS

SLOT_STATS = ("held_slots", "routed_slots", "dropped")


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
        self.slots = dict.fromkeys(SLOT_STATS, 0)

    # ------------------------------------------------------------------ cache
    def _cache_bytes(self, B: int, total: int) -> dict:
        """{"state_bytes", "kv_bytes"} of the decode cache for B sequences
        of ``total`` tokens, as ``_seed_cache`` lays it out."""
        out = {"state_bytes": 0, "kv_bytes": 0}
        if self.cfg.family == "encdec":
            return out

        def add(path, p):
            kind = path.rsplit("/", 1)[-1]
            key = "kv_bytes" if kind in ("k", "v") else "state_bytes"
            if kind in ("k", "v", "ssm", "conv"):
                out[key] += int(np.prod(p.shape)) * 4   # seeded in float32

        tree_map_pspec(add, self.model.cache_specs(B, total))
        return out

    def _seed_cache(self, prefill_cache, B: int, total: int, prompt: int):
        """Pack the prefill K/V (length=prompt) into the decode layout
        (length=total or window); SSM states and the slot counter pass
        through unchanged."""
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

        if cfg.family == "encdec":
            kinds = {"pos0": "attn"}
        else:
            kinds = {f"pos{i}": mixer
                     for i, (mixer, _) in enumerate(cfg.layer_pattern())}
        w = min(total, cfg.window) if cfg.window else 0
        out = {}
        for k, kind in kinds.items():
            sub = target[k] if k in target else target["self"]
            if kind == "attn":
                out[k] = {n: pack(sub[n], prefill_cache[k][n], w)
                          for n in ("k", "v")}
            else:
                out[k] = {n: prefill_cache[k][n].astype(sub[n].dtype)
                          for n in sub}
        if SLOTS in prefill_cache:
            out[SLOTS] = prefill_cache[SLOTS]
        return out

    def _read_slots(self, ann, before, after) -> None:
        """Put the slots counted between two counter values on the span
        and into the running totals (one read of the device)."""
        got = np.asarray(after) - (0 if before is None else np.asarray(before))
        stats = {name: int(v) for name, v in zip(SLOT_STATS, got)}
        ann.set_metadata(**stats)
        for name, v in stats.items():
            self.slots[name] += v

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

    def score(self, prompts: np.ndarray, tokens: np.ndarray):
        """Teacher-forced logits through the serving path: the prefill over
        prompts (B, P), then the decode step through the cache over tokens
        (B, T) one at a time, as ``generate()`` runs them.  Returns the
        next-token logits after the prompt and after each token: (B, T + 1,
        vocab) float32, on the device."""
        with sharding_profile(self.profile):
            B, P = prompts.shape
            T = tokens.shape[1]
            pf_cache, logits = self._prefill_batch(prompts)
            cache = self._seed_cache(pf_cache, B, P + T + 1, P)
            out = [logits[:, -1]]
            for i in range(T):
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(tokens[:, i:i + 1]),
                    jnp.int32(P + i))
                out.append(logits[:, -1])
            return jnp.stack(out, axis=1)

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
        stats = dict(batch=B, prompt=P, **self._cache_bytes(B, total))
        with span("engine.prefill", steps=0, **stats) as ann:
            pf_cache, logits = self._prefill_batch(prompts)
            if cfg.family == "encdec":
                cache = {"self": self._seed_cache(
                    {"pos0": pf_cache["self"]}, B, total, P)["pos0"],
                    "cross": pf_cache["cross"]}
            else:
                cache = self._seed_cache(pf_cache, B, total, P)
            seeded = cache.get(SLOTS)
            if seeded is not None:
                seeded = np.asarray(seeded)
                self._read_slots(ann, None, seeded)

        toks = np.zeros((B, total), np.int32)
        toks[:, :P] = prompts
        done = np.zeros(B, bool)
        steps = 0
        with span("engine.decode", **stats) as ann:
            cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            for t in range(P, total):
                toks[:, t] = np.where(done, scfg.eos_id, np.asarray(cur))
                done |= toks[:, t] == scfg.eos_id
                if done.all() or t == total - 1:
                    break
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(toks[:, t:t + 1]),
                    jnp.int32(t))
                cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                steps += 1
            ann.set_metadata(steps=steps)
            if seeded is not None:
                self._read_slots(ann, seeded, cache[SLOTS])
        return toks
