"""Model facade: one object per architecture exposing spec trees, init,
loss/prefill/decode functions and input specs for every shape cell."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeCell
from . import encdec, transformer
from .common import abstract_params, init_params, param_shardings


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ------------------------------------------------------------------ params
    def specs(self):
        if self.cfg.family == "encdec":
            return encdec.model_specs(self.cfg)
        return transformer.model_specs(self.cfg)

    def init(self, key: jax.Array):
        return init_params(self.specs(), key, jnp.dtype(self.cfg.param_dtype))

    def abstract(self):
        return abstract_params(self.specs(), jnp.dtype(self.cfg.param_dtype))

    def shardings(self, mesh):
        return param_shardings(self.specs(), mesh)

    def cache_specs(self, batch: int, seq: int):
        if self.cfg.family == "encdec":
            return encdec.cache_specs(self.cfg, batch, seq)
        return transformer.cache_specs(self.cfg, batch, seq)

    # ------------------------------------------------------------------- steps
    def loss(self, params, batch) -> jax.Array:
        """batch: tokens/labels (+ frames for encdec, embeds/positions for vlm)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            l, aux = encdec.loss(params, cfg, batch["frames"], batch["tokens"],
                                 batch["labels"])
            return l + aux
        hidden, aux, _ = transformer.forward_full(
            params, cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            positions=batch.get("positions"),
        )
        return transformer.xent_loss(params, cfg, hidden, batch["labels"]) + aux

    def prefill(self, params, batch):
        """Returns (per-layer cache stacked over periods, last-token logits)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            enc_out = encdec.encode(params, cfg, batch["frames"])
            hidden, cache = encdec.decode_full(params, cfg, batch["tokens"],
                                               enc_out, want_cache=True)
            logits = (hidden[:, -1:] @ params["unembed"].astype(hidden.dtype))
            return cache, logits.astype(jnp.float32)
        hidden, _, cache = transformer.forward_full(
            params, cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            positions=batch.get("positions"),
            want_cache=True,
            dropless=True,
        )
        logits = transformer.unembed(params, cfg, hidden[:, -1:])
        return cache, logits

    def decode(self, params, cache, tokens, pos, positions=None):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.decode_step(params, cfg, cache, tokens, pos)
        logits, new_cache = transformer.decode_step(
            params, cfg, cache, tokens=tokens, pos=pos, positions=positions
        )
        return logits, new_cache

    # ------------------------------------------------------------- input specs
    def input_specs(self, cell: ShapeCell) -> dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of a shape cell
        (the dry-run contract: weak-type-correct, shardable, no allocation)."""
        cfg = self.cfg
        B, S = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        f32 = jnp.float32
        tok = jax.ShapeDtypeStruct((B, S), i32)
        if cell.kind == "train":
            if cfg.family == "encdec":
                return {"frames": jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), f32),
                        "tokens": tok, "labels": tok}
            out = {"tokens": tok, "labels": tok}
            if cfg.family == "vlm":
                out = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), f32),
                       "labels": tok,
                       "positions": jax.ShapeDtypeStruct((3, B, S), i32)}
            return out
        if cell.kind == "prefill":
            if cfg.family == "encdec":
                return {"frames": jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), f32),
                        "tokens": tok}
            if cfg.family == "vlm":
                return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), f32),
                        "positions": jax.ShapeDtypeStruct((3, B, S), i32)}
            return {"tokens": tok}
        # decode: one new token against a seq_len cache
        out = {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
               "pos": jax.ShapeDtypeStruct((), i32)}
        if cfg.family == "vlm":
            out["positions"] = jax.ShapeDtypeStruct((3, B, 1), i32)
        return out


def build(cfg: ArchConfig) -> Model:
    return Model(cfg)
