"""Model facade: one object per architecture exposing

    init(rng)                 → params
    loss(params, batch, rng)  → (loss, metrics)          [train shapes]
    prefill(params, inputs)   → (last_logits, caches, counters) [prefill shapes]
    decode(params, ...)       → (logits, caches)         [decode shapes]
    input_specs(cell)         → ShapeDtypeStruct pytree for the dry-run
    decode_state_specs(cell)  → cache ShapeDtypeStructs (no allocation)

Every function is pure and jit/pjit-friendly; the launcher owns meshes,
shardings and optimizer state.
"""
from __future__ import annotations

import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeCell
from . import encdec as ed
from . import transformer as tf


class Model:
    def __init__(self, cfg: ModelConfig, use_kernels: bool = False):
        self.cfg = cfg
        self.use_kernels = use_kernels

    # -- params ---------------------------------------------------------------
    def init(self, rng) -> Any:
        if self.cfg.family == "encdec":
            return ed.init_encdec(rng, self.cfg)
        return tf.init_lm(rng, self.cfg)

    def init_shapes(self) -> Any:
        """ShapeDtypeStruct pytree of params — no allocation (dry-run)."""
        return jax.eval_shape(self.init, jax.random.key(0))

    # -- steps ------------------------------------------------------------------
    def loss(self, params, batch: Mapping[str, Any], rng=None, remat: bool = False):
        if self.cfg.family == "encdec":
            return ed.encdec_loss(params, batch, self.cfg, rng, self.use_kernels, remat)
        return tf.lm_loss(params, batch, self.cfg, rng, self.use_kernels, remat)

    def prefill(self, params, inputs: Mapping[str, Any], cache_len: int | None = None):
        if self.cfg.family == "encdec":
            logits, caches = ed.encdec_prefill(
                params, inputs["frames"], inputs["tokens"], self.cfg,
                cache_len or inputs["tokens"].shape[1], self.use_kernels)
            return logits, caches, {}
        return tf.lm_prefill(params, inputs["tokens"], self.cfg, cache_len,
                             self.use_kernels, inputs.get("extra_embeds"))

    def decode(self, params, token, caches, pos):
        if self.cfg.family == "encdec":
            return ed.encdec_decode(params, token, caches, pos, self.cfg, self.use_kernels)
        return tf.lm_decode(params, token, caches, pos, self.cfg, self.use_kernels)

    # -- paged decode ------------------------------------------------------------
    def supports_paged(self) -> bool:
        """Paged KV applies to pure-attention decoder stacks only (recurrent
        state — ssm/hybrid — and encdec cross-attention stay dense)."""
        return self.cfg.family in ("dense", "moe", "vlm")

    def init_paged_caches(self, num_pages: int, page_size: int):
        return tf.init_paged_decode_caches(self.cfg, num_pages, page_size)

    def paged_decode(self, params, token, caches, block_tables, pos):
        return tf.lm_paged_decode(params, token, caches, block_tables, pos,
                                  self.cfg, self.use_kernels)

    # -- dry-run input specs -----------------------------------------------------
    def input_specs(self, cell: ShapeCell) -> dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        b, s = cell.global_batch, cell.seq_len
        i32 = jnp.int32
        if cell.step == "train":
            if cfg.family == "encdec":
                fe = cfg.frontend
                return {
                    "frames": jax.ShapeDtypeStruct((b, fe.n_tokens, fe.feat_dim), cfg.dtype),
                    "tokens": jax.ShapeDtypeStruct((b, s), i32),
                    "labels": jax.ShapeDtypeStruct((b, s), i32),
                }
            out = {
                "tokens": jax.ShapeDtypeStruct((b, self._text_len(s)), i32),
                "labels": jax.ShapeDtypeStruct((b, self._text_len(s)), i32),
            }
            if cfg.family == "vlm":
                fe = cfg.frontend
                out["extra_embeds"] = jax.ShapeDtypeStruct(
                    (b, fe.n_tokens, fe.feat_dim), cfg.dtype)
            return out
        if cell.step == "prefill":
            if cfg.family == "encdec":
                fe = cfg.frontend
                return {
                    "frames": jax.ShapeDtypeStruct((b, fe.n_tokens, fe.feat_dim), cfg.dtype),
                    "tokens": jax.ShapeDtypeStruct((b, s), i32),
                }
            out = {"tokens": jax.ShapeDtypeStruct((b, self._text_len(s)), i32)}
            if cfg.family == "vlm":
                fe = cfg.frontend
                out["extra_embeds"] = jax.ShapeDtypeStruct(
                    (b, fe.n_tokens, fe.feat_dim), cfg.dtype)
            return out
        # decode: one new token against a seq_len-long cache
        return {
            "token": jax.ShapeDtypeStruct((b,), i32),
            "pos": jax.ShapeDtypeStruct((b,), i32),
        }

    def _text_len(self, s: int) -> int:
        """VLM text token count: total seq budget minus image patches."""
        if self.cfg.family == "vlm" and self.cfg.frontend is not None:
            return max(s - self.cfg.frontend.n_tokens, 16)
        return s

    def decode_state_specs(self, cell: ShapeCell) -> Any:
        """Cache ShapeDtypeStructs for decode cells (no allocation)."""
        cfg = self.cfg
        b = cell.global_batch
        length = cell.seq_len + cfg.meta_tokens

        def build():
            if cfg.family == "encdec":
                fe = cfg.frontend
                n_dec = cfg.n_dec_layers or cfg.n_layers
                kvh, hd = cfg.n_kv_heads, cfg.head_dim
                kv = lambda t: (jnp.zeros((n_dec, b, t, kvh, hd), cfg.dtype),
                                jnp.zeros((n_dec, b, t, kvh, hd), cfg.dtype))
                return (kv(length), kv(fe.n_tokens))
            return tf.init_decode_caches(cfg, b, length)

        return jax.eval_shape(build)


def make_model(cfg: ModelConfig, use_kernels: bool = False) -> Model:
    return Model(cfg, use_kernels)
