"""Step functions lowered by the dry-run / launchers.

``make_train_step``: loss → grad → (optional compression) → AdamW update.
``make_prefill_step`` / ``make_decode_step``: serving paths.

All are pure functions of (params/opt_state, batch) suitable for
``jax.jit(...).lower(...)`` with explicit in/out shardings.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ParallelConfig, ShapeCell
from ..models import Model
from ..optim import adamw_update, compress_grads
from ..optim.schedule import cosine_schedule


def make_train_step(model: Model, pcfg: ParallelConfig,
                    base_lr: float = 3e-4, warmup: int = 2000,
                    total_steps: int = 100_000) -> Callable:
    remat = pcfg.remat != "none"

    def train_step(params, opt_state, batch, seed):
        rng = jax.random.PRNGKey(seed)

        def loss_fn(p):
            loss, metrics = model.loss(p, batch, rng, remat=remat)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        from ..optim import AdamWState
        has_comp = not isinstance(opt_state, AdamWState)
        comp_state = None
        if has_comp:
            adam, comp_state = opt_state
        else:
            adam = opt_state
        if pcfg.grad_compression != "none" and comp_state is not None:
            grads, comp_state = compress_grads(grads, comp_state,
                                               pcfg.grad_compression)
        lr = cosine_schedule(adam.step, base_lr, warmup=warmup, total=total_steps)
        new_params, new_adam, opt_metrics = adamw_update(grads, adam, params, lr)
        new_opt = (new_adam, comp_state) if has_comp else new_adam
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return new_params, new_opt, metrics

    return train_step


def make_prefill_step(model: Model, cell: ShapeCell) -> Callable:
    cache_len = cell.seq_len + model.cfg.meta_tokens

    def prefill_step(params, inputs):
        logits, caches, _ = model.prefill(params, inputs, cache_len=cache_len)
        return logits, caches

    return prefill_step


def make_decode_step(model: Model) -> Callable:
    def decode_step(params, caches, token, pos):
        logits, new_caches = model.decode(params, token, caches, pos)
        return logits, new_caches

    return decode_step
