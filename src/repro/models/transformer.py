"""Transformer assembly for every assigned architecture family.

Design:
  * one ``init_block``/``block_seq``/``block_step`` triple covering
    dense / MoE / hybrid(Hymba) / ssm(RWKV6) layers;
  * layer parameters are STACKED ``[L, ...]`` and executed with
    ``jax.lax.scan`` (fast compiles at 61-layer production scale);
    non-uniform stacks (DeepSeek/Kimi dense-prefix layers) become two
    sequential scans;
  * per-layer attention windows are data (``window_sizes [L]``), so hybrid
    global/window layers share one scan body;
  * prefill returns stacked KV caches; decode consumes/updates them;
  * optional remat (``jax.checkpoint``) around the scan body for training.

Encoder-decoder (Whisper) and VLM (LLaVA) wrappers live at the bottom.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..utils import shard
from .attention import (
    attn_decode,
    attn_paged_decode,
    attn_prefill,
    init_attention,
    init_cache,
    init_paged_cache,
)
from .ffn import ffn, init_ffn
from .layers import apply_norm, embed, init_embedding, init_norm, unembed
from .ssm import (
    init_mamba,
    init_rwkv_channel_mix,
    init_rwkv_time_mix,
    mamba_seq,
    mamba_state_init,
    rwkv_channel_mix,
    rwkv_state_init,
    rwkv_time_mix_seq,
)

MTP_LOSS_WEIGHT = 0.3


# ============================ block =========================================

def init_block(key, cfg: ModelConfig, layer_kind: str):
    """layer_kind: dense | moe | hybrid | rwkv.  (moe/dense differ in ffn.)"""
    ks = jax.random.split(key, 6)
    if layer_kind == "rwkv":
        return {
            "norm1": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
            "time_mix": init_rwkv_time_mix(ks[0], cfg),
            "norm2": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
            "channel_mix": init_rwkv_channel_mix(ks[1], cfg),
        }
    import dataclasses as _dc
    ffn_cfg = cfg if layer_kind != "dense_prefix" else _dc.replace(cfg, moe=None)
    p = {
        "norm1": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
        "attn": init_attention(ks[0], cfg),
        "norm2": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
        "ffn": init_ffn(ks[1], ffn_cfg),
    }
    if layer_kind == "hybrid":
        p["mamba"] = init_mamba(ks[2], cfg)
    return p


def block_seq(p, x, cfg: ModelConfig, positions, window, rng=None,
              use_kernels: bool = False, layer_kind: str = "dense"):
    """Full-sequence block (train / prefill). Returns (x', cache, aux), aux
    a dict: ``aux_loss``, and ``moe_held`` for an expert-share layer."""
    if layer_kind == "rwkv":
        state = rwkv_state_init(cfg, x.shape[0])
        y, tm_state = rwkv_time_mix_seq(p["time_mix"], apply_norm(p["norm1"], x, cfg.norm),
                                        (state["tm_x"], state["tm_s"]), cfg, use_kernels)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm)
        y2, cm_x = rwkv_channel_mix(p["channel_mix"], h, state["cm_x"], cfg)
        x = x + y2
        cache = {"tm_x": tm_state[0], "tm_s": tm_state[1], "cm_x": cm_x}
        return x, cache, {"aux_loss": jnp.float32(0.0)}

    h = apply_norm(p["norm1"], x, cfg.norm)
    attn_out, kv = attn_prefill(p["attn"], h, cfg, positions, window, use_kernels)
    if layer_kind == "hybrid":
        m_state = mamba_state_init(cfg, x.shape[0])
        m_out, m_state = mamba_seq(p["mamba"], h, m_state, cfg, use_kernels)
        attn_out = 0.5 * (attn_out + m_out)  # Hymba: mean-fused parallel heads
    x = x + attn_out * cfg.residual_scale
    h2 = apply_norm(p["norm2"], x, cfg.norm)
    if layer_kind == "dense_prefix":
        from .ffn import mlp
        f_out, aux = mlp(p["ffn"], h2, cfg.act), {}
    else:
        f_out, aux = ffn(p["ffn"], h2, cfg, rng, use_kernels)
    x = x + f_out * cfg.residual_scale
    cache: Any = kv
    if layer_kind == "hybrid":
        cache = {"kv": kv, "mamba_conv": m_state[0], "mamba_h": m_state[1]}
    out = {"aux_loss": aux.get("aux_loss", jnp.float32(0.0))}
    if "moe_held" in aux:
        # an expert share also counts the pairs its experts computed
        out["moe_held"] = aux["moe_held"]
    return x, cache, out


def block_step(p, x, cache, pos, cfg: ModelConfig, window, layer_kind: str = "dense",
               use_kernels: bool = False):
    """Single-token decode. x: [B,1,d]."""
    if layer_kind == "rwkv":
        y, tm_state = rwkv_time_mix_seq(
            p["time_mix"], apply_norm(p["norm1"], x, cfg.norm),
            (cache["tm_x"], cache["tm_s"]), cfg)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm)
        y2, cm_x = rwkv_channel_mix(p["channel_mix"], h, cache["cm_x"], cfg)
        x = x + y2
        return x, {"tm_x": tm_state[0], "tm_s": tm_state[1], "cm_x": cm_x}

    h = apply_norm(p["norm1"], x, cfg.norm)
    if layer_kind == "hybrid":
        kv = cache["kv"]
        attn_out, kv = attn_decode(p["attn"], h, kv, pos, cfg, window, use_kernels)
        m_out, m_state = mamba_seq(p["mamba"], h, (cache["mamba_conv"], cache["mamba_h"]), cfg)
        attn_out = 0.5 * (attn_out + m_out)
        new_cache: Any = {"kv": kv, "mamba_conv": m_state[0], "mamba_h": m_state[1]}
    else:
        attn_out, new_cache = attn_decode(p["attn"], h, cache, pos, cfg, window, use_kernels)
    x = x + attn_out * cfg.residual_scale
    h2 = apply_norm(p["norm2"], x, cfg.norm)
    if layer_kind == "dense_prefix":
        from .ffn import mlp
        f_out = mlp(p["ffn"], h2, cfg.act)
    else:
        f_out, _ = ffn(p["ffn"], h2, cfg, None, use_kernels)
    x = x + f_out * cfg.residual_scale
    return x, new_cache


# ============================ stacks ========================================

def layer_kinds(cfg: ModelConfig) -> list[tuple[str, int]]:
    """[(kind, n_layers)] groups executed in order (dense-prefix before MoE)."""
    if cfg.family == "ssm":
        return [("rwkv", cfg.n_layers)]
    if cfg.family == "hybrid":
        return [("hybrid", cfg.n_layers)]
    if cfg.moe is not None:
        prefix = cfg_dense_prefix(cfg)
        groups = []
        if prefix:
            groups.append(("dense_prefix", prefix))
        groups.append(("moe", cfg.n_layers - prefix))
        return groups
    return [("dense", cfg.n_layers)]


def cfg_dense_prefix(cfg: ModelConfig) -> int:
    """Leading dense-FFN layers of an MoE stack (``cfg.dense_prefix``),
    leaving at least one MoE layer."""
    return min(cfg.dense_prefix, max(cfg.n_layers - 1, 0))


def expert_share(cfg: ModelConfig) -> bool:
    """MoE layers that hold a share of the routed experts: the aux dict of
    their forward, prefill and paged decode holds ``moe_held``, int32
    [pairs that reached a held expert, held experts that received one],
    summed over the MoE layers."""
    return cfg.moe is not None and cfg.moe.experts_held is not None


def _aux0(kind: str, cfg: ModelConfig) -> dict:
    """A stack's counters before its first layer: ``moe_held`` for an
    expert share's MoE stack, nothing else (an empty dict adds no output
    to a lowered program)."""
    if kind == "moe" and expert_share(cfg):
        return {"moe_held": jnp.zeros((2,), jnp.int32)}
    return {}


def _add_aux(a: dict, b: dict) -> dict:
    """Two aux dicts summed key by key."""
    return a | {k: a[k] + v if k in a else v for k, v in b.items()}


def window_for_layer(cfg: ModelConfig, global_index: int) -> int:
    """0 means no window (full attention)."""
    if cfg.window is None:
        return 0
    if global_index in cfg.global_layers:
        return 0
    return cfg.window


def stack_meta(cfg: ModelConfig) -> list[tuple[str, int, tuple[int, ...]]]:
    """Static metadata per stack: (kind, n_layers, window_sizes)."""
    out = []
    base = 0
    for kind, n in layer_kinds(cfg):
        windows = tuple(window_for_layer(cfg, base + i) for i in range(n))
        out.append((kind, n, windows))
        base += n
    return out


def init_stack(key, cfg: ModelConfig):
    """Returns list of stacked param pytrees [n, ...] (pure arrays only —
    kinds/windows are static metadata from :func:`stack_meta`)."""
    stacks = []
    for gi, (kind, n, _) in enumerate(stack_meta(cfg)):
        keys = jax.random.split(jax.random.fold_in(key, gi), n)
        stacks.append(jax.vmap(lambda k: init_block(k, cfg, kind))(keys))
    return stacks


def _scan_seq(stack_params, kind, windows, x, cfg, positions, rng, use_kernels,
              remat, with_cache: bool = True):
    win_arr = jnp.array([w if w > 0 else (1 << 30) for w in windows], jnp.int32)

    def body(carry, xs):
        x, aux = carry
        p_l, win_l, key_l = xs
        x, cache, a = block_seq(p_l, x, cfg, positions, win_l, key_l,
                                use_kernels, kind)
        # training never reads the caches — dropping them here (instead of
        # trusting scan-DCE through jax.checkpoint) saves the full stacked
        # KV allocation.
        return (x, jax.tree_util.tree_map(jnp.add, aux, a)), \
            (cache if with_cache else None)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    n = len(windows)
    keys = (jax.random.split(rng, n) if rng is not None
            else jnp.zeros((n,), jnp.uint32))
    aux0 = {"aux_loss": jnp.float32(0.0), **_aux0(kind, cfg)}
    (x, aux), caches = jax.lax.scan(
        body, (x, aux0), (stack_params, win_arr, keys))
    return x, aux, caches


def _scan_step(stack_params, kind, windows, x, caches, pos, cfg, use_kernels=False):
    win_arr = jnp.array([w if w > 0 else (1 << 30) for w in windows], jnp.int32)

    def body(x, xs):
        p_l, win_l, cache_l = xs
        x, new_cache = block_step(p_l, x, cache_l, pos, cfg, win_l, kind, use_kernels)
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (stack_params, win_arr, caches))
    return x, new_caches


# ============================ LM facade =====================================

def init_lm(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    p = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, cfg.dtype),
        "stacks": init_stack(ks[1], cfg),
        "final_norm": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = init_embedding(ks[2], cfg.vocab_size, cfg.d_model, cfg.dtype)
    if cfg.meta_tokens:
        p["meta"] = (jax.random.normal(ks[3], (cfg.meta_tokens, cfg.d_model),
                                       jnp.float32) * 0.02).astype(cfg.dtype)
    if cfg.mtp_heads:
        p["mtp"] = {
            "proj": {"w": (jax.random.normal(ks[4], (2 * cfg.d_model, cfg.d_model),
                                             jnp.float32) * (2 * cfg.d_model) ** -0.5
                           ).astype(cfg.dtype)},
            "block": init_block(jax.random.fold_in(ks[4], 1), cfg,
                                "dense" if cfg.moe is None else "moe"),
            "norm": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
        }
    if cfg.frontend is not None:
        fe = cfg.frontend
        from .layers import init_linear
        p["frontend"] = {
            "proj1": init_linear(jax.random.fold_in(ks[3], 2), fe.feat_dim,
                                 cfg.d_model, True, cfg.dtype),
            "proj2": init_linear(jax.random.fold_in(ks[3], 3), cfg.d_model,
                                 cfg.d_model, True, cfg.dtype),
        }
    return p


def _embed_inputs(params, tokens, cfg: ModelConfig, extra_embeds=None):
    """tokens [B,S] (+ optional modality embeds prepended). Returns [B,S',d]."""
    x = embed(params["embed"], tokens)
    if extra_embeds is not None:
        from .layers import gelu, linear
        fe = gelu(linear(params["frontend"]["proj1"], extra_embeds))
        fe = linear(params["frontend"]["proj2"], fe)
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
    if cfg.meta_tokens:
        meta = jnp.broadcast_to(params["meta"][None], (x.shape[0],) + params["meta"].shape)
        x = jnp.concatenate([meta.astype(x.dtype), x], axis=1)
    return shard(x, "batch", "seq", "embed")


def lm_forward(params, tokens, cfg: ModelConfig, rng=None, use_kernels=False,
               remat=False, extra_embeds=None, with_cache: bool = True,
               with_logits: bool = True):
    """Training/prefill forward → (logits [B,S',V] fp32, aux, caches), aux a
    dict: ``aux_loss``, and ``moe_held`` summed where :func:`expert_share`.
    ``with_logits=False`` returns the final hidden states instead (used by
    the chunked-CE path that fuses the head matmul into the loss)."""
    x = _embed_inputs(params, tokens, cfg, extra_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    aux_total = {}
    caches = []
    for stack_params, (kind, _, windows) in zip(params["stacks"], stack_meta(cfg)):
        r = jax.random.fold_in(rng, len(caches)) if rng is not None else None
        x, aux, cache = _scan_seq(stack_params, kind, windows, x, cfg, positions,
                                  r, use_kernels, remat, with_cache)
        aux_total = _add_aux(aux_total, aux)
        caches.append(cache)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    if not with_logits:
        return x, aux_total, caches
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(head, x)
    logits = shard(logits, "batch", "seq", "vocab")
    return logits, aux_total, caches


def lm_loss(params, batch, cfg: ModelConfig, rng=None, use_kernels=False, remat=False):
    """Next-token CE (+ MoE aux + MTP). batch: {tokens, labels[, extra_embeds]}."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    from ..flags import chunked_ce
    from .losses import chunked_softmax_xent, softmax_xent
    if chunked_ce():
        # §Perf O3: head matmul fused into a seq-chunked loss — the full
        # [B,S,V] fp32 logits tensor never exists.
        hidden, aux, _ = lm_forward(params, tokens, cfg, rng, use_kernels,
                                    remat, batch.get("extra_embeds"),
                                    with_cache=False, with_logits=False)
        prefix = hidden.shape[1] - labels.shape[1]
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        ce = chunked_softmax_xent(hidden[:, prefix:], head["table"], labels)
    else:
        logits, aux, _ = lm_forward(params, tokens, cfg, rng, use_kernels,
                                    remat, batch.get("extra_embeds"),
                                    with_cache=False)
        # align: logits predict the NEXT token; labels = tokens shifted by 1.
        prefix = logits.shape[1] - labels.shape[1]
        ce = softmax_xent(logits[:, prefix:], labels)
    aux = aux["aux_loss"]
    loss = ce + 0.01 * aux
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp_heads:
        mtp_ce = _mtp_loss(params, tokens, labels, cfg)
        loss = loss + MTP_LOSS_WEIGHT * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    return loss, metrics


def _mtp_loss(params, tokens, labels, cfg: ModelConfig):
    """DeepSeek-V3 multi-token prediction: one extra block predicting t+2,
    fed by concat(stopgrad-free h, embed(next token)) — simplified single head."""
    x = embed(params["embed"], tokens)
    x_next = embed(params["embed"], labels)             # emb of t+1 stream
    h = jnp.concatenate([x[:, :-1], x_next[:, :-1]], axis=-1)
    h = jnp.einsum("bsd,dk->bsk", h, params["mtp"]["proj"]["w"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    kind = "dense" if cfg.moe is None else "moe"
    h, _, _ = block_seq(params["mtp"]["block"], h, cfg, positions, None, None, False, kind)
    h = apply_norm(params["mtp"]["norm"], h, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    from ..utils import shard as _shard
    from .losses import softmax_xent
    logits = _shard(unembed(head, h), "batch", "seq", "vocab")
    return softmax_xent(logits, labels[:, 1:])          # predict t+2


# -- serving ------------------------------------------------------------------

def lm_prefill(params, tokens, cfg: ModelConfig, cache_len: int | None = None,
               use_kernels=False, extra_embeds=None):
    """Prefill → (last-token logits [B,V], caches padded to cache_len,
    counters): the counters hold ``moe_held`` where :func:`expert_share`,
    else nothing."""
    logits, aux, caches = lm_forward(params, tokens, cfg, None, use_kernels,
                                     False, extra_embeds)
    if cache_len is not None and cfg.family not in ("ssm",):
        caches = [_pad_cache(c, cache_len, cfg) for c in caches]
    aux.pop("aux_loss")
    return logits[:, -1], caches, aux


def _pad_cache(cache, length: int, cfg: ModelConfig):
    def pad(x):
        # KV tensors have the seq axis at position 2 ([L,B,S,...]); states
        # (mamba/rwkv) are position-free and pass through.
        return x

    if cfg.mla is not None and isinstance(cache, tuple):
        c, r = cache
        padw = [(0, 0), (0, 0), (0, length - c.shape[2]), (0, 0)]
        return (jnp.pad(c, padw), jnp.pad(r, padw))
    if isinstance(cache, tuple):
        k, v = cache
        padw = [(0, 0), (0, 0), (0, length - k.shape[2])] + [(0, 0)] * (k.ndim - 3)
        return (jnp.pad(k, padw), jnp.pad(v, padw))
    if isinstance(cache, dict) and "kv" in cache:
        return {**cache, "kv": _pad_cache(cache["kv"], length, cfg)}
    return cache


def init_decode_caches(cfg: ModelConfig, batch: int, length: int):
    """Empty caches shaped for decode (used by dry-run decode cells)."""
    caches = []
    for kind, n, _ in stack_meta(cfg):
        if kind == "rwkv":
            st = rwkv_state_init(cfg, batch)
            caches.append(jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), st))
        else:
            kv = init_cache(cfg, batch, length)
            entry: Any = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), kv)
            if kind == "hybrid":
                ms = mamba_state_init(cfg, batch)
                entry = {
                    "kv": entry,
                    "mamba_conv": jnp.broadcast_to(ms[0][None], (n,) + ms[0].shape),
                    "mamba_h": jnp.broadcast_to(ms[1][None], (n,) + ms[1].shape),
                }
            caches.append(entry)
    return caches


def block_step_paged(p, x, pool, layer, block_tables, pos, cfg: ModelConfig,
                     window, layer_kind: str = "dense",
                     use_kernels: bool = False):
    """Single-token decode against paged KV. x: [B,1,d]; pool: the whole
    stack's page pool, of which this block is layer ``layer``."""
    h = apply_norm(p["norm1"], x, cfg.norm)
    attn_out, pool = attn_paged_decode(p["attn"], h, pool, layer,
                                       block_tables, pos, cfg, window,
                                       use_kernels)
    x = x + attn_out * cfg.residual_scale
    h2 = apply_norm(p["norm2"], x, cfg.norm)
    aux = {}
    with jax.named_scope("mlp"):
        if layer_kind == "dense_prefix":
            from .ffn import mlp
            f_out = mlp(p["ffn"], h2, cfg.act)
        else:
            f_out, aux = ffn(p["ffn"], h2, cfg, None, use_kernels)
    x = x + f_out * cfg.residual_scale
    return x, pool, {k: aux[k] for k in ("moe_held",) if k in aux}


def _scan_step_paged(stack_params, kind, windows, x, pool, block_tables, pos,
                     cfg, use_kernels=False):
    """Returns (x, pool, counters summed over the stack).  The scan carries
    the stack's page pool and each layer writes its row into it in place:
    nothing maps over the pool, so nothing copies it."""
    win_arr = jnp.array([w if w > 0 else (1 << 30) for w in windows], jnp.int32)
    layers = jnp.arange(len(windows), dtype=jnp.int32)

    def body(carry, xs):
        x, pool, aux = carry
        p_l, win_l, layer = xs
        x, pool, a = block_step_paged(p_l, x, pool, layer, block_tables, pos,
                                      cfg, win_l, kind, use_kernels)
        return (x, pool, _add_aux(aux, a)), None

    (x, pool, aux), _ = jax.lax.scan(body, (x, pool, _aux0(kind, cfg)),
                                     (stack_params, win_arr, layers))
    return x, pool, aux


def init_paged_decode_caches(cfg: ModelConfig, num_pages: int, page_size: int):
    """One page pool per layer stack, in the paged kernel's layout (see
    :func:`~repro.models.attention.init_paged_cache`)."""
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"family {cfg.family!r} carries recurrent state; paged KV "
            "applies only to pure-attention stacks")
    return [init_paged_cache(cfg, n, num_pages, page_size)
            for _, n, _ in stack_meta(cfg)]


def lm_paged_decode(params, token, caches, block_tables, pos,
                    cfg: ModelConfig, use_kernels=False):
    """One decode step over paged caches. token/pos: [B] int32;
    block_tables: [B,MAXP] int32 (shared by every layer). → (logits, caches',
    counters): the counters hold ``moe_held`` where :func:`expert_share`,
    else nothing."""
    x = embed(params["embed"], token[:, None])
    if cfg.meta_tokens:
        pos = pos + cfg.meta_tokens
    new_caches = []
    aux = {}
    for stack_params, cache, (kind, _, windows) in zip(
            params["stacks"], caches, stack_meta(cfg)):
        x, cache, a = _scan_step_paged(stack_params, kind, windows, x, cache,
                                       block_tables, pos, cfg, use_kernels)
        new_caches.append(cache)
        aux = _add_aux(aux, a)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(head, x)[:, 0]
    return logits, new_caches, aux


def lm_decode(params, token, caches, pos, cfg: ModelConfig, use_kernels=False):
    """One decode step. token: [B] int32; pos: [B] int32. → (logits, caches')."""
    x = embed(params["embed"], token[:, None])
    if cfg.meta_tokens:
        pos = pos + cfg.meta_tokens
    new_caches = []
    for stack_params, cache, (kind, _, windows) in zip(
            params["stacks"], caches, stack_meta(cfg)):
        x, cache = _scan_step(stack_params, kind, windows, x, cache, pos, cfg,
                              use_kernels)
        new_caches.append(cache)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(head, x)[:, 0]
    return logits, new_caches
