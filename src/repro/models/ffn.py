"""Feed-forward: dense SwiGLU / GELU MLP and Mixture-of-Experts.

MoE uses static-capacity dense dispatch (TPU-friendly: one-hot einsum
scatter/gather, no data-dependent shapes) with:
  * softmax top-k routing + optional DeepSeek-V3 aux-loss-free bias balancing
    and group-limited selection,
  * shared (always-on) experts,
  * expert sharding over the ``expert`` logical axis (EP),
  * optional Pallas grouped-GEMM kernel for the expert compute.

A layer told which experts it holds (``MoEConfig.experts_held``) is one
chip's share under expert parallelism: it routes over every expert and
computes, without dropping any, the (token, expert) pairs of the experts
it holds (:func:`moe_ffn_share`).

The per-expert FFN branches are exactly the "parallelizable operators" Opara
schedules; the capacity-dense formulation IS the wave-fused execution of all
expert lanes in one grouped kernel (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..utils import shard
from .layers import gelu, init_linear, linear


# -- dense MLP ----------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, act: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        return {
            "gate": init_linear(ks[0], d, d_ff, False, dtype),
            "up": init_linear(ks[1], d, d_ff, False, dtype),
            "down": init_linear(ks[2], d_ff, d, False, dtype),
        }
    return {
        "up": init_linear(ks[0], d, d_ff, False, dtype),
        "down": init_linear(ks[1], d_ff, d, False, dtype),
    }


def mlp(p, x, act: str = "swiglu"):
    if "gate" in p:
        h = jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x)
    else:
        h = gelu(linear(p["up"], x))
    if h.ndim == 3:
        h = shard(h, "batch", "seq", "mlp")
    else:  # flattened tokens (MoE shared-expert path)
        h = shard(h, "batch", "mlp")
    return linear(p["down"], h)


# -- MoE ----------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    e = cfg.moe
    assert e is not None
    d, dtype = cfg.d_model, cfg.dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": {
            "w": (jax.random.normal(ks[0], (d, e.n_experts), jnp.float32) * d ** -0.5),
            # aux-free balancing: the correction bias added to the scores
            # for selection only
            "b": jnp.zeros((e.n_experts,), jnp.float32),
        },
        # stacked weights [E, ...] of the experts held — sharded over the
        # expert axis
        "experts": {
            "gate": (jax.random.normal(ks[1], (e.n_local, d, e.d_expert), jnp.float32)
                     * d ** -0.5).astype(dtype),
            "up": (jax.random.normal(ks[2], (e.n_local, d, e.d_expert), jnp.float32)
                   * d ** -0.5).astype(dtype),
            "down": (jax.random.normal(ks[3], (e.n_local, e.d_expert, d), jnp.float32)
                     * e.d_expert ** -0.5).astype(dtype),
        },
    }
    if e.n_shared:
        p["shared"] = init_mlp(ks[4], d, e.d_expert * e.n_shared, "swiglu", dtype)
    return p


def _group_limit(select, e):
    """DeepSeek-V3 group-limited selection: a group scores the sum of its
    two best selection scores; experts outside the ``topk_group`` best
    groups are masked to -inf."""
    n = select.shape[0]
    groups = select.reshape(n, e.n_group, -1)                    # [N,G,E/G]
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)            # [N,G]
    _, keep_idx = jax.lax.top_k(group_score, e.topk_group)       # [N,tg]
    keep = jnp.zeros((n, e.n_group), bool).at[
        jnp.arange(n)[:, None], keep_idx].set(True)
    return jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(n, -1)


def route(p_router, x, e, rng=None):
    """Top-k routing. Returns (weights [N,k], experts [N,k], aux metrics).

    DeepSeek-V3 aux-loss-free: scores are sigmoid(x W) in float32 and
    selection uses scores + the per-expert correction bias ``b`` (limited
    to the ``topk_group`` best of ``n_group`` groups); the combine weights
    are the un-biased scores of the chosen experts, normalised, times
    ``routed_scale``.  The bias is updated outside the step (optimizer
    hook) toward load balance.
    """
    n = x.shape[0]
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), p_router["w"])
    scores = jax.nn.sigmoid(logits) if e.router_aux_free else jax.nn.softmax(logits, -1)
    select = scores + p_router["b"][None, :] if e.router_aux_free else scores
    if rng is not None and e.router_noise > 0:
        select = select + jax.random.normal(rng, select.shape) * e.router_noise
    if e.n_group > 1:
        select = _group_limit(select, e)
    _, top_idx = jax.lax.top_k(select, e.top_k)                  # [N,k]
    top_w = jnp.take_along_axis(scores, top_idx, axis=-1)        # [N,k]
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)
    if e.routed_scale != 1.0:
        top_w = top_w * e.routed_scale
    # load-balance metrics (Switch-style): fraction per expert
    counts = jnp.zeros((e.n_experts,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
    load = counts / jnp.maximum(counts.sum(), 1.0)
    importance = scores.mean(0)
    aux = {"load": load, "aux_loss": e.n_experts * jnp.sum(load * importance)}
    return top_w, top_idx, aux


def _capacity(n: int, e) -> int:
    return min(int(max(1, round(n * e.top_k / e.n_experts * e.capacity_factor))), n)


def _expert_mlp(p_experts, buf, use_kernels: bool, rows=None):
    """Grouped expert GEMM: buf [E,C,d] → [E,C,d].  ONE fused kernel over the
    expert axis — the horizontally-fused Opara wave (DESIGN.md §2).  With
    ``rows`` [E] only each expert's first ``rows[e]`` rows are live: the
    kernel skips the row tiles past them, whose output rows are undefined
    (the reference computes them from the buffer's zero rows)."""
    if use_kernels:
        from ..kernels.moe_gemm.ops import moe_mlp_tpu_or_ref
        return moe_mlp_tpu_or_ref(buf, p_experts, rows)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p_experts["gate"],
                               preferred_element_type=jnp.float32).astype(buf.dtype))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p_experts["up"],
                       preferred_element_type=jnp.float32).astype(buf.dtype)
    return jnp.einsum("ecf,efd->ecd", h, p_experts["down"],
                      preferred_element_type=jnp.float32).astype(buf.dtype)


def moe_ffn_dense(p, x, cfg: ModelConfig, rng=None, use_kernels: bool = False):
    """One-hot capacity-dense dispatch (GShard-style einsum).  O(N·E·C)
    dispatch tensors — only viable for small expert counts; used by smoke
    configs and as the semantics oracle for the sort-based path.
    """
    e = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    top_w, top_idx, aux = route(p["router"], xf, e, rng)

    cap = _capacity(n, e)
    onehot = jax.nn.one_hot(top_idx, e.n_experts, dtype=jnp.int32)   # [N,k,E]
    flatoh = onehot.reshape(n * e.top_k, e.n_experts)
    pos_in_e = (jnp.cumsum(flatoh, axis=0) - flatoh).reshape(n, e.top_k, e.n_experts)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                        # [N,k]
    keep = pos < cap
    w = top_w * keep

    disp = (onehot * keep[..., None]).astype(xf.dtype)               # [N,k,E]
    poh = jax.nn.one_hot(pos, cap, dtype=xf.dtype)                   # [N,k,C]
    comb = jnp.einsum("nke,nkc->nec", disp, poh)                     # [N,E,C]
    buf = jnp.einsum("nec,nd->ecd", comb, xf)                        # [E,C,d]
    buf = shard(buf, "expert", None, None)
    out_buf = shard(_expert_mlp(p["experts"], buf, use_kernels), "expert", None, None)
    comb_w = jnp.einsum("nke,nkc,nk->nec", disp, poh, w.astype(xf.dtype))
    y = jnp.einsum("nec,ecd->nd", comb_w, out_buf)

    if e.n_shared:
        y = y + mlp(p["shared"], xf, "swiglu")
    return y.reshape(b, s, d), aux


def moe_ffn_sort(p, x, cfg: ModelConfig, rng=None, use_kernels: bool = False):
    """Sort-based capacity dispatch (production path, large E).

    No [N,E,·] one-hot tensors: (token,k) pairs are argsorted by expert id,
    ranked within their expert group, and scatter-added into the [E,C,d]
    buffer (overflow rows drop to a dummy slot).  Combine is the transpose
    gather.  Memory: O(N·k·d) expanded activations — the true MoE dispatch
    cost — sharded over data (tokens) and expert (buffers) axes so GSPMD
    lowers the exchange to an all-to-all.
    """
    e = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    top_w, top_idx, aux = route(p["router"], xf, e, rng)

    cap = _capacity(n, e)
    nk = n * e.top_k
    expert_flat = top_idx.reshape(nk)                                # [NK]
    tok_flat = jnp.repeat(jnp.arange(n, dtype=jnp.int32), e.top_k)   # [NK]
    w_flat = top_w.reshape(nk)

    # rank within expert group via stable argsort (no [NK,E] one-hot)
    order = jnp.argsort(expert_flat, stable=True)                    # [NK]
    sorted_e = expert_flat[order]
    counts = jnp.zeros((e.n_experts,), jnp.int32).at[expert_flat].add(1)
    starts = jnp.cumsum(counts) - counts                             # [E]
    pos_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((nk,), jnp.int32).at[order].set(pos_sorted)      # [NK]

    keep = pos < cap
    slot = jnp.where(keep, expert_flat * cap + pos, e.n_experts * cap)
    gathered = xf[tok_flat] * keep[:, None].astype(xf.dtype)         # [NK,d]
    buf = jnp.zeros((e.n_experts * cap + 1, d), xf.dtype).at[slot].add(gathered)
    buf = buf[: e.n_experts * cap].reshape(e.n_experts, cap, d)
    buf = shard(buf, "expert", None, None)

    out_buf = shard(_expert_mlp(p["experts"], buf, use_kernels), "expert", None, None)

    out_rows = out_buf.reshape(e.n_experts * cap, d)[jnp.minimum(slot, e.n_experts * cap - 1)]
    out_rows = out_rows * (w_flat * keep)[:, None].astype(xf.dtype)  # [NK,d]
    y = out_rows.reshape(n, e.top_k, d).sum(axis=1)

    if e.n_shared:
        y = y + mlp(p["shared"], xf, "swiglu")
    return y.reshape(b, s, d), aux


def moe_ffn_share(p, x, cfg: ModelConfig, use_kernels: bool = False):
    """One chip's expert-parallel share of an MoE layer, dropless.

    Routes every token over all ``n_experts`` and computes every (token,
    expert) pair whose expert lies in ``[expert_offset, expert_offset +
    experts_held)``: each held expert's tokens are packed into its rows of
    an [H, C, d] buffer (a token reaches an expert at most once, so C >= N
    rows hold every pair), run through the grouped expert GEMM, which
    skips the rows past each expert's pairs, and summed back with their
    routing weights.  The output is the shared expert plus
    the held experts' part; what the other experts add is computed by the
    chips that hold them and is not part of this layer.

    Returns (y, aux) with ``aux["moe_held"]`` = int32 [pairs that reached a
    held expert, held experts that received at least one pair].
    """
    e = cfg.moe
    b, s, d = x.shape
    n = b * s
    held = e.experts_held
    xf = x.reshape(n, d)
    # rows an expert: every token, padded to a multiple of 8 (of 128 past
    # 128 tokens: a TPU row gather at an unaligned count compiles ~100x
    # slower at d 7168)
    cap = -(-n // 8) * 8 if n <= 128 else -(-n // 128) * 128
    with jax.named_scope("router"):
        top_w, top_idx, aux = route(p["router"], xf, e)
        local = top_idx - e.expert_offset                              # [N,k]
        pick = (local[:, :, None] == jnp.arange(held)) \
            & ((local >= 0) & (local < held))[:, :, None]               # [N,k,H]
        sel = jnp.pad(pick.any(1).T, ((0, 0), (0, cap - n)))           # [H,C]
        w = jnp.pad(jnp.einsum("nkh,nk->hn", pick.astype(jnp.float32), top_w),
                    ((0, 0), (0, cap - n)))                            # [H,C]
        rows = sel.sum(1, dtype=jnp.int32)                             # [H]
    with jax.named_scope("experts"):
        # each expert's tokens first, in token order: token t sits at row
        # slot[h, t] of expert h, and row j < rows[h] holds token order[h, j]
        slot = jnp.cumsum(sel, axis=1, dtype=jnp.int32) - 1            # [H,C]
        order = jnp.zeros((held, cap), jnp.int32).at[
            jnp.arange(held)[:, None], jnp.where(sel, slot, cap)].set(
            jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (held, cap)),
            mode="drop")
        xp = jnp.pad(xf, ((0, cap - n), (0, 0)))
        live = (jnp.arange(cap) < rows[:, None])[..., None]
        buf = jnp.where(live, xp[order], jnp.zeros((), xf.dtype))      # [H,C,d]
        out = _expert_mlp(p["experts"], buf, use_kernels, rows)
        back = jnp.take_along_axis(out, jnp.maximum(slot, 0)[..., None], axis=1)
        back = jnp.where(sel[..., None], back, jnp.zeros((), back.dtype))
        y = jnp.einsum("hn,hnd->nd", w, back.astype(jnp.float32))[:n]
        y = y.astype(xf.dtype)
    if e.n_shared:
        with jax.named_scope("shared_expert"):
            y = y + mlp(p["shared"], xf, "swiglu")
    aux = dict(aux, moe_held=jnp.stack([rows.sum(), (rows > 0).sum(dtype=jnp.int32)]))
    return y.reshape(b, s, d), aux


def moe_ffn(p, x, cfg: ModelConfig, rng=None, use_kernels: bool = False):
    e = cfg.moe
    if e.experts_held is not None:
        return moe_ffn_share(p, x, cfg, use_kernels)
    if e.n_experts > 32:
        return moe_ffn_sort(p, x, cfg, rng, use_kernels)
    return moe_ffn_dense(p, x, cfg, rng, use_kernels)


def init_ffn(key, cfg: ModelConfig):
    if cfg.moe is not None:
        return init_moe(key, cfg)
    return init_mlp(key, cfg.d_model, cfg.d_ff, cfg.act, cfg.dtype)


def ffn(p, x, cfg: ModelConfig, rng=None, use_kernels=False):
    if cfg.moe is not None:
        return moe_ffn(p, x, cfg, rng, use_kernels)
    return mlp(p, x, cfg.act), {}
