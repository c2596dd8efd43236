"""Attention: GQA (optionally sliding-window) and MLA (DeepSeek-style),
with prefill and single-token-decode paths and an explicit KV cache.

Kernel dispatch: when ``use_kernels=True`` (and shapes are TPU-tileable) the
prefill path calls the Pallas flash-attention kernel and the decode path the
split-KV decode kernel; otherwise the pure-jnp reference math runs (identical
semantics — tests assert allclose).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig
from ..utils import shard
from .layers import apply_rope, init_linear, linear, yarn_mscale

NEG_INF = -1e30


# -- masks --------------------------------------------------------------------

def causal_window_mask(q_pos, k_pos, window: int | None):
    """[qs, ks] boolean: causal AND within window (window=None → pure causal)."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


# -- GQA ----------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig):
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, h * hd, cfg.qkv_bias, cfg.dtype),
        "wk": init_linear(ks[1], d, kvh * hd, cfg.qkv_bias, cfg.dtype),
        "wv": init_linear(ks[2], d, kvh * hd, cfg.qkv_bias, cfg.dtype),
        "wo": init_linear(ks[3], h * hd, d, False, cfg.dtype),
    }


def _sdpa(q, k, v, mask, use_kernels: bool = False, scale: float | None = None):
    """q: [B,S,H,Dk]; k: [B,T,KVH,Dk]; v: [B,T,KVH,Dv];
    mask: [S,T] or [B,S,T] or None.  Dv may differ from Dk (MLA)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    dv = v.shape[-1]
    if use_kernels and mask is not None and mask.ndim == 2 and dv == d:
        from ..kernels.flash_attention.ops import flash_attention_tpu_or_ref
        return flash_attention_tpu_or_ref(q, k, v, mask)
    groups = h // kvh
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, s, kvh, groups, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    logits *= scale
    if mask is not None:
        m = mask if mask.ndim == 2 else mask[:, None, None]
        logits = jnp.where(m, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, dv).astype(q.dtype)


# -- chunked flash-structured attention (pure jnp, production shapes) ---------

_CHUNK_THRESHOLD = 1 << 22        # s*t above which we never materialize [S,T]
MLA_Q_CHUNK = 512                 # query rows per chunk of MLA prefill
# roofline hook: "single" forces one chunk (scan trip=1) so cost_analysis
# counts attention exactly (launch/roofline.py); None = production chunking.
_CHUNK_OVERRIDE: str | None = None


def _pad_axis(x, axis: int, to: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pad) if to > x.shape[axis] else x


def _chunk_mask(q_pos, k_pos, t, causal, window_f):
    """[qc,kc] bool from absolute positions. window_f: fp32 scalar (<=0 off)."""
    mask = k_pos[None, :] < t
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    mask &= jnp.where(window_f > 0,
                      k_pos[None, :].astype(jnp.float32)
                      > (q_pos[:, None].astype(jnp.float32) - window_f),
                      True)
    return mask


def _bcast_heads(x, g):
    """[b,t,kvh,d] → [b,t,kvh*g,d]: per-chunk KV broadcast so the attention
    einsums keep ONE head axis (h = kvh·g) that TP shards cleanly.  The g×
    duplication only ever exists for one chunk in VMEM-scale buffers."""
    if g == 1:
        return x
    return jnp.repeat(x, g, axis=2)


def _flash_fwd(q, k, v, window_f, *, causal, scale, qc, kc, t_true):
    """Returns (out [B,S2,H,Dv], lse [b,h,S2]) on padded length S2."""
    b, s2, h, dk = q.shape
    _, t2, kvh, _ = k.shape
    dv = v.shape[-1]
    g = h // kvh
    t = t_true  # padded KV rows (k_pos >= t_true) masked inside _chunk_mask

    nq, nk = s2 // qc, t2 // kc
    qs = jnp.moveaxis(q.reshape(b, nq, qc, h, dk), 1, 0)
    ks = jnp.moveaxis(k.reshape(b, nk, kc, kvh, dk), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, kc, kvh, dv), 1, 0)

    from ..flags import causal_skip

    def q_step(_, qi_blk):
        qi, qblk = qi_blk
        q_pos = qi * qc + jnp.arange(qc)

        def kv_work(carry, kj, kblk, vblk):
            m, l, acc = carry
            kb = _bcast_heads(kblk, g)                   # [b,kc,h,dk]
            vb = _bcast_heads(vblk, g)
            k_pos = kj * kc + jnp.arange(kc)
            logits = jnp.einsum("bchd,bthd->bhct", qblk, kb,
                                preferred_element_type=jnp.float32) * scale
            logits = shard(logits, "batch", "heads", None, None)
            mask = _chunk_mask(q_pos, k_pos, t, causal, window_f)
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(-1))       # [b,h,qc]
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhct,bthd->bhcd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc = acc * alpha[..., None] + pv
            return m_new, l, acc

        def kv_step(carry, kj_blk):
            kj, kblk, vblk = kj_blk
            if causal and causal_skip():
                # §Perf O5: a KV chunk entirely in the causal future (or
                # entirely outside the window) contributes nothing — skip
                # its matmuls at runtime via cond (≈ halves prefill flops).
                above = kj * kc > qi * qc + (qc - 1)
                below = jnp.logical_and(
                    window_f > 0,
                    (kj + 1) * kc - 1 < qi * qc - window_f + 1)
                skip = jnp.logical_or(above, below)
                carry = jax.lax.cond(
                    skip, lambda c: c,
                    lambda c: kv_work(c, kj, kblk, vblk), carry)
                return carry, None
            return kv_work(carry, kj, kblk, vblk), None

        m0 = jnp.full((b, h, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        a0 = jnp.zeros((b, h, qc, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]                    # [b,h,qc,dv]
        out = jnp.moveaxis(out, 2, 1)                    # [b,qc,h,dv]
        lse = m + jnp.log(l_safe)                        # [b,h,qc]
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s2, h, dv)
    lse = jnp.moveaxis(lses, 0, 2).reshape(b, h, s2)     # [nq,b,h,qc]→[b,h,S2]
    return out, lse


def _flash_bwd_impl(q, k, v, window_f, out, lse, dout, *, causal, scale, qc, kc,
                    t_true):
    """FlashAttention backward: recompute p per chunk from saved lse.

    Outer scan over KV chunks (yields dk,dv per chunk), inner scan over Q
    chunks (accumulates dq as a carry).  Memory: O(chunk²) per step.
    """
    b, s2, h, dk = q.shape
    _, t2, kvh, _ = k.shape
    dv_dim = v.shape[-1]
    g = h // kvh
    t = t_true
    nq, nk = s2 // qc, t2 // kc

    qs = jnp.moveaxis(q.reshape(b, nq, qc, h, dk), 1, 0)
    dos = jnp.moveaxis(dout.reshape(b, nq, qc, h, dv_dim), 1, 0)
    ks = jnp.moveaxis(k.reshape(b, nk, kc, kvh, dk), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, kc, kvh, dv_dim), 1, 0)
    # D = rowsum(dout ⊙ out) [b,h,S2]
    dsum = jnp.einsum("bshd,bshd->bsh", dout.astype(jnp.float32),
                      out.astype(jnp.float32))
    dsum = jnp.moveaxis(dsum, 1, 2)                      # [b,h,S2]
    dsums = jnp.moveaxis(dsum.reshape(b, h, nq, qc), 2, 0)   # [nq,b,h,qc]
    lses = jnp.moveaxis(lse.reshape(b, h, nq, qc), 2, 0)

    def kv_step(dq_acc, kj_blk):
        kj, kblk, vblk = kj_blk
        kb = _bcast_heads(kblk, g)                       # [b,kc,h,dk]
        vb = _bcast_heads(vblk, g)
        k_pos = kj * kc + jnp.arange(kc)

        def q_step(carry, qi_blk):
            dkj, dvj, dq_acc = carry
            qi, qblk, doblk, lse_i, dsum_i = qi_blk
            q_pos = qi * qc + jnp.arange(qc)
            logits = jnp.einsum("bchd,bthd->bhct", qblk, kb,
                                preferred_element_type=jnp.float32) * scale
            logits = shard(logits, "batch", "heads", None, None)
            mask = _chunk_mask(q_pos, k_pos, t, causal, window_f)
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            p = jnp.exp(logits - lse_i[..., None])       # [b,h,qc,kc]
            dp = jnp.einsum("bchd,bthd->bhct", doblk, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dsum_i[..., None]) * scale    # [b,h,qc,kc]
            dvj = dvj + jnp.einsum("bhct,bchd->bthd", p.astype(doblk.dtype),
                                   doblk, preferred_element_type=jnp.float32)
            dkj = dkj + jnp.einsum("bhct,bchd->bthd", ds.astype(qblk.dtype),
                                   qblk, preferred_element_type=jnp.float32)
            dq_i = jnp.einsum("bhct,bthd->bchd", ds.astype(kb.dtype), kb,
                              preferred_element_type=jnp.float32)
            dq_acc = dq_acc.at[qi].add(dq_i)
            return (dkj, dvj, dq_acc), None

        dk_h0 = jnp.zeros((b, kc, h, dk), jnp.float32)
        dv_h0 = jnp.zeros((b, kc, h, dv_dim), jnp.float32)
        (dkj, dvj, dq_acc), _ = jax.lax.scan(
            q_step, (dk_h0, dv_h0, dq_acc),
            (jnp.arange(nq), qs, dos, lses, dsums))
        # fold the broadcast heads back onto kv heads
        dkj = dkj.reshape(b, kc, kvh, g, dk).sum(3)
        dvj = dvj.reshape(b, kc, kvh, g, dv_dim).sum(3)
        return dq_acc, (dkj, dvj)

    dq0 = jnp.zeros((nq, b, qc, h, dk), jnp.float32)
    dq_acc, (dks, dvs) = jax.lax.scan(kv_step, dq0, (jnp.arange(nk), ks, vs))
    dq = jnp.moveaxis(dq_acc, 0, 1).reshape(b, s2, h, dk).astype(q.dtype)
    dk_out = jnp.moveaxis(dks, 0, 1).reshape(b, t2, kvh, dk).astype(k.dtype)
    dv_out = jnp.moveaxis(dvs, 0, 1).reshape(b, t2, kvh, dv_dim).astype(v.dtype)
    return dq, dk_out, dv_out


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, scale: float, qc: int, kc: int, t_true: int):
    kwargs = dict(causal=causal, scale=scale, qc=qc, kc=kc, t_true=t_true)

    @jax.custom_vjp
    def flash(q, k, v, window_f):
        out, _ = _flash_fwd(q, k, v, window_f, **kwargs)
        return out

    def fwd(q, k, v, window_f):
        out, lse = _flash_fwd(q, k, v, window_f, **kwargs)
        return out, (q, k, v, window_f, out, lse)

    def bwd(res, dout):
        q, k, v, window_f, out, lse = res
        dq, dk, dv = _flash_bwd_impl(q, k, v, window_f, out, lse, dout, **kwargs)
        return dq, dk, dv, jnp.zeros_like(window_f)

    flash.defvjp(fwd, bwd)
    return flash


def chunked_attention(q, k, v, *, causal: bool = True, window=None,
                      scale: float | None = None,
                      q_chunk: int = 2048, kv_chunk: int = 2048):
    """Flash-structured attention in pure jnp with a flash custom-VJP:
    O(S·chunk) memory forward AND backward (p recomputed from saved LSE).
    The jnp twin of the Pallas flash kernel; every production prefill/train
    cell lowers through here (naive attention would claim [S,T] buffers no
    HBM holds).

    q: [B,S,H,Dk]; k: [B,T,KVH,Dk]; v: [B,T,KVH,Dv].  ``window`` may be a
    traced scalar (cast to fp32; <=0 or >=2^29 disables).
    """
    b, s, h, dk = q.shape
    _, t, kvh, _ = k.shape
    scale = dk ** -0.5 if scale is None else scale

    if _CHUNK_OVERRIDE == "single":
        q_chunk, kv_chunk = s, t
    qc = min(q_chunk, s)
    kc = min(kv_chunk, t)
    s2 = -(-s // qc) * qc
    t2 = -(-t // kc) * kc
    qp = _pad_axis(q, 1, s2)
    kp = _pad_axis(k, 1, t2)
    vp = _pad_axis(v, 1, t2)
    if window is None:
        window_f = jnp.float32(0.0)
    else:
        wf = jnp.asarray(window).astype(jnp.float32)
        window_f = jnp.where(wf >= jnp.float32(1 << 29), 0.0, wf)
    flash = _make_flash(causal, float(scale), qc, kc, t)
    return flash(qp, kp, vp, window_f)[:, :s]


def gqa_prefill(p, x, cfg: ModelConfig, positions, window=None, use_kernels=False):
    """Returns (attn_out [B,S,d_model], (k_cache, v_cache) [B,S,KVH,D])."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(b, s, h, hd)
    k = linear(p["wk"], x).reshape(b, s, kvh, hd)
    v = linear(p["wv"], x).reshape(b, s, kvh, hd)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    if use_kernels:
        from ..kernels.flash_attention.ops import flash_attention_tpu_or_ref
        out = flash_attention_tpu_or_ref(q, k, v, None)
    elif s * s > _CHUNK_THRESHOLD:
        out = chunked_attention(q, k, v, causal=True, window=window)
    else:
        mask = causal_window_mask(positions[0], positions[0], window)
        out = _sdpa(q, k, v, mask)
    y = linear(p["wo"], out.reshape(b, s, h * hd))
    return shard(y, "batch", "seq", "embed"), (k, v)


def gqa_decode(p, x, cache_kv, pos, cfg: ModelConfig, window=None, use_kernels=False):
    """One-token decode. x: [B,1,d]; cache_kv: (k,v) [B,T,KVH,D]; pos: [B] int.

    Writes the new K/V at ``pos`` and attends over positions <= pos (and
    within the window).  Cache length T is static.
    """
    k_cache, v_cache = cache_kv
    b, t = k_cache.shape[0], k_cache.shape[1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(b, 1, h, hd)
    k = linear(p["wk"], x).reshape(b, 1, kvh, hd)
    v = linear(p["wv"], x).reshape(b, 1, kvh, hd)
    if cfg.rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
    from ..flags import cache_update_mode
    if cache_update_mode() == "scatter":
        # §Perf O1: scatter writes ONE slot per sequence (aliasable in-place
        # update) instead of the where-select that rewrites the full cache.
        rows = jnp.arange(b)
        k_cache = k_cache.at[rows, pos].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, pos].set(v[:, 0].astype(v_cache.dtype))
    else:
        idx = pos[:, None, None, None]
        onehot = (jnp.arange(t)[None, :, None, None] == idx)
        k_cache = jnp.where(onehot, k.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(onehot, v.astype(v_cache.dtype), v_cache)
    from ..flags import window_slice_decode
    w_static = cfg.window                               # static per-arch bound
    if (window_slice_decode() and w_static is not None
            and w_static + 1 + cfg.meta_tokens < t):
        # §Perf O6: windowed layers read only window+1 cache slots via a
        # per-sequence dynamic slice; global layers (traced window ≥ 2^29)
        # take the full-cache branch of the cond.
        size = w_static + 1

        def windowed(_):
            start = jnp.clip(pos - w_static, 0, t - size)      # [B]
            ks = jax.vmap(lambda c, s0: jax.lax.dynamic_slice_in_dim(
                c, s0, size, axis=0))(k_cache, start)          # [B,size,KVH,D]
            vs = jax.vmap(lambda c, s0: jax.lax.dynamic_slice_in_dim(
                c, s0, size, axis=0))(v_cache, start)
            k_pos_w = start[:, None] + jnp.arange(size)[None]  # [B,size]
            ok = (k_pos_w <= pos[:, None]) & (k_pos_w > (pos[:, None] - w_static))
            return _sdpa(q, ks, vs, ok[:, None, :])

        def full(_):
            k_pos = jnp.arange(t)[None, :]
            ok = k_pos <= pos[:, None]
            ok &= k_pos > (pos[:, None] - window)
            return _sdpa(q, k_cache, v_cache, ok[:, None, :])

        is_windowed = window < jnp.int32(1 << 29)
        out = jax.lax.cond(is_windowed, windowed, full, operand=None)
    else:
        k_pos = jnp.arange(t)[None, :]                  # [1,T]
        valid = k_pos <= pos[:, None]
        if window is not None:
            valid &= k_pos > (pos[:, None] - window)
        if use_kernels:
            from ..kernels.decode_attention.ops import decode_attention_tpu_or_ref
            out = decode_attention_tpu_or_ref(q[:, 0], k_cache, v_cache, valid)
            out = out[:, None]
        else:
            out = _sdpa(q, k_cache, v_cache, valid[:, None, :])  # [b,s=1,t]
    y = linear(p["wo"], out.reshape(b, 1, h * hd))
    return y, (k_cache, v_cache)


# -- MLA (DeepSeek-V3) --------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    assert m is not None
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 7)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": init_linear(ks[0], d, m.q_lora_rank, False, cfg.dtype),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), cfg.dtype)},
        "wq_b": init_linear(ks[1], m.q_lora_rank, h * qk_head, False, cfg.dtype),
        "wkv_a": init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, False, cfg.dtype),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), cfg.dtype)},
        "wk_b": init_linear(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim, False, cfg.dtype),
        "wv_b": init_linear(ks[4], m.kv_lora_rank, h * m.v_head_dim, False, cfg.dtype),
        "wo": init_linear(ks[5], h * m.v_head_dim, d, False, cfg.dtype),
    }


def _mla_qkv(p, x, cfg: ModelConfig, positions):
    """Shared projection math. Returns q_nope,q_rope,c_kv,k_rope."""
    from .layers import rmsnorm
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    cq = rmsnorm(p["q_norm"], linear(p["wq_a"], x))
    q = linear(p["wq_b"], cq).reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    kv = linear(p["wkv_a"], x)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv)                  # [B,S,rank]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times mscale(mscale_all_dim)^2 under YaRN (the
    published DeepSeek attention's softmax scale)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def mla_attention(p, q_nope, q_rope, c_kv, k_rope, cfg: ModelConfig,
                  mask=None, chunked: bool = False):
    """Latent attention via the absorbed formulation: MLA ≡ GQA with ONE
    shared latent KV head.

    q_lat = q_nope @ W_kbᵀ (per head, absorbed so the cache stays
    compressed); q_cat = [q_lat ‖ q_rope] against k_cat = [c_kv ‖ k_rope]
    with V = c_kv — a single kvh=1 attention with Dk = rank+rope, Dv = rank.
    This routes MLA through the exact same naive/chunked/flash machinery as
    GQA (and the chunked path keeps 32k×32k cells O(S·chunk)).
    """
    m = cfg.mla
    b, s, h, _ = q_nope.shape
    rank = m.kv_lora_rank
    scale = mla_softmax_scale(cfg)
    with jax.named_scope("latent_attention"):
        wk_b = p["wk_b"]["w"].reshape(rank, h, m.qk_nope_head_dim)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b,
                           preferred_element_type=jnp.float32).astype(q_nope.dtype)
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)   # [B,S,H,rank+rope]
        k_cat = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
        v_lat = c_kv[:, :, None, :]                         # [B,T,1,rank]
        if chunked:
            # 128 heads x 576 dims: query chunks of 512 keep one chunk's
            # fp32 scores (H x 512 x 2048) near half a GB
            lat = chunked_attention(q_cat, k_cat, v_lat, causal=True,
                                    window=None, scale=scale,
                                    q_chunk=MLA_Q_CHUNK)
        else:
            lat = _sdpa(q_cat, k_cat, v_lat, mask, scale=scale)  # [B,S,H,rank]
        wv_b = p["wv_b"]["w"].reshape(rank, h, m.v_head_dim)
        out = jnp.einsum("bshr,rhd->bshd", lat, wv_b,
                         preferred_element_type=jnp.float32).astype(c_kv.dtype)
    return linear(p["wo"], out.reshape(b, s, h * m.v_head_dim))


def mla_prefill(p, x, cfg: ModelConfig, positions, use_kernels=False):
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions)
    s = x.shape[1]
    if s * s > _CHUNK_THRESHOLD:
        y = mla_attention(p, q_nope, q_rope, c_kv, k_rope, cfg, chunked=True)
    else:
        mask = causal_window_mask(positions[0], positions[0], None)
        y = mla_attention(p, q_nope, q_rope, c_kv, k_rope, cfg, mask=mask)
    return shard(y, "batch", "seq", "embed"), (c_kv, k_rope)


def mla_decode(p, x, cache, pos, cfg: ModelConfig, use_kernels=False):
    from ..flags import cache_update_mode, kv_quant
    quant = kv_quant() and len(cache) == 3
    if quant:
        c_q, c_scale, r_cache = cache   # int8 [B,T,rank], f16 [B,T], bf16 rope
        b, t = c_q.shape[0], c_q.shape[1]
    else:
        c_cache, r_cache = cache                        # [B,T,rank], [B,T,rope]
        b, t = c_cache.shape[0], c_cache.shape[1]
    q_nope, q_rope, c_new, r_new = _mla_qkv(p, x, cfg, pos[:, None])
    rows = jnp.arange(b)
    if quant:
        # quantize the new latent token: per-token absmax scale
        scale_new = jnp.maximum(jnp.max(jnp.abs(c_new[:, 0]), axis=-1), 1e-6)
        c_new_q = jnp.clip(jnp.round(c_new[:, 0] / scale_new[:, None] * 127.0),
                           -127, 127).astype(jnp.int8)
        c_q = c_q.at[rows, pos].set(c_new_q)
        c_scale = c_scale.at[rows, pos].set((scale_new / 127.0).astype(jnp.float16))
        r_cache = r_cache.at[rows, pos].set(r_new[:, 0].astype(r_cache.dtype))
        c_cache = (c_q.astype(jnp.bfloat16)
                   * c_scale[..., None].astype(jnp.bfloat16))
        new_cache = (c_q, c_scale, r_cache)
    elif cache_update_mode() == "scatter":
        c_cache = c_cache.at[rows, pos].set(c_new[:, 0].astype(c_cache.dtype))
        r_cache = r_cache.at[rows, pos].set(r_new[:, 0].astype(r_cache.dtype))
        new_cache = (c_cache, r_cache)
    else:
        onehot2 = (jnp.arange(t)[None, :, None] == pos[:, None, None])
        c_cache = jnp.where(onehot2, c_new.astype(c_cache.dtype), c_cache)
        r_cache = jnp.where(onehot2, r_new.astype(r_cache.dtype), r_cache)
        new_cache = (c_cache, r_cache)
    valid = jnp.arange(t)[None, :] <= pos[:, None]      # [B,T]
    y = mla_attention(p, q_nope, q_rope, c_cache, r_cache, cfg,
                      mask=valid[:, None, :])           # [B,1,T] = [b,s,t]
    return y, new_cache


# -- paged decode (block-table KV) --------------------------------------------

def gqa_paged_decode(p, x, pool, layer, block_tables, pos, cfg: ModelConfig,
                     window=None, use_kernels=False):
    """One-token decode against paged KV. x: [B,1,d]; pool: (k,v) the whole
    stack's [L,P,KVH,D,ps]; layer: this layer's index in the stack;
    block_tables: [B,MAXP] int32; pos: [B] int.

    Writes the new K/V in place at ``(layer, table[pos//ps], :, :, pos%ps)`` and
    attends positions ``[max(0, pos-window+1), pos]`` through the block
    table — there is no per-sequence dense slab, and no layer's pool is
    sliced out of the stack.  ``window`` may be the traced sentinel (>= 2^29
    disables): the start clamp maps it to 0.
    """
    k_pool, v_pool = pool
    ps = k_pool.shape[4]
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(b, 1, h, hd)
    k = linear(p["wk"], x).reshape(b, 1, kvh, hd)
    v = linear(p["wv"], x).reshape(b, 1, kvh, hd)
    if cfg.rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
    rows = jnp.arange(b)
    page = block_tables[rows, pos // ps]                # [B] physical pages
    off = pos % ps
    with jax.named_scope("pool_write"):
        k_pool = write_positions(k_pool, k[:, 0], page, off, layer)
        v_pool = write_positions(v_pool, v[:, 0], page, off, layer)
    lengths = (pos + 1).astype(jnp.int32)
    starts = None
    if window is not None:
        starts = jnp.clip(pos - window + 1, 0).astype(jnp.int32)
    with jax.named_scope("attention"):
        if use_kernels:
            from ..kernels.paged_decode.ops import paged_decode_attention
            out = paged_decode_attention(q[:, 0], k_pool, v_pool,
                                         block_tables, lengths, starts,
                                         layer=layer)
        else:
            from ..kernels.paged_decode.ref import paged_decode_attention_ref
            out = paged_decode_attention_ref(q[:, 0], k_pool, v_pool,
                                             block_tables, lengths, starts,
                                             layer=layer)
    y = linear(p["wo"], out.reshape(b, 1, h * hd))
    return y, (k_pool, v_pool)


def mla_paged_decode(p, x, pool, layer, block_tables, pos, cfg: ModelConfig,
                     use_kernels=False):
    """Paged MLA decode over the stack's latent pool ``([L,P,1,rank+rope,ps],)``
    (each position ``[ckv ‖ kpe]``) via matrix absorption — see
    :func:`mla_attention` for the math."""
    m = cfg.mla
    (lat_pool,) = pool
    ps = lat_pool.shape[4]
    b = x.shape[0]
    h, rank = cfg.n_heads, m.kv_lora_rank
    q_nope, q_rope, c_new, r_new = _mla_qkv(p, x, cfg, pos[:, None])
    rows = jnp.arange(b)
    page = block_tables[rows, pos // ps]
    off = pos % ps
    with jax.named_scope("pool_write"):
        row = jnp.concatenate([c_new[:, 0], r_new[:, 0]], axis=-1)
        lat_pool = write_positions(lat_pool, row[:, None], page, off, layer)
    lengths = (pos + 1).astype(jnp.int32)
    scale = mla_softmax_scale(cfg)
    with jax.named_scope("latent_attention"):
        wk_b = p["wk_b"]["w"].reshape(rank, h, m.qk_nope_head_dim)
        if use_kernels:
            from ..kernels.paged_decode.ops import paged_mla_decode_attention
            lat = paged_mla_decode_attention(q_nope[:, 0], q_rope[:, 0],
                                             lat_pool, wk_b, block_tables,
                                             lengths, scale, layer)
        else:
            from ..kernels.paged_decode.ref import paged_decode_attention_ref
            q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wk_b,
                               preferred_element_type=jnp.float32).astype(x.dtype)
            q_cat = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
            lat = paged_decode_attention_ref(q_cat, lat_pool, None,
                                             block_tables, lengths, None,
                                             scale, layer, rank)
        wv_b = p["wv_b"]["w"].reshape(rank, h, m.v_head_dim)
        out = jnp.einsum("bhr,rhd->bhd", lat, wv_b,
                         preferred_element_type=jnp.float32).astype(x.dtype)
    y = linear(p["wo"], out.reshape(b, 1, h * m.v_head_dim))
    return y, (lat_pool,)


def attn_paged_decode(p, x, pool, layer, block_tables, pos, cfg, window=None,
                      use_kernels=False):
    if cfg.mla is not None:
        return mla_paged_decode(p, x, pool, layer, block_tables, pos, cfg,
                                use_kernels)
    return gqa_paged_decode(p, x, pool, layer, block_tables, pos, cfg, window,
                            use_kernels)


def init_paged_cache(cfg: ModelConfig, num_layers: int, num_pages: int,
                     page_size: int, dtype=None):
    """One layer stack's page pool in the kernel's layout (page 0 reserved
    as the null page), positions on the minor axis: GQA/MHA ``(k, v)``, each
    [L, P, KVH, D, ps]; MLA one latent pool ``([L, P, 1, rank + rope, ps],)``
    of ``[ckv ‖ kpe]`` columns.

    MLA always pages the *compressed* latent cache (no int8 variant — the
    engine gates ``kv_quant`` off the paged path).  Materialized with
    ``jnp.zeros`` so ``nbytes`` honestly reports the paged footprint."""
    dtype = dtype or cfg.dtype
    if cfg.mla is not None:
        m = cfg.mla
        return (jnp.zeros((num_layers, num_pages, 1,
                           m.kv_lora_rank + m.qk_rope_head_dim, page_size),
                          dtype),)
    shape = (num_layers, num_pages, cfg.n_kv_heads, cfg.head_dim, page_size)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def write_positions(pool, rows, page, off, layer=0):
    """``rows[b]`` ([KVH, D], or [L', KVH, D] for layers ``layer ..``) into
    position ``off[b]`` of page ``page[b]`` of a pool [L, P, KVH, D, ps]:
    one ``dynamic_update_slice`` a row, which XLA does in place whatever
    layout the pool has on the device (a scatter would relayout it)."""
    for b in range(rows.shape[0]):
        upd = rows[b].reshape(-1, 1, *pool.shape[2:4], 1).astype(pool.dtype)
        pool = jax.lax.dynamic_update_slice(
            pool, upd, (layer, page[b], 0, 0, off[b]))
    return pool


def pool_rows(cfg: ModelConfig, cache):
    """A stack's dense cache (leaves [L, B, T, ...], as prefill and dense
    decode keep it) as rows of its page pool's leaves: [L, B, T, KVH, D]
    (MLA: one [L, B, T, 1, rank + rope] of ``[ckv ‖ kpe]``)."""
    if cfg.mla is not None:
        c, r = cache
        return (jnp.concatenate([c, r.astype(c.dtype)], -1)[..., None, :],)
    return cache


def dense_cache(cfg: ModelConfig, rows):
    """The inverse of :func:`pool_rows`."""
    if cfg.mla is not None:
        (lat,) = rows
        return (lat[..., 0, :cfg.mla.kv_lora_rank],
                lat[..., 0, cfg.mla.kv_lora_rank:])
    return rows


# -- dispatch -----------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    return init_mla(key, cfg) if cfg.mla is not None else init_gqa(key, cfg)


def attn_prefill(p, x, cfg, positions, window=None, use_kernels=False):
    if cfg.mla is not None:
        return mla_prefill(p, x, cfg, positions, use_kernels)
    return gqa_prefill(p, x, cfg, positions, window, use_kernels)


def attn_decode(p, x, cache, pos, cfg, window=None, use_kernels=False):
    if cfg.mla is not None:
        return mla_decode(p, x, cache, pos, cfg, use_kernels)
    return gqa_decode(p, x, cache, pos, cfg, window, use_kernels)


def init_cache(cfg: ModelConfig, batch: int, length: int, dtype=None):
    """Empty per-layer KV cache (single layer); transformer stacks [L, ...]."""
    dtype = dtype or cfg.dtype
    if cfg.mla is not None:
        m = cfg.mla
        from ..flags import kv_quant
        if kv_quant():
            # §Perf O8: int8 latent + per-token fp16 scale (+ bf16 rope keys)
            return (jnp.zeros((batch, length, m.kv_lora_rank), jnp.int8),
                    jnp.zeros((batch, length), jnp.float16),
                    jnp.zeros((batch, length, m.qk_rope_head_dim), dtype))
        return (jnp.zeros((batch, length, m.kv_lora_rank), dtype),
                jnp.zeros((batch, length, m.qk_rope_head_dim), dtype))
    return (jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
            jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype))
