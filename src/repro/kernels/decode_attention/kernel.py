"""Split-KV flash decoding: one query token against a long KV cache.

Decode is pure HBM bandwidth (read the whole cache once); the kernel's job
is to stream KV tiles through VMEM at line rate with the online-softmax
epilogue fused (no [T]-sized logits round-trip to HBM).  Validity masking
(cache positions beyond ``pos``/outside the window) comes in as a bool mask
so ring/window policies stay outside the kernel.

    q: [B, H, D]   k,v: [B, KVH, T, D]   valid: [B, T]  →  out: [B, H, D]

The query is viewed as ``[B, KVH, G, D]`` (``G = H / KVH``, heads of one
KV group adjacent) and one block carries all G query heads of a KV head:
each KV tile is read once per KV head, and the block's last two dims equal
the array's, which the TPU lowering requires for head counts that are not
multiples of 8.  Grid: (B, KVH, T/bk), KV tiles innermost (sequential
accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                    # [G, D]
    k = k_ref[0, 0]                                    # [bk, D]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # [G,bk]
    valid = valid_ref[0]                               # [1, bk] int32 mask
    s = jnp.where(valid > 0, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == pl.num_programs(2) - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_pallas(
    q: jax.Array,       # [B, H, D]
    k: jax.Array,       # [B, KVH, T, D]
    v: jax.Array,
    valid: jax.Array,   # [B, T] int32 (1 = attendable)
    bk: int = 512,
    interpret: bool = True,
) -> jax.Array:
    b, h, d = q.shape
    _, kvh, t, _ = k.shape
    g = h // kvh
    bk = min(bk, t)
    assert t % bk == 0
    grid = (b, kvh, t // bk)
    kernel = functools.partial(_kernel, scale=d ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, hh, kk: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, kk: (bb, hh, kk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, kk: (bb, hh, kk, 0)),
            pl.BlockSpec((1, 1, bk), lambda bb, hh, kk: (bb, 0, kk)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bb, hh, kk: (bb, hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(b, kvh, g, d), k, v, valid.astype(jnp.int32)[:, None, :])
    return out.reshape(b, h, d)
