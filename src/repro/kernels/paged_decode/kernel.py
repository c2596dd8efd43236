"""Paged split-KV flash decoding: one query token against block-table pages.

Same online-softmax structure as ``decode_attention`` (grid walks KV blocks
innermost, fp32 VMEM running max/sum/accumulator), but K/V live in physical
pages addressed through a **scalar-prefetched block table** — the
``PrefetchScalarGridSpec`` pattern of ``grouped_gemm``: the index map of the
K/V operands reads ``bt[b * MAXP + p]`` so the DMA for logical page ``p``
of sequence ``b`` streams the right physical page while page ``p-1``'s
matmul runs.  Nothing is ever gathered into a contiguous slab.

    q:  [B, H, Dk]     k: [L, P, KVH, Dk, ps]     v: [L, P, KVH, Dv, ps]
    bt: [B*MAXP] int32      starts, lengths: [B] int32      layer: [1] int32
    →   out: [B, H, Dv]

The pools are the whole layer stack: the layer index is one more scalar
prefetch, read by the K/V index maps, so no layer's pool is ever sliced out
of the stack.  A page holds its positions on the lanes (``[D, ps]``): a
page size of 128 fills them whatever the head dim, so the pool is stored
unpadded in the TPU's tiled layout and the kernel reads it as it lies
(``s = q @ K``, ``o = p @ Vᵀ``).  ``v=None`` reads the values as the first
``Dv`` rows of the K block (the MLA absorbed variant: one latent pool
``[ckv ‖ kpe]`` with ``Dk = rank + rope`` and ``Dv = rank``), so each latent
page is read once.

The query is viewed as ``[B, KVH, G, Dk]`` (``G = H / KVH``) and one block
carries all G query heads of a KV head, so each page is read once per KV
head and the block's last two dims equal the array's (the TPU lowering's
rule for head counts that are not multiples of 8).
Grid: (B, KVH, MAXP), pages innermost (sequential accumulation).  Masking is
positional (``starts <= pos < lengths``), so trailing table entries may
point anywhere (the engine points them at the reserved null page 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, starts_ref, lengths_ref, layer_ref, q_ref, k_ref, *refs,
            scale: float, page_size: int, dv: int):
    del bt_ref, layer_ref  # consumed by the K/V index maps
    if len(refs) == 5:
        v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:                                              # V = K[:dv]
        (o_ref, m_ref, l_ref, acc_ref), v_ref = refs, None
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                    # [G, Dk]
    k = k_ref[0, 0, 0]                                 # [Dk, ps]
    v = k[:dv] if v_ref is None else v_ref[0, 0, 0]    # [Dv, ps]
    s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    posn = pi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                  # absolute positions
    ok = (posn >= starts_ref[b]) & (posn < lengths_ref[b])
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(pi == pl.num_programs(2) - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "dv", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,            # [B, H, Dk]
    k: jax.Array,            # [L, P, KVH, Dk, ps]
    v: jax.Array | None,     # [L, P, KVH, Dv, ps], or None: V = k[..., :dv, :]
    block_tables: jax.Array,  # [B * MAXP] int32 (flattened)
    starts: jax.Array,       # [B] int32
    lengths: jax.Array,      # [B] int32
    layer: jax.Array,        # [] int32: the layer of the stack to read
    scale: float,
    dv: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    b, h, dk = q.shape
    _, _, kvh, _, ps = k.shape
    dv = v.shape[3] if v is not None else dv
    g = h // kvh
    maxp = block_tables.shape[0] // b

    def page(width):
        return pl.BlockSpec((1, 1, 1, width, ps),
                            lambda bb, hh, pp, bt, st, ln, ly, mp=maxp:
                            (ly[0], bt[bb * mp + pp], hh, 0, 0))

    in_specs = [pl.BlockSpec((1, 1, g, dk),
                             lambda bb, hh, pp, bt, st, ln, ly: (bb, hh, 0, 0)),
                page(dk)]
    operands = [q.reshape(b, kvh, g, dk), k]
    if v is not None:
        in_specs.append(page(dv))
        operands.append(v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kvh, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda bb, hh, pp, bt, st, ln, ly:
                               (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, page_size=ps, dv=dv)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), starts.astype(jnp.int32),
      lengths.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands)
    return out.reshape(b, h, dv)
