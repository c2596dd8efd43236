"""Pure-jnp oracle for paged single-token decode attention.

Layout contract (the engine's page pool, one per layer stack, in the
kernel's layout — a page holds its positions on the minor axis, and each
layer's new row is written in place at ``(layer, page, :, :, offset)``):

    q:            [B, H, Dk]              one query token per sequence
    k_pool:       [L, P, KVH, Dk, ps]     physical KV pages (page 0 = null)
    v_pool:       [L, P, KVH, Dv, ps]     or None: V = k_pool[..., :dv, :] (MLA)
    block_tables: [B, MAXP] int32   logical page i of seq b -> physical page
    lengths:      [B] int32         attendable positions: [starts, lengths)
    starts:       [B] int32 | None  window lower bound (None -> 0)
    layer:        int32 scalar      the layer of the stack to read

Out-of-range table entries simply point at the null page; masking is purely
positional, so the gather never needs bounds logic.
"""
import jax
import jax.numpy as jnp

NEG_INF = -1e30


def page_positions(pages):
    """Pages [..., n, KVH, D, ps] as their positions [..., n*ps, KVH, D]."""
    *lead, n, kvh, d, ps = pages.shape
    return jnp.moveaxis(pages, -1, -3).reshape(*lead, n * ps, kvh, d)


def positions_to_pages(x, page_size: int):
    """The inverse of :func:`page_positions`: positions [..., n*ps, KVH, D]
    as pages [..., n, KVH, D, ps]."""
    *lead, t, kvh, d = x.shape
    x = x.reshape(*lead, t // page_size, page_size, kvh, d)
    return jnp.moveaxis(x, -3, -1)


def gather_pages(pool, block_tables, layer=0):
    """``layer``'s pages of each row as a contiguous [B, MAXP*ps, KVH, D]."""
    return page_positions(pool[layer, block_tables])


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                               starts=None, scale=None, layer=0, dv=None):
    """Gather-then-mask einsum reference → [B, H, Dv]."""
    b, h, dk = q.shape
    kvh = k_pool.shape[2]
    groups = h // kvh
    scale = dk ** -0.5 if scale is None else scale
    k = gather_pages(k_pool, block_tables, layer)      # [B, T', KVH, Dk]
    v = (k[..., :dv] if v_pool is None
         else gather_pages(v_pool, block_tables, layer))
    dv = v.shape[-1]
    posn = jnp.arange(k.shape[1])[None, :]              # [1, T']
    valid = posn < lengths[:, None]
    if starts is not None:
        valid &= posn >= starts[:, None]
    # numerics mirror _sdpa (models/attention.py) term for term — bf16
    # operands, fp32 accumulation, probabilities cast back to the value
    # dtype — so paged and dense decode emit identical token streams.
    qg = q.reshape(b, kvh, groups, dk)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32)
    logits *= scale
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, dv).astype(q.dtype)
