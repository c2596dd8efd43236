"""Public wrappers for paged decode attention (+ the MLA absorbed variant).

Ladder contract (docs/robustness.md): every fallback taken here is recorded
through :func:`repro.runtime.guard.note_kernel_fallback` — counted on
``kernel_log()``, one ``DegradationWarning`` per site per process.  Both
rungs compute the identical function (tests assert allclose).
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import INTERPRET_GRID_LIMIT, interpret_mode
from ...runtime.guard import note_kernel_fallback
from .kernel import paged_decode_attention_pallas
from .ref import paged_decode_attention_ref


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           starts=None, scale=None, layer=0, dv=None):
    """Engine-layout wrapper: q [B,H,Dk]; pools [L,P,KVH,Dk|Dv,ps] (``v_pool``
    None: the values are the first ``dv`` rows of ``k_pool``'s pages);
    block_tables [B,MAXP]; lengths/starts [B]; ``layer`` the stack's layer
    to read → [B,H,Dv]."""
    b, h, dk = q.shape
    _, _, kvh, _, ps = k_pool.shape
    dv = v_pool.shape[3] if v_pool is not None else dv
    maxp = block_tables.shape[1]
    scale = float(dk ** -0.5) if scale is None else float(scale)
    if starts is None:
        starts = jnp.zeros_like(lengths)
    args = (q, k_pool, v_pool, block_tables, lengths, starts, scale, layer, dv)
    if ps % 128 or dk % 8 or dv % 8 or h % kvh:
        # off-lattice: the page is the kernel's KV tile, so the page size
        # must be a lane multiple (and head dims sublane multiples) to tile
        # the MXU.  Static shapes → fires once per route decision.
        note_kernel_fallback(
            "paged_decode", "pallas->ref",
            f"off-lattice paged shapes ps={ps}, Dk={dk}, Dv={dv}, H={h}, "
            f"KVH={kvh} (need ps%128==0, Dk%8==0, Dv%8==0, H%KVH==0); "
            "gather-einsum reference")
        return paged_decode_attention_ref(*args)
    if interpret_mode() and b * kvh * maxp > INTERPRET_GRID_LIMIT:
        # interpret mode unrolls the grid at trace time; beyond the shared
        # limit the gather-einsum reference compiles and runs faster (same
        # silent route decision as grouped_gemm's interpret guard).
        return paged_decode_attention_ref(*args)
    try:
        return paged_decode_attention_pallas(
            q, k_pool, v_pool, block_tables.reshape(-1), starts, lengths,
            jnp.asarray(layer, jnp.int32), scale=scale, dv=dv,
            interpret=interpret_mode())
    except Exception as exc:  # pragma: no cover - depends on backend
        note_kernel_fallback("paged_decode", "pallas->ref",
                             f"Pallas launch failed: {exc!r}")
        return paged_decode_attention_ref(*args)


def paged_mla_decode_attention(q_nope, q_pe, lat_pool, wk_b, block_tables,
                               lengths, scale, layer=0):
    """MLA matrix-absorption variant over the compressed latent pool — the
    flashinfer-style contract (``deepseek_ma.py``): per-head ``q_nope`` is
    absorbed through ``W_kb`` into latent space, then a single kvh=1 paged
    attention runs with K the whole ``[ckv ‖ kpe]`` row and V its ``ckv``
    lanes, both read from the one pool.

        q_nope: [B, H, D_nope]   q_pe: [B, H, D_pe]   wk_b: [rank, H, D_nope]
        lat_pool: [L, P, 1, rank + D_pe, ps]

    Returns the latent output [B, H, rank] — the caller applies ``W_vb``.
    """
    rank = wk_b.shape[0]
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, wk_b,
                       preferred_element_type=jnp.float32).astype(q_nope.dtype)
    q_cat = jnp.concatenate([q_lat, q_pe], axis=-1)     # [B, H, rank+rope]
    return paged_decode_attention(q_cat, lat_pool, None, block_tables,
                                  lengths, scale=scale, layer=layer, dv=rank)
