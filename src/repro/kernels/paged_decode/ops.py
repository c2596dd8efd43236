"""Public wrappers for paged decode attention (+ the MLA absorbed variant).

Ladder contract (docs/robustness.md): every fallback taken here is recorded
through :func:`repro.runtime.guard.note_kernel_fallback` — counted on
``kernel_log()``, one ``DegradationWarning`` per site per process.  Both
rungs compute the identical function (tests assert allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import INTERPRET_GRID_LIMIT, interpret_mode
from ...runtime.guard import note_kernel_fallback
from .kernel import paged_decode_attention_pallas
from .ref import paged_decode_attention_ref


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           starts=None, scale=None):
    """Engine-layout wrapper: q [B,H,Dk]; pages [P,ps,KVH,Dk|Dv];
    block_tables [B,MAXP]; lengths/starts [B] → [B,H,Dv]."""
    b, h, dk = q.shape
    _, ps, kvh, _ = k_pages.shape
    dv = v_pages.shape[-1]
    maxp = block_tables.shape[1]
    scale = float(dk ** -0.5) if scale is None else float(scale)
    if starts is None:
        starts = jnp.zeros_like(lengths)
    if ps % 128 or dk % 8 or dv % 8 or h % kvh:
        # off-lattice: the page is the kernel's KV tile, so the page size
        # must be a lane multiple (and head dims sublane multiples) to tile
        # the MXU.  Static shapes → fires once per route decision.
        note_kernel_fallback(
            "paged_decode", "pallas->ref",
            f"off-lattice paged shapes ps={ps}, Dk={dk}, Dv={dv}, H={h}, "
            f"KVH={kvh} (need ps%128==0, Dk%8==0, Dv%8==0, H%KVH==0); "
            "gather-einsum reference")
        return paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                                          lengths, starts, scale)
    if interpret_mode() and b * kvh * maxp > INTERPRET_GRID_LIMIT:
        # interpret mode unrolls the grid at trace time; beyond the shared
        # limit the gather-einsum reference compiles and runs faster (same
        # silent route decision as grouped_gemm's interpret guard).
        return paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                                          lengths, starts, scale)
    try:
        with jax.named_scope("kv_layout"):
            kt = jnp.swapaxes(k_pages, 1, 2)           # [P, KVH, ps, Dk]
            vt = jnp.swapaxes(v_pages, 1, 2)
        return paged_decode_attention_pallas(
            q, kt, vt, block_tables.reshape(-1), starts, lengths,
            scale=scale, interpret=interpret_mode())
    except Exception as exc:  # pragma: no cover - depends on backend
        note_kernel_fallback("paged_decode", "pallas->ref",
                             f"Pallas launch failed: {exc!r}")
        return paged_decode_attention_ref(q, k_pages, v_pages, block_tables,
                                          lengths, starts, scale)


def paged_mla_decode_attention(q_nope, q_pe, ckv_pages, kpe_pages, wk_b,
                               block_tables, lengths, scale):
    """MLA matrix-absorption variant over compressed latent pages — the
    flashinfer-style contract (``deepseek_ma.py``): per-head ``q_nope`` is
    absorbed through ``W_kb`` into latent space, then a single kvh=1 paged
    attention runs against ``[ckv ‖ kpe]`` pages with ``V = ckv``.

        q_nope: [B, H, D_nope]   q_pe: [B, H, D_pe]   wk_b: [rank, H, D_nope]
        ckv_pages: [P, ps, rank]   kpe_pages: [P, ps, D_pe]

    Returns the latent output [B, H, rank] — the caller applies ``W_vb``.
    """
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, wk_b,
                       preferred_element_type=jnp.float32).astype(q_nope.dtype)
    q_cat = jnp.concatenate([q_lat, q_pe], axis=-1)     # [B, H, rank+rope]
    k_cat = jnp.concatenate([ckv_pages, kpe_pages], axis=-1)[:, :, None, :]
    v_lat = ckv_pages[:, :, None, :]                    # [P, ps, 1, rank]
    return paged_decode_attention(q_cat, k_cat, v_lat, block_tables, lengths,
                                  starts=None, scale=scale)
