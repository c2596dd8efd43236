"""Public wrappers for the grouped expert MLP: Pallas on TPU, interpret on
CPU, and the einsum reference, recorded through
:func:`repro.runtime.guard.note_kernel_fallback`, for shapes the kernel
cannot tile and for Pallas launch failures."""
from __future__ import annotations

import jax.numpy as jnp

from ...runtime.guard import note_kernel_fallback
from .. import interpret_mode
from ..branch_gemm.ops import VMEM_BUDGET
from .kernel import moe_mlp_pallas
from .ref import moe_mlp_ref


def vmem_bytes(bc: int, bf: int, d: int, itemsize: int) -> int:
    """VMEM the kernel holds at tiles ``(bc, bf)``: two buffers each of the
    x, gate, up, down and out blocks at ``itemsize`` bytes, the fp32
    accumulator and the fp32 down-projection product added into it, and
    the fp32 gate and up products.  (At d 7168 the compiler asked 16.48
    MiB for (64, 128), which the first three terms put at 15.8.)"""
    return (2 * (2 * bc * d + 3 * d * bf) * itemsize + 2 * 4 * bc * d
            + 2 * 4 * bc * bf)


def select_tiles(c: int, d: int, f: int,
                 itemsize: int = 2) -> tuple[int, int] | None:
    """The kernel's tiles ``(bc, bf)`` for ``c`` rows an expert, or None
    where the wrapper runs the reference instead: the rule of
    ``branch_gemm.select_tiles``.  The row tile ``bc`` (a multiple of 8) is
    made as large as :data:`VMEM_BUDGET` allows first, since each row tile
    streams all of its expert's weights once, then the column tile ``bf``
    (a multiple of 128 dividing F).  Rows are padded up to a multiple of
    ``bc``, so ``bc`` is the least multiple of 8 that covers them in as few
    tiles as the largest fitting one would.  None when d or F is off the
    128 lattice or no tiles fit."""
    if d % 128 or f % 128 or c < 1:
        return None
    c8 = -(-c // 8) * 8
    bfs = [b for b in range(f // 128 * 128, 0, -128) if f % b == 0]
    for bc in range(c8, 0, -8):
        for bf in bfs:
            if vmem_bytes(bc, bf, d, itemsize) <= VMEM_BUDGET:
                tiles = -(-c8 // bc)
                return -(-c8 // (8 * tiles)) * 8, bf
    return None


def moe_mlp(buf, gate, up, down, rows=None):
    """buf [E,C,d] through each expert's SwiGLU MLP → [E,C,d].  With
    ``rows`` [E] the kernel computes only each expert's first ``rows[e]``
    rows; the others are undefined."""
    e, c, d = buf.shape
    f = gate.shape[-1]
    tiles = select_tiles(c, d, f, max(buf.dtype.itemsize, gate.dtype.itemsize))
    if tiles is None:
        note_kernel_fallback(
            "moe_gemm", "pallas->ref",
            f"untileable expert shapes C={c}, d={d}, F={f} (need d%128==0, "
            f"F%128==0 and blocks within {VMEM_BUDGET} B of VMEM); einsum "
            "reference")
        return moe_mlp_ref(buf, gate, up, down)
    bc, bf = tiles
    cp = -(-c // bc) * bc
    if rows is None:
        rows = jnp.full((e,), c, jnp.int32)
    xp = jnp.pad(buf, ((0, 0), (0, cp - c), (0, 0))) if cp > c else buf
    try:
        out = moe_mlp_pallas(xp, gate, up, down, rows, bc=bc, bf=bf,
                             interpret=interpret_mode())
    except Exception as exc:  # pragma: no cover - depends on backend
        note_kernel_fallback("moe_gemm", "pallas->ref",
                             f"Pallas launch failed: {exc!r}")
        return moe_mlp_ref(buf, gate, up, down)
    return out[:, :c]


def moe_mlp_tpu_or_ref(buf, p_experts, rows=None):
    """Model adapter: p_experts = {gate, up, down} stacked [E, ...]."""
    return moe_mlp(buf, p_experts["gate"], p_experts["up"], p_experts["down"],
                   rows)
