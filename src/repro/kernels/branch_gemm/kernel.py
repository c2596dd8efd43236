"""Horizontally-fused multi-branch GEMM — the Opara wave as ONE kernel.

The paper's streams run N independent small kernels concurrently so the SM
pool stays busy.  On TPU the MXU is one big systolic array, so the same
insight becomes: stack the N independent GEMMs (same M,K,F signature —
Opara's fusion groups guarantee this) into a single ``pallas_call`` whose
grid iterates branches × tiles.  One kernel launch, zero per-branch dispatch,
MXU tiles stay 128-aligned, and the per-branch operand DMA double-buffers
under the previous branch's matmul (compute/memory overlap — paper Fig. 3,
realized by Pallas' automatic pipelining across sequential grid steps).

    x: [N, M, K]   w: [N, K, F]   out: [N, M, F]

Tiles come from ``ops.select_tiles``: the whole of K in one block, with
row and column tiles as large as VMEM holds.  Grid: (N, M/bm, F/bf) — with
one K block the x block's index does not change across the F tiles of a
row tile, so the pipeline fetches each row tile of x once, and each output
block is one MXU pass with fp32 accumulation, written straight out in the
output dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref):
    o_ref[0] = jax.lax.dot_general(
        x_ref[0], w_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def branch_gemm_pallas(
    x: jax.Array,
    w: jax.Array,
    bm: int,
    bf: int,
    interpret: bool = True,
) -> jax.Array:
    n, m, k = x.shape
    n2, k2, f = w.shape
    assert (n, k) == (n2, k2), f"shape mismatch {x.shape} @ {w.shape}"
    assert m % bm == 0 and f % bf == 0, (
        f"dims ({m},{f}) must tile by ({bm},{bf})")
    return pl.pallas_call(
        _kernel,
        grid=(n, m // bm, f // bf),
        in_specs=[
            pl.BlockSpec((1, bm, k), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, k, bf), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bf), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m, f), x.dtype),
        interpret=interpret,
    )(x, w)
