"""Grouped expert GEMM with fused SwiGLU — all expert lanes as ONE kernel.

This is Opara's widest wave (up to 384 parallel expert-FFN operators in
Kimi-K2) executed as a single grouped kernel: the grid iterates
(expert, token-tile, ffn-tile) so every MXU step is a dense 128-aligned
matmul, and per-expert weight DMA pipelines under the previous tile's
compute.  SwiGLU and the down-projection accumulate in VMEM — the
memory-bound epilogue rides under the compute-bound GEMM (paper Fig. 3 at
kernel scale).

    buf:  [E, C, d]     gate/up: [E, d, f]    down: [E, f, d]
    rows: [E] int32
    out:  [E, C, d] = (silu(buf@gate) * (buf@up)) @ down

Grid: (E, C/bc, F/bf); the fp32 accumulator [bc, d] carries across F tiles.
Only the first ``rows[e]`` rows of expert ``e`` are live.  A row tile past
them is dead: it computes nothing, and its index maps repeat the blocks of
the grid step before it, so the pipeline fetches nothing for it either.
Those are the blocks of the last live tile, and the last weight blocks, of
the last expert at or before ``e`` with a live row (expert 0 if none).
The output rows of dead tiles are left undefined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(rows_ref, src_ref, src_tile_ref, x_ref, g_ref, u_ref, d_ref,
            o_ref, acc_ref, *, bc: int):
    del src_ref, src_tile_ref  # consumed by the index maps
    e, c, f_i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = c * bc < rows_ref[e]

    @pl.when(live & (f_i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        x = x_ref[0]                                   # [bc, d]
        g = g_ref[0]                                   # [d, bf]
        u = u_ref[0]
        dn = d_ref[0]                                  # [bf, d]
        h = jax.nn.silu(jax.lax.dot_general(
            x, g, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))
        h = h * jax.lax.dot_general(
            x, u, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            h.astype(dn.dtype), dn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (f_i == pl.num_programs(2) - 1))
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "interpret"))
def moe_mlp_pallas(buf, gate, up, down, rows, bc: int = 128, bf: int = 256,
                   interpret: bool = True):
    e, c, d = buf.shape
    f = gate.shape[-1]
    assert c % bc == 0 and f % bf == 0, (c, bc, f, bf)
    nf = f // bf
    rows = jnp.minimum(rows.astype(jnp.int32), c)
    # the last expert at or before each one with a live row (0 if none),
    # and that expert's last live row tile
    src = jax.lax.cummax(jnp.where(rows > 0, jnp.arange(e, dtype=jnp.int32), 0))
    src_tile = jnp.maximum((rows[src] + bc - 1) // bc - 1, 0)

    def x_map(ee, cc, ff, r, s, st):
        live = cc * bc < r[ee]
        return jnp.where(live, ee, s[ee]), jnp.where(live, cc, st[ee]), 0

    def w_map(ee, cc, ff, r, s, st):          # gate and up: [E, d, f]
        live = cc * bc < r[ee]
        return jnp.where(live, ee, s[ee]), 0, jnp.where(live, ff, nf - 1)

    def down_map(ee, cc, ff, r, s, st):       # [E, f, d]
        live = cc * bc < r[ee]
        return jnp.where(live, ee, s[ee]), jnp.where(live, ff, nf - 1), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(e, c // bc, nf),
        in_specs=[
            pl.BlockSpec((1, bc, d), x_map),
            pl.BlockSpec((1, d, bf), w_map),
            pl.BlockSpec((1, d, bf), w_map),
            pl.BlockSpec((1, bf, d), down_map),
        ],
        out_specs=pl.BlockSpec((1, bc, d), x_map),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bc=bc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, d), buf.dtype),
        interpret=interpret,
    )(rows, src, src_tile, buf, gate, up, down)
