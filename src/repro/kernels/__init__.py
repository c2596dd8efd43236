"""Pallas TPU kernels for the compute hot-spots.

Each kernel lives in its own subpackage with the mandated layout:

    <name>/kernel.py   pl.pallas_call + explicit BlockSpec VMEM tiling
    <name>/ops.py      jit'd public wrapper (+ CPU interpret fallback)
    <name>/ref.py      pure-jnp oracle used by tests

Kernels:
    branch_gemm       horizontally-fused multi-branch GEMM — the Opara wave
                      (N independent small GEMMs → one MXU-saturating kernel);
                      shape-aware tiles: the whole of K, and row and column
                      tiles as large as the v5e's scoped VMEM holds
    grouped_gemm      ragged-M grouped GEMM (unequal branch row counts, MoE
                      expert fan-out) — scalar-prefetched tile→group table
    flash_attention   causal/windowed GQA flash attention (prefill/train)
    decode_attention  split-KV flash-decoding for single-token decode
    rwkv6             chunked WKV6 recurrence (memory-bound scan)
    moe_gemm          capacity-buffer grouped expert GEMM
    rmsnorm           fused RMSNorm (bandwidth-bound epilogue)

All kernels validate on CPU via ``interpret=True`` and are written for
TPU VMEM tiling (128-aligned MXU tiles, fp32 accumulation).
"""


# In interpret mode (CPU) a Pallas grid is unrolled at trace time; beyond
# this many grid points a non-Pallas fallback (vmap / einsum ref) compiles
# and runs faster.  Shared by the capturer's route decision and the kernel
# wrappers' internal fallbacks so the two can never drift.
INTERPRET_GRID_LIMIT = 64


def interpret_mode() -> bool:
    import jax
    return jax.default_backend() != "tpu"
