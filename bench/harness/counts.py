"""Operations and bytes from shapes that do not depend on the model's
structure; an architecture's own counts (per forward, decode step or
kernel call) live in ``architectures/<architecture>.py``.  Weights and KV
are bfloat16 (2 bytes); logits are float32.

FLOPs count what the algorithm needs: a multiply-add is 2, causal
attention counts only the pairs at or below the diagonal, and nothing
recomputed or padded counts.
"""
from __future__ import annotations

BF16 = 2


def branch_gemm(n: int, m: int, k: int, f: int, bias: bool) -> tuple[int, int]:
    """(FLOPs, bytes) of one fused step: ``n`` branches of [m,k] @ [k,f]."""
    flops = 2 * n * m * k * f
    nbytes = BF16 * n * (m * k + k * f + m * f + (f if bias else 0))
    return flops, nbytes
