"""Public jit'd wrapper: Pallas on TPU, interpret on CPU, ref fallback for
shapes the kernel cannot tile — off the (8, 128) lattice, or a K too deep
for whole-K blocks to fit VMEM — and for Pallas lowering failures (real or
injected via the ``kernel_compile`` fault site), since the einsum ref
computes the identical function."""
from __future__ import annotations

import math
import warnings

import jax

from ...runtime.faults import maybe_fire
from ...runtime.guard import DegradationWarning, kernel_log
from .. import interpret_mode
from .kernel import branch_gemm_pallas
from .ref import branch_gemm_ref

# The default scoped VMEM limit of a TPU v5e core.  The tile rule keeps the
# kernel's double-buffered x, w and out blocks, plus its fp32 result, in it.
VMEM_BUDGET = 16 * 1024 * 1024


def vmem_bytes(bm: int, bf: int, k: int, itemsize: int) -> int:
    """VMEM the kernel holds at tiles ``(bm, bf)`` over the whole of K: two
    buffers each of the x, w and out blocks at ``itemsize`` bytes, and the
    fp32 result."""
    return 2 * (bm * k + k * bf + bm * bf) * itemsize + 4 * bm * bf


def _divisors(n: int, step: int, cap: int) -> list[int]:
    """Multiples of ``step`` that divide ``n`` and are at most ``cap``,
    largest first."""
    return [d for d in range(min(n, cap) // step * step, 0, -step)
            if n % d == 0]


def select_tiles(m: int, k: int, f: int,
                 itemsize: int = 2) -> tuple[int, int] | None:
    """The ONE tile-selection rule for the fused branch GEMM: the exact
    ``(bm, bf)`` the kernel launches with, over the whole of K, or ``None``
    where the wrapper runs the einsum ref instead.

    The row tile ``bm`` (a multiple of 8 dividing M) is made as large as
    :data:`VMEM_BUDGET` allows first, since each row tile streams all of w
    once; then the column tile ``bf`` (a multiple of 128 dividing F).
    ``None`` when M, K or F is off the (8, 128) lattice, or when no blocks
    of the whole of K fit.  Shared with the capturer's route estimate and
    grid counter (:func:`grid_steps`), so they count the grid the kernel
    actually runs."""
    if m % 8 or k % 128 or f % 128:
        return None
    # the x block alone bounds the row tile
    for bm in _divisors(m, 8, VMEM_BUDGET // (2 * k * itemsize)):
        for bf in _divisors(f, 128, f):
            if vmem_bytes(bm, bf, k, itemsize) <= VMEM_BUDGET:
                return bm, bf
    return None


def grid_steps(n: int, m: int, k: int, f: int, itemsize: int = 2) -> int:
    """Grid steps the kernel launches for ``n`` branches of ``[m, k] @
    [k, f]`` (0 where the wrapper runs the einsum reference instead)."""
    tiles = select_tiles(m, k, f, itemsize)
    if tiles is None:
        return 0
    bm, bf = tiles
    return n * (m // bm) * (f // bf)


def _itemsize(x: jax.Array, w: jax.Array) -> int:
    return max(x.dtype.itemsize, w.dtype.itemsize)


def launch_grid(x: jax.Array, w: jax.Array) -> int:
    """:func:`grid_steps` of ``branch_gemm(x.reshape(n, -1, k), w)`` for a
    stacked input ``x: [N, *rows, K]``."""
    n, k, f = w.shape
    return grid_steps(n, math.prod(x.shape[1:-1]), k, f, _itemsize(x, w))


def branch_gemm(x: jax.Array, w: jax.Array) -> jax.Array:
    """Fused N-branch GEMM: [N,M,K] @ [N,K,F] → [N,M,F]."""
    n, m, k = x.shape
    f = w.shape[-1]
    tiles = select_tiles(m, k, f, _itemsize(x, w))
    if tiles is None:
        return branch_gemm_ref(x, w)
    bm, bf = tiles
    try:
        maybe_fire("kernel_compile")
        return branch_gemm_pallas(x, w, bm=bm, bf=bf,
                                  interpret=interpret_mode())
    except Exception as exc:
        # counted on the kernel ladder log (a chip run asserts it is empty)
        # and warned on every event, not once per process
        kernel_log().note("branch_gemm", "pallas->ref",
                          f"Pallas launch failed: {exc!r}")
        warnings.warn(f"branch_gemm: Pallas launch failed ({exc!r}); "
                      "running the einsum reference",
                      DegradationWarning, stacklevel=2)
        return branch_gemm_ref(x, w)
