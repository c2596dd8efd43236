"""Public jit'd wrapper: Pallas on TPU, interpret on CPU, ref fallback for
non-tileable shapes — and for Pallas lowering failures (real or injected
via the ``kernel_compile`` fault site), since the einsum ref computes the
identical function."""
from __future__ import annotations

import warnings

import jax

from ...runtime.faults import maybe_fire
from ...runtime.guard import DegradationWarning, kernel_log
from .. import interpret_mode
from .kernel import branch_gemm_pallas
from .ref import branch_gemm_ref


def select_tiles(m: int, k: int, f: int, bm: int = 128, bf: int = 128,
                 bk: int = 512) -> tuple[int, int, int] | None:
    """The ONE tile-selection rule for the fused branch GEMM: ``None`` when
    ``(m, k, f)`` is not tileable (the wrapper then runs the einsum ref),
    otherwise the exact ``(bm, bf, bk)`` the kernel will launch with.
    Shared with the capturer's route estimate so the Pallas-vs-vmap
    decision counts the same grid the kernel actually runs."""
    if m % 8 or k % 128 or f % 128:
        return None
    bm, bf, bk = min(bm, m), min(bf, f), min(bk, k)
    while m % bm:
        bm //= 2
    while f % bf:
        bf //= 2
    while k % bk:
        bk //= 2
    return bm, bf, bk


def branch_gemm(x: jax.Array, w: jax.Array, bm: int = 128, bf: int = 128,
                bk: int = 512) -> jax.Array:
    """Fused N-branch GEMM: [N,M,K] @ [N,K,F] → [N,M,F]."""
    n, m, k = x.shape
    f = w.shape[-1]
    tiles = select_tiles(m, k, f, bm, bf, bk)
    if tiles is None:
        return branch_gemm_ref(x, w)
    bm, bf, bk = tiles
    try:
        maybe_fire("kernel_compile")
        return branch_gemm_pallas(x, w, bm=bm, bf=bf, bk=bk,
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
