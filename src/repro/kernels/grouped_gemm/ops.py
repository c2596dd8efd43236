"""Public wrappers for the grouped ragged-M GEMM.

``grouped_gemm_parts`` is the primary entry (the capturer's grouped step
already holds per-branch arrays): each part is zero-padded up to the row
tile and concatenated ONCE into the kernel's padded layout — no
intermediate ``[sum_M, K]`` materialization.  ``grouped_gemm`` is the flat
convenience form over rows concatenated per group.  Non-tileable (K, F) —
or interpret-mode grids too large to unroll — fall back to the einsum
reference, which is still ONE fused op inside the captured program.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from ...runtime.faults import maybe_fire
from ...runtime.guard import DegradationWarning, kernel_log
from .. import INTERPRET_GRID_LIMIT, interpret_mode
from .kernel import grouped_gemm_pallas
from .ref import grouped_gemm_ref


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _halve_to_divisor(dim: int, tile: int) -> int:
    """The largest ``tile / 2**i`` (capped at ``dim``) that divides ``dim``."""
    tile = min(tile, dim)
    while dim % tile:
        tile //= 2
    return tile


def grouped_gemm_parts(xs: list[jax.Array], w: jax.Array,
                       bm: int = 128, bf: int = 128,
                       bk: int = 512) -> list[jax.Array]:
    """Ragged fused GEMM over per-branch parts: ``xs[i]: [M_i, K]`` against
    ``w: [N, K, F]`` → one ``[M_i, F]`` output per branch.  Row counts are
    static by construction (trace-time shapes); zero-row parts are
    allowed."""
    n, k, f = w.shape
    if len(xs) != n:
        raise ValueError(f"{len(xs)} input parts for {n} groups")
    for x in xs:
        if x.ndim != 2 or x.shape[1] != k:
            raise ValueError(f"part shape {x.shape} != (M_i, K={k})")
    group_sizes = tuple(int(x.shape[0]) for x in xs)
    total = sum(group_sizes)
    if k % 128 or f % 128 or total == 0:
        return [grouped_gemm_ref(x, w[i:i + 1], (m,))
                for i, (x, m) in enumerate(zip(xs, group_sizes))]
    # the F/K tiles halve from the defaults until they divide; the row tile
    # is ragged-specific (per-group padding picks it below)
    bf, bk = _halve_to_divisor(f, bf), _halve_to_divisor(k, bk)
    m_max = max(group_sizes)
    bm = min(bm, _round_up(m_max, 8))
    tile_group: list[int] = []
    for i, m in enumerate(group_sizes):
        tile_group += [i] * (-(-m // bm))
    grid_points = len(tile_group) * (f // bf) * (k // bk)
    if interpret_mode() and grid_points > INTERPRET_GRID_LIMIT:
        return [grouped_gemm_ref(x, w[i:i + 1], (m,))
                for i, (x, m) in enumerate(zip(xs, group_sizes))]

    # zero-pad each part to a bm multiple and concatenate ONCE — row tiles
    # then never straddle groups (the kernel's tile→group contract)
    segs = []
    for x, m in zip(xs, group_sizes):
        pad = _round_up(m, bm) - m if m else 0
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, k), x.dtype)], axis=0)
        if x.shape[0]:
            segs.append(x)
    xp = jnp.concatenate(segs, axis=0)
    try:
        maybe_fire("grouped_gemm_route")
        out = grouped_gemm_pallas(xp, w, tuple(tile_group), bm=bm, bf=bf,
                                  bk=bk, interpret=interpret_mode())
    except Exception as exc:
        # Pallas launch failure (real, or injected via the
        # ``grouped_gemm_route`` site): the per-part einsum reference
        # computes the identical function
        kernel_log().note("grouped_gemm", "pallas->ref",
                          f"Pallas launch failed: {exc!r}")
        warnings.warn(f"grouped_gemm: Pallas launch failed ({exc!r}); "
                      "running the einsum reference",
                      DegradationWarning, stacklevel=2)
        return [grouped_gemm_ref(x, w[i:i + 1], (m,))
                for i, (x, m) in enumerate(zip(xs, group_sizes))]
    # strip the per-group padding rows
    outs, off = [], 0
    for m in group_sizes:
        outs.append(out[off:off + m])
        off += _round_up(m, bm)
    return outs


def grouped_gemm(x: jax.Array, w: jax.Array,
                 group_sizes: tuple[int, ...],
                 bm: int = 128, bf: int = 128, bk: int = 512) -> jax.Array:
    """Flat form: rows ``[sum_M, K]`` (group ``i`` owns the
    ``group_sizes[i]`` rows after groups ``< i``) → ``[sum_M, F]``.
    ``group_sizes`` must be static ints; zero-row groups are allowed."""
    group_sizes = tuple(int(m) for m in group_sizes)
    n, k, f = w.shape
    if len(group_sizes) != n:
        raise ValueError(f"{len(group_sizes)} group sizes for {n} groups")
    if any(m < 0 for m in group_sizes):
        raise ValueError(f"negative group size in {group_sizes}")
    total = sum(group_sizes)
    if x.shape != (total, k):
        raise ValueError(f"x {x.shape} != (sum_M={total}, K={k})")
    if total == 0:
        return jnp.zeros((0, f), x.dtype)
    parts, off = [], 0
    for m in group_sizes:
        parts.append(x[off:off + m])
        off += m
    return jnp.concatenate(grouped_gemm_parts(parts, w, bm=bm, bf=bf, bk=bk),
                           axis=0)
