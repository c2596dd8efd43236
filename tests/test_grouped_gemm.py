"""Grouped ragged-M GEMM: kernel parity/properties + capture routing.

Kernel level: the Pallas path (interpret=True on CPU; same code targets
TPU) and the ops wrapper (padding, tile selection, ref fallback) against
the pure-jnp oracle over random ragged group sizes — zero-row groups
included.  Capture level: a wave of same-(K, F) matmul branches with
unequal M must lower to ONE ``grouped_gemm`` step whose outputs match
naive sequential execution.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OpGraph,
    OpKind,
    compile_plan,
    run_sequential_uncompiled,
    schedule,
)
from repro.core.profiler import gemm_cost
from repro.kernels.grouped_gemm.kernel import grouped_gemm_pallas
from repro.kernels.grouped_gemm.ops import grouped_gemm
from repro.kernels.grouped_gemm.ref import grouped_gemm_ref

rng = np.random.default_rng(0)


def _rand(shape, dtype, scale=0.1):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def _assert_close(a, b, rtol, atol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=rtol, atol=atol)


# ------------------------------------------------------------ kernel parity

@pytest.mark.parametrize("sizes", [(8, 16, 24), (8, 0, 16), (0, 8, 0, 32),
                                   (40,), (1, 2, 3, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm_matches_ref(sizes, dtype):
    k, f = 128, 128
    x = _rand((sum(sizes), k), dtype)
    w = _rand((len(sizes), k, f), dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    _assert_close(grouped_gemm(x, w, sizes), grouped_gemm_ref(x, w, sizes),
                  tol, tol)


def test_grouped_gemm_random_property():
    """Random ragged splits (zero-row groups included) against the oracle —
    and against ``jax.lax.ragged_dot`` where this jax version has it."""
    prng = np.random.default_rng(7)
    for _ in range(5):
        n = int(prng.integers(1, 6))
        sizes = tuple(int(prng.integers(0, 20)) for _ in range(n))
        k, f = 128, 256
        x = jnp.asarray(prng.standard_normal((sum(sizes), k)) * 0.1,
                        jnp.float32)
        w = jnp.asarray(prng.standard_normal((n, k, f)) * 0.1, jnp.float32)
        got = grouped_gemm(x, w, sizes)
        _assert_close(got, grouped_gemm_ref(x, w, sizes), 1e-5, 1e-5)
        if hasattr(jax.lax, "ragged_dot") and sum(sizes):
            rd = jax.lax.ragged_dot(x, w, jnp.asarray(sizes, jnp.int32))
            _assert_close(got, rd, 1e-5, 1e-5)


def test_grouped_gemm_pallas_direct():
    """The kernel itself (pre-padded layout, explicit tile→group table)."""
    bm, k, f = 8, 128, 128
    sizes = (16, 8, 24)                       # already bm multiples
    tile_group = (0, 0, 1, 2, 2, 2)
    x = _rand((sum(sizes), k), jnp.float32)
    w = _rand((len(sizes), k, f), jnp.float32)
    got = grouped_gemm_pallas(x, w, tile_group, bm=bm, bf=128, bk=128,
                              interpret=True)
    _assert_close(got, grouped_gemm_ref(x, w, sizes), 1e-5, 1e-5)


def test_grouped_gemm_non_tileable_falls_back_to_ref():
    """K/F off the 128 lattice → einsum reference, numerics unchanged."""
    sizes = (3, 7, 5)
    x = _rand((sum(sizes), 48), jnp.float32)
    w = _rand((len(sizes), 48, 80), jnp.float32)
    got = grouped_gemm(x, w, sizes)
    _assert_close(got, grouped_gemm_ref(x, w, sizes), 1e-5, 1e-5)


@pytest.mark.parametrize("k,f,tiles", [(640, 384, (8, 128, 128)),
                                       (1024, 256, (8, 128, 512))])
def test_grouped_gemm_keeps_its_halving_tiles(monkeypatch, k, f, tiles):
    """The ragged kernel keeps its own tiles — F and K halve from 128 and
    512 until they divide — apart from branch_gemm's VMEM-sized rule."""
    from repro.kernels.grouped_gemm import ops

    launched = []

    def spy(xp, w, tile_group, bm, bf, bk, interpret):
        launched.append((bm, bf, bk))
        return grouped_gemm_pallas(xp, w, tile_group, bm=bm, bf=bf, bk=bk,
                                   interpret=interpret)

    monkeypatch.setattr(ops, "grouped_gemm_pallas", spy)
    sizes = (3, 5)
    x = _rand((sum(sizes), k), jnp.float32)
    w = _rand((len(sizes), k, f), jnp.float32)
    _assert_close(grouped_gemm(x, w, sizes), grouped_gemm_ref(x, w, sizes),
                  1e-4, 1e-4)
    assert launched == [tiles]


def test_grouped_gemm_all_empty():
    x = jnp.zeros((0, 128), jnp.float32)
    w = _rand((3, 128, 128), jnp.float32)
    assert grouped_gemm(x, w, (0, 0, 0)).shape == (0, 128)


def test_grouped_gemm_validates_inputs():
    x = jnp.zeros((10, 128), jnp.float32)
    w = jnp.zeros((2, 128, 128), jnp.float32)
    with pytest.raises(ValueError, match="group sizes"):
        grouped_gemm(x, w, (10,))
    with pytest.raises(ValueError, match="sum_M"):
        grouped_gemm(x, w, (4, 4))
    with pytest.raises(ValueError, match="negative"):
        grouped_gemm(x, w, (12, -2))


# ---------------------------------------------------------- capture routing

def _mm(x, w):
    return x @ w


def _mm_b(x, w, b):
    return x @ w + b


def build_ragged_graph(sizes, k=128, f=128, dtype=jnp.float32,
                       bias=False, seed=3):
    """N parallel matmul branches sharing (K, F) with unequal M — the MoE
    expert fan-out shape, hand-built."""
    prng = np.random.default_rng(seed)
    g = OpGraph("ragged")
    for i, m in enumerate(sizes):
        x = g.add(f"x{i}", OpKind.INPUT, out_shape=(m, k), out_dtype=dtype)
        w = jnp.asarray(prng.standard_normal((k, f)) * 0.05, dtype)
        consts = (w,)
        if bias:
            consts += (jnp.asarray(prng.standard_normal((f,)), dtype),)
        g.add(f"gemm{i}", OpKind.GEMM, [x],
              fn=_mm_b if bias else _mm, cost=gemm_cost(m, k, f, 4),
              fuse_sig=("gemm", k, f, bias), consts=consts,
              payload="matmul", out_shape=(m, f), out_dtype=dtype)
    g.validate()
    return g


def _inputs_for(g, seed=9):
    prng = np.random.default_rng(seed)
    return {n.name: jnp.asarray(
                prng.standard_normal(n.out_shape) * 0.1, n.out_dtype)
            for n in g if n.fn is None}


@pytest.mark.parametrize("bias", [False, True])
def test_capture_routes_ragged_group_to_grouped_gemm(bias):
    sizes = (8, 24, 16)
    g = build_ragged_graph(sizes, bias=bias)
    exe = compile_plan(schedule(g, "opara", "opara"))
    stats = exe.program_stats()
    assert stats["n_grouped_gemm"] == 1, stats
    step = next(s for s in exe.steps if s.route == "grouped_gemm")
    # the offset table follows the packed branch order within the wave
    assert step.group_sizes == tuple(
        g.nodes[g.nodes[op].inputs[0]].out_shape[0] for op in step.op_ids)
    assert sorted(step.group_sizes) == sorted(sizes)
    inputs = _inputs_for(g)
    got = exe(inputs)
    ref = run_sequential_uncompiled(g, inputs, output_ids=exe.output_ids)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_capture_ragged_vmap_kernel_falls_back_to_singles():
    """gemm_kernel="vmap" cannot stack ragged branches — per-branch calls,
    same numerics."""
    g = build_ragged_graph((8, 24, 16))
    plan = schedule(g, "opara", "opara")
    exe = compile_plan(plan, gemm_kernel="vmap")
    stats = exe.program_stats()
    assert stats["n_grouped_gemm"] == 0 and stats["n_vmap"] == 0
    inputs = _inputs_for(g)
    got = exe(inputs)
    ref = run_sequential_uncompiled(g, inputs, output_ids=exe.output_ids)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_capture_ragged_non_tileable_still_one_step():
    """Ragged group on off-lattice (K, F): still ONE grouped step — the ops
    wrapper's ref fallback keeps it fused."""
    g = build_ragged_graph((3, 5, 9), k=48, f=80)
    exe = compile_plan(schedule(g, "opara", "opara"))
    assert exe.program_stats()["n_grouped_gemm"] == 1
    inputs = _inputs_for(g)
    got = exe(inputs)
    ref = run_sequential_uncompiled(g, inputs, output_ids=exe.output_ids)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_capture_equal_sizes_keep_stacked_path():
    """Uniform M with declared shapes must NOT take the grouped route — the
    stacked (branch_gemm/vmap) path is strictly cheaper."""
    g = build_ragged_graph((16, 16, 16))
    exe = compile_plan(schedule(g, "opara", "opara"))
    stats = exe.program_stats()
    assert stats["n_grouped_gemm"] == 0
    assert stats["n_branch_gemm"] + stats["n_vmap"] == 1
