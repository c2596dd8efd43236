"""Graph Capturer: wave fusion + single-program execution correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    build_waves,
    capture,
    compile_plan,
    fusion_stats,
    run_sequential_uncompiled,
    schedule,
)

from conftest import build_inception_like


def test_capture_matches_sequential():
    g = build_inception_like(n_blocks=3, width=4, with_payloads=True)
    plan = schedule(g, "opara", "opara")
    exe = compile_plan(plan)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, 64)), jnp.float32)
    got = exe({"x": x})
    ref = run_sequential_uncompiled(g, {"x": x})
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               rtol=1e-5, atol=1e-5)


def test_capture_matches_for_all_policies():
    g = build_inception_like(n_blocks=2, width=3, with_payloads=True, seed=3)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((8, 64)), jnp.float32)
    ref = run_sequential_uncompiled(g, {"x": x})
    for alloc in ("opara", "nimble", "sequential"):
        for order in ("opara", "topo", "depth_first"):
            plan = schedule(g, alloc, order)
            got = compile_plan(plan)({"x": x})
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"{alloc}/{order}")


def test_horizontal_fusion_reduces_kernels():
    g = build_inception_like(n_blocks=3, width=4)
    plan = schedule(g, "opara", "opara")
    stats = fusion_stats(plan.waves)
    # 4 same-signature branch GEMMs per block must fuse into one kernel
    assert stats["fusion_ratio"] > 1.5
    assert stats["n_kernels_after_fusion"] < stats["n_ops"]


def test_sequential_policy_single_wave_width():
    g = build_inception_like(n_blocks=2, width=4)
    plan = schedule(g, "sequential", "topo")
    assert plan.waves.n_waves == len(g)  # one op per wave: no parallelism


def test_wave_independence():
    g = build_inception_like(n_blocks=3, width=4)
    plan = schedule(g, "opara", "opara")
    pos = {}
    for w in plan.waves.waves:
        for op in w.op_ids:
            pos[op] = w.index
    for node in g:
        for p in node.inputs:
            assert pos[p] < pos[node.op_id], "producer must be in earlier wave"


def test_mixed_dtype_consts_do_not_stack():
    """jnp.stack over mixed-dtype branch weights silently promotes, so a
    fused group would return different dtypes than unfused execution — the
    capturer must refuse to stack and run the branches as singles."""
    from repro.core.graph import OpGraph, OpKind
    from repro.core.profiler import gemm_cost

    g = OpGraph("mixed")
    x = g.add("x", OpKind.INPUT, out_shape=(8, 32))
    rng = np.random.default_rng(0)
    for i, dt in enumerate((jnp.float32, jnp.float16)):
        w = jnp.asarray(rng.standard_normal((32, 32)) * 0.1, dt)
        g.add(f"gemm{i}", OpKind.GEMM, [x], fn=lambda a, w: a @ w,
              cost=gemm_cost(8, 32, 32, 4), fuse_sig=("gemm", 32, 32),
              consts=(w,), payload="matmul")
    exe = compile_plan(schedule(g, "opara", "opara"))
    stats = exe.program_stats()
    assert stats["n_vmap"] == stats["n_branch_gemm"] == 0, stats
    assert stats["n_single"] == 2
    x_val = jnp.ones((8, 32), jnp.float32)
    got = exe({"x": x_val})
    ref = run_sequential_uncompiled(g, {"x": x_val}, output_ids=exe.output_ids)
    for a, b in zip(got, ref):
        assert jnp.asarray(a).dtype == jnp.asarray(b).dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-3, atol=1e-3)


def test_unconsumed_non_output_slot_freed_at_producer():
    """An op whose result nothing consumes (and which is not a program
    output) must be freed right after its producing step, not pinned for
    the whole program."""
    from repro.core import capture
    from repro.core.fusion import build_waves
    from repro.core.graph import OpGraph, OpKind
    from repro.core.launch_order import ORDER_POLICIES
    from repro.core.stream_alloc import allocate_streams

    g = OpGraph("dangling")
    x = g.add("x", OpKind.INPUT, out_shape=(4, 4))
    dead = g.add("dead", OpKind.ELEMENTWISE, [x], fn=lambda a: a * 2)
    live = g.add("live", OpKind.ELEMENTWISE, [x], fn=lambda a: a + 1)
    out = g.add("out", OpKind.ELEMENTWISE, [live], fn=lambda a: a - 1)
    plan_streams = allocate_streams(g)
    order = ORDER_POLICIES["topo"](g, None)
    waves = build_waves(g, plan_streams, order)
    exe = capture(g, waves, output_ids=[out])
    slot_of = {op: k for k, op in enumerate(g.nodes)}
    producing = next(s for s in exe.steps if s.op_ids == (dead,))
    assert slot_of[dead] in producing.free_slots, (
        "unconsumed non-output result must die at its producing step")
    got = exe({"x": jnp.ones((4, 4), jnp.float32)})
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.ones((4, 4), np.float32))


def test_bind_rejects_unknown_input_names():
    g = build_inception_like(n_blocks=1, width=2, with_payloads=True)
    exe = compile_plan(schedule(g, "opara", "opara"))
    x = jnp.ones((8, 64), jnp.float32)
    with pytest.raises(KeyError, match="unrecognized"):
        exe({"x": x, "xx": x})      # typo'd extra name
    with pytest.raises(KeyError, match="missing"):
        exe({})


def test_run_sequential_honors_output_ids():
    g = build_inception_like(n_blocks=2, width=2, with_payloads=True)
    x = jnp.ones((8, 64), jnp.float32)
    mid = [n.op_id for n in g if n.name == "b0_sum"]
    full = run_sequential_uncompiled(g, {"x": x})
    sel = run_sequential_uncompiled(g, {"x": x}, output_ids=mid)
    assert len(full) == len(g.leaves()) and len(sel) == 1
    assert sel[0].shape == (8, 64)


def _launched_grids(monkeypatch):
    """Record the grid of every branch_gemm kernel launch."""
    from repro.kernels.branch_gemm import ops

    grids = []
    real = ops.branch_gemm_pallas

    def spy(x, w, bm, bf, interpret):
        n, m, _ = x.shape
        grids.append(n * (m // bm) * (w.shape[-1] // bf))
        return real(x, w, bm=bm, bf=bf, interpret=interpret)

    monkeypatch.setattr(ops, "branch_gemm_pallas", spy)
    return grids


def test_pick_gemm_route_estimate_matches_kernel_tiles(monkeypatch):
    """The interpret-mode grid estimate must count the grid the branch_gemm
    wrapper actually launches (shared tile rule), M included."""
    from repro.core.capture import _VMAP, _BRANCH_GEMM, _pick_gemm_route
    from repro.kernels.branch_gemm.ops import branch_gemm

    grids = _launched_grids(monkeypatch)
    # K=640 (5 x 128) is taken whole: one grid step per branch, where the
    # old halving rule split it into 5 K-tiles
    w = jnp.zeros((640, 128), jnp.float32)
    branch_gemm(jnp.zeros((1, 8, 640), jnp.float32), w[None])
    assert grids == [1]
    assert _pick_gemm_route(w, 65, "auto", m=8) == _VMAP        # 65 > 64
    assert _pick_gemm_route(w, 64, "auto", m=8) == _BRANCH_GEMM  # 64 ≤ 64

    # M scales the grid too: 4 branches fit at m=512 (one row tile), not at
    # m=131072, where VMEM bounds the row tile to 4096 rows (32 of them)
    w2 = jnp.zeros((128, 128), jnp.float32)
    branch_gemm(jnp.zeros((1, 512, 128), jnp.float32), w2[None])
    assert grids[-1] == 1
    assert _pick_gemm_route(w2, 4, "auto", m=512) == _BRANCH_GEMM
    assert _pick_gemm_route(w2, 4, "auto", m=131072) == _VMAP
    # explicit kernel choice still wins
    assert _pick_gemm_route(w, 64, "pallas", m=4096) == _BRANCH_GEMM
    assert _pick_gemm_route(w2, 2, "vmap", m=8) == _VMAP


def test_program_stats_counts_the_launched_branch_gemm_grid(monkeypatch):
    """``branch_gemm_grid`` appears once the program has traced and equals
    the grid steps the kernel wrapper launched for one call."""
    from repro.core.graph import OpGraph, OpKind
    from repro.core.profiler import gemm_cost
    from repro.kernels.branch_gemm.ops import grid_steps, select_tiles

    g = OpGraph("two_gemms")
    x = g.add("x", OpKind.INPUT, out_shape=(2, 24, 640))
    rng = np.random.default_rng(0)
    for i in range(2):
        w = jnp.asarray(rng.standard_normal((640, 384)) * 0.05, jnp.float32)
        g.add(f"gemm{i}", OpKind.GEMM, [x], fn=lambda a, w: a @ w,
              cost=gemm_cost(48, 640, 384, 4), fuse_sig=("gemm", 640, 384),
              consts=(w,), payload="matmul")
    exe = compile_plan(schedule(g, "opara", "opara"), gemm_kernel="pallas")
    assert exe.program_stats()["n_branch_gemm"] == 1
    assert "branch_gemm_grid" not in exe.program_stats()
    grids = _launched_grids(monkeypatch)
    x_val = jnp.asarray(rng.standard_normal((2, 24, 640)), jnp.float32)
    got = exe({"x": x_val})
    ref = run_sequential_uncompiled(g, {"x": x_val},
                                    output_ids=exe.output_ids)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    assert select_tiles(48, 640, 384, 4) == (48, 384)
    assert grids == [grid_steps(2, 48, 640, 384, 4)] == [2]
    assert exe.program_stats()["branch_gemm_grid"] == 2.0
