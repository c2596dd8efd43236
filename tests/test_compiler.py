"""Capture-time program compiler: pre-lowered executor, const hoisting,
branch-GEMM routing, topology cache and the compiled-plan cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Session
from repro.core import (
    OpGraph,
    OpKind,
    capture,
    compile_plan,
    run_sequential_uncompiled,
    schedule,
)
from repro.core.profiler import ModelProfiler

from conftest import build_inception_like


@pytest.fixture
def sess():
    return Session()


# -- executor correctness on real model graphs --------------------------------

def test_compiled_executor_matches_sequential_on_model_graph(sess):
    """Captured outputs match the uncompiled sequential reference on a real
    opgraph_export model graph with fusion groups present."""
    from repro.configs import get_config
    from repro.models import make_model
    from repro.models.opgraph_export import build_lm_opgraph

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    g = build_lm_opgraph(cfg, batch=2, seq=8, params=params, n_layers=2)

    exe = sess.optimize(g)
    # fusion groups must actually be exercised (stacked steps present)
    stats = exe.program_stats()
    assert stats["n_vmap"] + stats["n_branch_gemm"] >= 1, stats

    tokens = jnp.zeros((2, 8), jnp.int32)
    got = exe({"tokens": tokens})
    ref = run_sequential_uncompiled(g, {"tokens": tokens})
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        # smoke models run in bfloat16: stacked vs per-op GEMMs may differ
        # by one bf16 ulp upstream of the (float32) logits head; f32
        # graphs must match tightly.
        tol = 1e-2 if cfg.dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


def test_branch_gemm_routing_agrees_with_vmap_path():
    """The Pallas fused-GEMM route and the generic vmap route are the same
    function (tileable shapes: d=128 → the kernel path actually runs)."""
    g = build_inception_like(n_blocks=3, width=4, d=128, tokens=8,
                             with_payloads=True, seed=7)
    plan = schedule(g, "opara", "opara")
    exe_pallas = compile_plan(plan, gemm_kernel="pallas")
    exe_vmap = compile_plan(plan, gemm_kernel="vmap")

    assert exe_pallas.program_stats()["n_branch_gemm"] >= 1
    assert exe_vmap.program_stats()["n_branch_gemm"] == 0

    x = jnp.asarray(np.random.default_rng(5).standard_normal((8, 128)),
                    jnp.float32)
    got_p = exe_pallas({"x": x})
    got_v = exe_vmap({"x": x})
    ref = run_sequential_uncompiled(g, {"x": x})
    np.testing.assert_allclose(np.asarray(got_p[0]), np.asarray(got_v[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_p[0]), np.asarray(ref[0]),
                               rtol=1e-5, atol=1e-5)


def test_consts_hoisted_and_stacked_once_at_capture():
    """Stacked groups hold device arrays stacked at capture time (leading
    dim == group width) — nothing is re-stacked inside the trace."""
    g = build_inception_like(n_blocks=2, width=4, with_payloads=True)
    exe = compile_plan(schedule(g, "opara", "opara"))
    stacked = [s for s in exe.steps if len(s.op_ids) > 1]
    assert stacked, "expected at least one fused group"
    for s in stacked:
        for c in s.consts:
            assert isinstance(c, jax.Array)
            assert c.shape[0] == len(s.op_ids)


def test_slot_env_frees_dead_intermediates():
    """Last-use analysis marks intermediates dead; outputs stay correct."""
    g = build_inception_like(n_blocks=3, width=4, with_payloads=True)
    exe = compile_plan(schedule(g, "opara", "opara"))
    freed = {s for step in exe.steps for s in step.free_slots}
    assert freed, "expected dead intermediates to be freed"
    # output slots are never freed
    slot_of = {op: k for k, op in enumerate(g.nodes)}
    assert not freed & {slot_of[o] for o in exe.output_ids}
    x = jnp.ones((8, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(exe({"x": x})[0]),
        np.asarray(run_sequential_uncompiled(g, {"x": x})[0]),
        rtol=1e-5, atol=1e-5)


# -- compiled-plan cache -------------------------------------------------------

def test_plan_cache_hit_returns_identical_executable(sess):
    g = build_inception_like(n_blocks=2, width=3, with_payloads=True)
    e1 = sess.optimize(g)
    e2 = sess.optimize(g)
    assert e1 is e2
    stats = sess.cache_stats()
    assert stats["exec_hits"] == 1 and stats["exec_misses"] == 1
    assert stats["plan_hits"] == 1 and stats["plan_misses"] == 1


def test_second_schedule_does_zero_reprofiling(monkeypatch, sess):
    calls = {"profile": 0}
    orig = ModelProfiler.profile

    def counting(self, graph):
        calls["profile"] += 1
        return orig(self, graph)

    monkeypatch.setattr(ModelProfiler, "profile", counting)
    g = build_inception_like(n_blocks=2, width=3, with_payloads=True)
    sess.plan(g)
    assert calls["profile"] == 1
    sess.plan(g)
    assert calls["profile"] == 1, "cache hit must not re-profile"


def test_plan_cache_rebinds_structurally_equal_graph(sess):
    """Two separately-built graphs with the same structure but different
    weights share the schedule, NOT the executable — each output matches
    its own weights."""
    g1 = build_inception_like(n_blocks=2, width=3, with_payloads=True, seed=1)
    g2 = build_inception_like(n_blocks=2, width=3, with_payloads=True, seed=2)
    p1 = sess.plan(g1)
    p2 = sess.plan(g2)
    assert sess.cache_stats()["plan_hits"] == 1
    assert p2.graph is g2 and p1.graph is g1
    assert p1.order == p2.order

    e1, e2 = sess.optimize(g1), sess.optimize(g2)
    assert e1 is not e2, "different weights must not share an executable"
    x = jnp.ones((8, 64), jnp.float32)
    for g, e in ((g1, e1), (g2, e2)):
        np.testing.assert_allclose(
            np.asarray(e({"x": x})[0]),
            np.asarray(run_sequential_uncompiled(g, {"x": x})[0]),
            rtol=1e-5, atol=1e-5)


def test_graph_mutation_changes_signature():
    from repro.core import graph_signature
    g = build_inception_like(n_blocks=2, width=3, with_payloads=False)
    sig1 = graph_signature(g)
    g.add("extra", OpKind.ELEMENTWISE, [0])
    assert graph_signature(g) != sig1


def test_content_weights_key_reuses_executable_on_reload():
    """Checkpoint-reload scenario: rebuilding the same model recreates
    identical weight ARRAYS (new objects, same bytes).  The default identity
    fingerprint misses; ``weights_key="content"`` reuses the executable."""
    g1 = build_inception_like(n_blocks=2, width=3, with_payloads=True, seed=5)
    g2 = build_inception_like(n_blocks=2, width=3, with_payloads=True, seed=5)

    content = Session(weights_key="content")
    e1 = content.optimize(g1)
    e2 = content.optimize(g2)
    assert e1 is e2, "identical weight content must share the executable"
    assert content.cache_stats()["exec_hits"] == 1

    # identity mode on the same pair: arrays are distinct objects → miss
    identity = Session()
    i1 = identity.optimize(g1)
    i2 = identity.optimize(g2)
    assert i1 is not i2

    # different weight values must NOT collide in content mode
    g3 = build_inception_like(n_blocks=2, width=3, with_payloads=True, seed=6)
    e3 = content.optimize(g3)
    assert e3 is not e1
    # and the shared executable computes with the weights it closed over
    x = jnp.ones((8, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(e2({"x": x})[0]),
        np.asarray(run_sequential_uncompiled(g1, {"x": x})[0]),
        rtol=1e-5, atol=1e-5)


def test_weights_key_rejects_unknown_mode():
    from repro.core import SessionConfig
    with pytest.raises(ValueError):
        SessionConfig(weights_key="values")
    g = build_inception_like(n_blocks=1, width=2, with_payloads=True)
    with pytest.raises(ValueError), pytest.warns(DeprecationWarning):
        import repro.core.api as opara
        opara.optimize(g, weights_key="values")


# -- topology cache ------------------------------------------------------------

def test_topology_cache_invalidated_by_add():
    g = OpGraph("t")
    a = g.add("a", OpKind.INPUT)
    b = g.add("b", OpKind.GEMM, [a])
    assert g.topological_order() == [a, b]
    assert g.leaves() == [b]
    c = g.add("c", OpKind.GEMM, [b])
    assert g.topological_order() == [a, b, c]
    assert g.leaves() == [c]
    assert g.unique_successors_map()[b] == [c]


def test_topology_queries_are_consistent_with_recompute():
    from conftest import random_dag
    rng = np.random.default_rng(0)
    g = random_dag(rng, 60)
    order = g.topological_order()
    pos = {i: k for k, i in enumerate(order)}
    for node in g:
        for p in node.inputs:
            assert pos[p] < pos[node.op_id]
    indeg = g.indegree_map()
    assert indeg == {i: len(set(n.inputs)) for i, n in g.nodes.items()}
    # indegree_map must hand out a private copy (schedulers decrement it)
    indeg[order[0]] = 999
    assert g.indegree_map()[order[0]] != 999
