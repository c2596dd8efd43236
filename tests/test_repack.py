"""Resource-aware wave repacking + simulator-guided autotuning.

Property tests (hypothesis when installed, deterministic seeds otherwise)
over the repacker's invariants — on random DAGs AND all four paper
topologies:

  (a) repacked schedules respect every graph dependency;
  (b) no wave's summed ``resource_demand()`` exceeds ``resource_cap``
      (except a single op that alone exceeds it, which runs solo);
  (c) the executed op set — and therefore the union of fusion-group
      members — is preserved exactly.

Plus: the estimate/simulate agreement and speed contract, autotune's
min-makespan guarantee over its candidate space, the api-level autotune
plan cache, and the calibration cache's disk tier.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    SimConfig,
    autotune,
    build_waves,
    estimate_makespan,
    repack_waves,
    schedule,
    simulate,
)
from repro.core import Session, SessionConfig
from repro.core.fusion import fusion_stats
from repro.core.graph import IntensityClass
from repro.core.launch_order import ORDER_POLICIES, validate_order
from repro.core.profiler import ModelProfiler, V5E
from repro.core.stream_alloc import allocate_streams

from conftest import build_inception_like, random_dag

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from benchmarks.workloads import (
    bert_like,
    googlenet_like,
    inception_v3_like,
    t5_like,
)

PAPER_TOPOLOGIES = {
    "googlenet": lambda: googlenet_like(1),
    "inception-v3": lambda: inception_v3_like(1),
    "bert": lambda: bert_like(1, seq=8, n_layers=3),
    "t5": lambda: t5_like(1, seq=8, n_layers=3),
}

TIGHT = SimConfig(resource_cap=24e6, sync_us=0.5, head_of_line=True)


def _check_repack_invariants(g, cfg):
    profiles = ModelProfiler(V5E).profile(g)
    plan = allocate_streams(g)
    order = ORDER_POLICIES["opara"](g, profiles)
    sched = repack_waves(g, plan, order, profiles, cfg=cfg)

    # (c) partition: every op exactly once, fusion groups partition waves
    seen = [op for w in sched.waves for op in w.op_ids]
    assert sorted(seen) == sorted(g.nodes)
    for w in sched.waves:
        grouped = sorted(op for grp in w.fusion_groups for op in grp)
        assert grouped == sorted(w.op_ids)

    # (a) dependencies: producers in strictly earlier waves
    wave_of = {op: w.index for w in sched.waves for op in w.op_ids}
    for node in g:
        for p in node.inputs:
            assert wave_of[p] < wave_of[node.op_id]

    # (b) resource cap per wave (solo oversized ops exempt)
    for w in sched.waves:
        used = sum(profiles[o].cost.resource_demand() for o in w.op_ids)
        assert used <= cfg.resource_cap or len(w.op_ids) == 1

    # flat order is a valid launch order
    validate_order(g, sched.flat_order())
    return sched, profiles


def _check_fusion_members_preserved(g, cfg):
    """Same fusion-group members execute, regrouped but never dropped."""
    profiles = ModelProfiler(V5E).profile(g)
    plan = allocate_streams(g)
    order = ORDER_POLICIES["opara"](g, profiles)
    base = build_waves(g, plan, order)
    packed = repack_waves(g, plan, order, profiles, cfg=cfg)
    members = lambda s: sorted(
        op for w in s.waves for grp in w.fusion_groups for op in grp)
    assert members(base) == members(packed)


if HAVE_HYPOTHESIS:
    dag_strategy = st.builds(
        lambda seed, n, p: random_dag(np.random.default_rng(seed), n, p),
        st.integers(0, 10_000), st.integers(1, 40), st.floats(0.05, 0.9))

    @settings(max_examples=40, deadline=None)
    @given(dag_strategy, st.floats(2e6, 200e6))
    def test_repack_invariants_random_dags(g, cap):
        _check_repack_invariants(
            g, SimConfig(resource_cap=cap, head_of_line=True))

    @settings(max_examples=20, deadline=None)
    @given(dag_strategy)
    def test_repack_preserves_fusion_members_random(g):
        _check_fusion_members_preserved(g, TIGHT)
else:
    @pytest.mark.parametrize("seed", range(20))
    def test_repack_invariants_random_dags(seed):
        g = random_dag(np.random.default_rng(seed), 5 + seed * 2)
        cap = [2e6, 24e6, 200e6][seed % 3]
        _check_repack_invariants(
            g, SimConfig(resource_cap=cap, head_of_line=True))

    @pytest.mark.parametrize("seed", range(8))
    def test_repack_preserves_fusion_members_random(seed):
        g = random_dag(np.random.default_rng(seed), 10 + seed * 3)
        _check_fusion_members_preserved(g, TIGHT)


@pytest.mark.parametrize("name", sorted(PAPER_TOPOLOGIES))
def test_repack_invariants_paper_topologies(name):
    g = PAPER_TOPOLOGIES[name]()
    _check_repack_invariants(g, TIGHT)
    _check_fusion_members_preserved(g, TIGHT)


def test_workload_nodes_own_their_costs():
    """OpCost is mutable (apply_profile writes measured_us in place) —
    workload builders must never share one instance across nodes, or
    hydrated timings cross-contaminate."""
    for name in sorted(PAPER_TOPOLOGIES):
        g = PAPER_TOPOLOGIES[name]()
        ids = [id(n.cost) for n in g]
        assert len(ids) == len(set(ids)), name


def test_repack_mixes_intensity_classes():
    """Complementary fill lowers the same-class overlap fraction vs the
    order-bucketing packer on a class-diverse graph."""
    g = bert_like(1, seq=8, n_layers=3)
    profiles = ModelProfiler(V5E).profile(g)
    classes = {profiles[i].intensity for i in g.nodes}
    assert classes == {IntensityClass.MEMORY, IntensityClass.COMPUTE}, \
        "kind-aware classification must yield both classes at batch 1"
    plan = allocate_streams(g)
    order = ORDER_POLICIES["opara"](g, profiles)
    cfg = SimConfig(resource_cap=128e6, head_of_line=True)
    base = fusion_stats(build_waves(g, plan, order), profiles,
                        cfg.resource_cap)
    packed = fusion_stats(repack_waves(g, plan, order, profiles, cfg=cfg),
                          profiles, cfg.resource_cap)
    assert packed["same_class_overlap_frac"] <= base["same_class_overlap_frac"]


def test_estimate_matches_simulate_under_head_of_line():
    """With non-preemptive dispatch the sweep is a faithful reduction of the
    event-driven simulator."""
    for name in sorted(PAPER_TOPOLOGIES):
        g = PAPER_TOPOLOGIES[name]()
        p = schedule(g, "opara", "opara")
        cfg = SimConfig(resource_cap=52e6, sync_us=0.5, head_of_line=True)
        sim = simulate(g, p.stream_plan, p.order, p.profiles, cfg)
        est = estimate_makespan(g, p.stream_plan, p.order, p.profiles, cfg)
        assert est == pytest.approx(sim.makespan_us, rel=1e-9), name


def test_estimate_tracks_simulate_without_head_of_line():
    """FIFO arbitration differs, but the cost model must still rank
    schedules — keep it within a loose band of the simulator."""
    for seed in range(5):
        g = random_dag(np.random.default_rng(seed), 30)
        p = schedule(g, "opara", "opara")
        cfg = SimConfig(sync_us=0.5)
        sim = simulate(g, p.stream_plan, p.order, p.profiles, cfg)
        est = estimate_makespan(g, p.stream_plan, p.order, p.profiles, cfg)
        assert est == pytest.approx(sim.makespan_us, rel=0.35)


def test_estimate_is_fast():
    """≥10× cheaper than the event-driven simulator on a big graph (the
    acceptance bar is measured on bert-180L in bench_overhead; a 40-layer
    stack keeps the unit test quick while exercising the same asymptotics)."""
    import time
    g = bert_like(1, n_layers=40)
    p = schedule(g, "opara", "opara")
    cfg = SimConfig(resource_cap=128e6, sync_us=0.5, head_of_line=True)
    t0 = time.perf_counter()
    simulate(g, p.stream_plan, p.order, p.profiles, cfg)
    t_sim = time.perf_counter() - t0
    t_est = min(_once(lambda: estimate_makespan(
        g, p.stream_plan, p.order, p.profiles, cfg)) for _ in range(3))
    assert t_sim / t_est >= 10.0


def _once(fn):
    import time
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def test_autotune_never_worse_than_its_candidates():
    cfg = SimConfig(resource_cap=52e6, sync_us=0.5, head_of_line=True)
    for name in sorted(PAPER_TOPOLOGIES):
        g = PAPER_TOPOLOGIES[name]()
        tuned = autotune(g, cfg=cfg)
        assert tuned.n_candidates >= 4
        for alloc in ("opara", "nimble"):
            for order in ("opara", "topo", "critical_path"):
                p = schedule(g, alloc, order)
                est = estimate_makespan(g, p.stream_plan, p.order,
                                        p.profiles, cfg)
                assert tuned.est_makespan_us <= est + 1e-6, (name, alloc, order)


def test_autotune_plan_is_simulatable_and_capturable():
    from repro.core import compile_plan, simulate_plan
    g = build_inception_like(n_blocks=3, width=4)
    cfg = SimConfig(resource_cap=24e6, head_of_line=True)
    tuned = autotune(g, cfg=cfg)
    res = simulate_plan(tuned, cfg)
    assert res.makespan_us > 0
    exe = compile_plan(tuned)         # capture consumes repacked waves
    import jax.numpy as jnp
    outs = exe({"x": jnp.ones((8, 64), jnp.float32)})
    assert outs and all(o.shape == (8, 64) for o in outs)


def test_autotune_stats_surface_repack_efficacy():
    g = bert_like(1, seq=8, n_layers=2)
    tuned = autotune(g, cfg=SimConfig(resource_cap=128e6, head_of_line=True))
    s = tuned.stats()
    for key in ("mean_wave_resource_util", "max_wave_resource_util",
                "same_class_overlap_frac", "repacked", "autotune_ms",
                "n_candidates", "est_makespan_us"):
        assert key in s
    assert s["n_candidates"] >= 4


def test_session_plan_autotune_caches_by_sim_cfg():
    g = build_inception_like(n_blocks=2, width=3, with_payloads=False)
    cfg_a = SimConfig(resource_cap=24e6, head_of_line=True)
    cfg_b = SimConfig(resource_cap=200e6, head_of_line=True)
    sess = Session(autotune=True, sim_cfg=cfg_a)
    p1 = sess.plan(g)
    assert sess.cache_stats()["plan_misses"] == 1
    p2 = sess.plan(g)
    assert p2 is p1
    assert sess.cache_stats()["plan_hits"] == 1
    # same session state, different cost model → distinct tuned plan.  The
    # api shims route per-call config overrides through the same private
    # entry points, so this mirrors the legacy plan(autotune=True, sim_cfg=)
    sess._plan(g, dataclasses.replace(sess.config, sim_cfg=cfg_b))
    assert sess.cache_stats()["plan_misses"] == 2
    sess._plan(g, dataclasses.replace(sess.config, autotune=False))
    assert sess.cache_stats()["plan_misses"] == 3


def test_calibration_survives_memory_clear_via_disk(tmp_path):
    """Process-restart analogue: a second Session (or clear_caches()) drops
    the memory tier, the shared disk tier rehydrates without re-timing."""
    import jax.numpy as jnp
    from conftest import count_measure_calls
    g = build_inception_like(n_blocks=1, width=2)
    inputs = {0: jnp.ones((8, 64), jnp.float32)}
    sess = Session(calib_dir=str(tmp_path / "calib"))
    with count_measure_calls() as calls:
        t1 = sess.calibrate(g, inputs, repeats=1)
        assert calls["n"] == 1
        sess.clear_caches()                 # "restart"
        t2 = sess.calibrate(g, inputs, repeats=1)
        assert calls["n"] == 1, "disk tier must prevent re-timing"
        # a brand-new session pointed at the same disk tier also rehydrates
        sess2 = Session(calib_dir=str(tmp_path / "calib"))
        sess2.calibrate(g, inputs, repeats=1)
        assert calls["n"] == 1
    assert t2.measured_us == t1.measured_us
    stats = sess.cache_stats()   # counters were reset by the "restart"
    assert stats["calib_disk_hits"] == 1 and stats["calib_misses"] == 0


def test_calibration_load_false_skips_disk(tmp_path):
    import jax.numpy as jnp
    from conftest import count_measure_calls
    g = build_inception_like(n_blocks=1, width=2)
    inputs = {0: jnp.ones((8, 64), jnp.float32)}
    sess = Session(calib_dir=str(tmp_path / "calib"))
    with count_measure_calls() as calls:
        sess.calibrate(g, inputs, repeats=1)
        sess.clear_caches()
        # escape hatch: SessionConfig(load_calibration=False) — e.g. after a
        # runtime upgrade invalidates persisted timings
        cold = Session(calib_dir=str(tmp_path / "calib"),
                       load_calibration=False)
        cold.plan(g, measured_inputs=inputs)
        assert calls["n"] == 2, "load_calibration=False must re-measure"
    assert cold.cache_stats()["calib_disk_hits"] == 0


def test_calibration_disk_corruption_falls_back(tmp_path):
    import jax.numpy as jnp
    from repro.core.session import _calib_path, calibration_key
    calib_dir = str(tmp_path / "calib")
    g = build_inception_like(n_blocks=1, width=2)
    inputs = {0: jnp.ones((8, 64), jnp.float32)}
    sess = Session(calib_dir=calib_dir)
    sess.calibrate(g, inputs, repeats=1)
    path = _calib_path(calibration_key(g, inputs), calib_dir)
    with open(path, "w") as f:
        f.write("{not json")
    sess.clear_caches()
    sess.calibrate(g, inputs, repeats=1)    # must re-measure, not crash
    assert sess.cache_stats()["calib_misses"] == 1
