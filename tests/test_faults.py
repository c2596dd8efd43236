"""Degradation ladder + deterministic fault injection (docs/robustness.md).

Covers, per ISSUE: the :class:`repro.runtime.FaultPlan` grammar and firing
semantics; the calibration measure-retry → analytic degrade chain; the disk
tier under injected read/write faults, concurrent writers and mid-write
corruption; the capture route ladder (branch_gemm→vmap,
grouped_gemm→sequential, plan_validate→sequential schedule); the serving
engine's poisoned-request isolation and decode watchdog; and a differential
property — any single-site fault with an available fallback produces the
same outputs as the fault-free run.
"""
import os
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Session, SessionConfig, run_sequential_uncompiled
from repro.core.profiler import ProfileTable
from repro.core.session import (
    _calib_disk_evict,
    _calib_disk_load,
    _calib_disk_store,
)
from repro.runtime import (
    DegradationLog,
    DegradationWarning,
    FaultInjected,
    FaultPlan,
    FaultSpec,
    activate,
    retry_with_backoff,
)
from repro.runtime import faults as faults_mod

from conftest import build_inception_like, count_measure_calls
from test_grouped_gemm import build_ragged_graph, _inputs_for

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic fallback below
    HAVE_HYPOTHESIS = False


def _inputs(g):
    return {n.op_id: jnp.ones((8, 64), jnp.float32) for n in g if n.fn is None}


# -- FaultPlan unit behavior ---------------------------------------------------

def test_fault_spec_validates_site_and_mode():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec(site="bogus")
    with pytest.raises(ValueError, match="unknown fault mode"):
        FaultSpec(site="decode_step", mode="bogus")
    with pytest.raises(ValueError, match="duplicate"):
        FaultPlan([FaultSpec(site="decode_step"), FaultSpec(site="decode_step")])


def test_fault_plan_parse_grammar():
    plan = FaultPlan.parse(
        "calibration_measure:raise:2; decode_step:corrupt:-1:3,plan_validate")
    assert plan.specs["calibration_measure"] == FaultSpec(
        site="calibration_measure", mode="raise", times=2)
    assert plan.specs["decode_step"] == FaultSpec(
        site="decode_step", mode="corrupt", times=-1, arg=3.0)
    # bare site → raise mode, every activation
    assert plan.specs["plan_validate"] == FaultSpec(
        site="plan_validate", mode="raise", times=-1)


def test_fire_counts_activations_and_disarms():
    plan = FaultPlan.single("kernel_compile", times=1)
    assert plan.armed("kernel_compile")
    with pytest.raises(FaultInjected) as exc:
        plan.fire("kernel_compile")
    assert exc.value.site == "kernel_compile"
    # second activation: disarmed — payload passes through, nothing counted
    assert plan.fire("kernel_compile", payload="ok") == "ok"
    assert plan.fired["kernel_compile"] == 1
    # unkeyed sites are free
    assert plan.fire("decode_step", payload=5) == 5
    assert plan.describe()["kernel_compile"]["fired"] == 1


def test_corrupt_mode_payloads():
    plan = FaultPlan.single("calib_disk_write", mode="corrupt", times=-1)
    mangled = plan.fire("calib_disk_write", payload='{"key": "v"}')
    assert "~CORRUPT~" in mangled
    with pytest.raises(ValueError):
        import json
        json.loads(mangled)
    arr_plan = FaultPlan.single("decode_step", mode="corrupt", times=-1, arg=1)
    poisoned = arr_plan.fire("decode_step", payload=jnp.ones((3, 4)))
    finite = np.isfinite(np.asarray(poisoned)).all(axis=-1)
    assert list(finite) == [True, False, True]   # exactly row 1 poisoned


def test_delay_mode_uses_injected_clock():
    plan = FaultPlan.single("decode_step", mode="delay", times=1, arg=0.7)
    slept = []
    plan.sleep = slept.append
    assert plan.fire("decode_step", payload="x") == "x"
    assert slept == [0.7]


def test_activate_overrides_env_plan(monkeypatch):
    monkeypatch.setenv(faults_mod.ENV_VAR, "plan_validate:raise:1")
    env_plan = faults_mod.get_active()
    assert env_plan is not None and "plan_validate" in env_plan.specs
    assert faults_mod.get_active() is env_plan        # cached per env string
    override = FaultPlan.single("decode_step")
    with activate(override):
        assert faults_mod.get_active() is override
    assert faults_mod.get_active() is env_plan
    monkeypatch.delenv(faults_mod.ENV_VAR)
    assert faults_mod.get_active() is None


def test_retry_with_backoff_bounded_and_clock_injectable():
    calls = {"n": 0}
    slept, retried = [], []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    out = retry_with_backoff(flaky, retries=2, base_delay_s=0.25,
                             sleep=slept.append,
                             on_retry=lambda a, e: retried.append(a))
    assert out == "ok" and calls["n"] == 3
    assert slept == [0.25, 0.5] and retried == [0, 1]

    with pytest.raises(RuntimeError, match="always"):
        retry_with_backoff(lambda: (_ for _ in ()).throw(RuntimeError("always")),
                           retries=1, sleep=lambda s: None)


# -- calibration ladder --------------------------------------------------------

def test_calibration_measure_retries_then_succeeds():
    sess = Session(fault_plan=FaultPlan.single("calibration_measure", times=1))
    g = build_inception_like(n_blocks=1, width=2)
    with count_measure_calls() as timing:
        table = sess.calibrate(g, _inputs(g))
    assert table is not None and timing["n"] == 1
    stats = sess.cache_stats()
    assert stats["calib_retries"] == 1
    assert stats["calib_degraded_analytic"] == 0
    assert [e.site for e in sess.guard_log.events] == ["calibration_measure"]
    assert sess.guard_log.events[0].action == "retry#1"


def test_calibration_degrades_to_analytic_when_measure_keeps_failing():
    sess = Session(
        fault_plan=FaultPlan.single("calibration_measure", times=-1))
    g = build_inception_like(n_blocks=2, width=3)
    x = jnp.ones((8, 64), jnp.float32)
    with pytest.warns(DegradationWarning, match="measured->analytic"):
        model = sess.compile(g, inputs=_inputs(g))
    assert model.provenance["calibration"] == "analytic (degraded)"
    stats = sess.cache_stats()
    assert stats["calib_degraded_analytic"] == 1
    assert stats["calib_retries"] == sess.config.calib_retries
    # the analytic schedule still computes the right function
    np.testing.assert_allclose(
        np.asarray(model({"x": x})[0]),
        np.asarray(run_sequential_uncompiled(g, {"x": x})[0]),
        rtol=1e-5, atol=1e-5)
    degraded = model.explain()["degraded"]
    assert any(d["site"] == "calibration_measure"
               and d["action"] == "measured->analytic" for d in degraded)


def test_calibration_backoff_uses_injected_session_clock():
    sess = Session(
        calib_backoff_s=0.25,
        fault_plan=FaultPlan.single("calibration_measure", times=2))
    delays = []
    sess._sleep = delays.append
    table = sess.calibrate(build_inception_like(n_blocks=1, width=2),
                           {0: jnp.ones((8, 64), jnp.float32)})
    assert table is not None
    assert delays == [0.25, 0.5]                 # doubling, injected clock
    assert sess.cache_stats()["calib_retries"] == 2


def test_disk_write_fault_degrades_to_memory_tier(tmp_path, monkeypatch):
    calib_dir = str(tmp_path / "calib-wf")
    monkeypatch.setenv("REPRO_CALIB_DIR", calib_dir)
    sess = Session(fault_plan=FaultPlan.single("calib_disk_write", times=1))
    g = build_inception_like(n_blocks=1, width=2)
    table = sess.calibrate(g, _inputs(g))
    assert table is not None                      # build survived
    assert sess.cache_stats()["calib_disk_errors"] == 1
    # nothing published, nothing stranded
    if os.path.isdir(calib_dir):
        assert not os.listdir(calib_dir)
    # the memory tier still serves this session
    with count_measure_calls() as timing:
        sess.calibrate(g, _inputs(g))
    assert timing["n"] == 0
    assert sess.cache_stats()["calib_hits"] == 1


def test_corrupt_disk_write_is_survivable_as_a_later_miss():
    """Mid-write corruption publishes an atomically-whole but unparseable
    entry: later sessions treat it as a miss, re-measure, and repair the
    entry in place."""
    g = build_inception_like(n_blocks=1, width=2)
    s1 = Session(fault_plan=FaultPlan.single("calib_disk_write",
                                             mode="corrupt", times=1))
    with count_measure_calls() as timing:
        assert s1.calibrate(g, _inputs(g)) is not None
        assert timing["n"] == 1
        s2 = Session()
        assert s2.calibrate(g, _inputs(g)) is not None
        assert timing["n"] == 2                   # corrupt entry → re-measure
    assert s2.cache_stats()["calib_disk_hits"] == 0
    s3 = Session()
    with count_measure_calls() as timing:
        assert s3.calibrate(g, _inputs(g)) is not None
        assert timing["n"] == 0                   # s2 repaired the entry
    assert s3.cache_stats()["calib_disk_hits"] == 1


def test_disk_read_fault_counts_and_falls_back_to_measure():
    g = build_inception_like(n_blocks=1, width=2)
    Session().calibrate(g, _inputs(g))            # publish a good entry
    sess = Session(fault_plan=FaultPlan.single("calib_disk_read", times=1))
    with count_measure_calls() as timing:
        table = sess.calibrate(g, _inputs(g))
    assert table is not None and timing["n"] == 1
    stats = sess.cache_stats()
    assert stats["calib_disk_errors"] == 1 and stats["calib_disk_hits"] == 0


def test_disk_tier_survives_concurrent_writers_and_corruption(tmp_path):
    d = str(tmp_path / "calib-conc")
    tables = {i: ProfileTable(device="tpu:TPU v5 lite",
                              measured_us=((0, 1.0 + i), (1, 2.0 * i + 1.0)))
              for i in range(8)}
    corrupting = FaultPlan.single("calib_disk_write", mode="corrupt", times=1)

    def write(i):
        _calib_disk_store(("k", i), tables[i], dirpath=d,
                          faults=corrupting if i == 3 else None)

    threads = [threading.Thread(target=write, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every write published atomically — no stranded temp files
    assert not [p for p in os.listdir(d) if p.endswith(".tmp")]
    for i in range(8):
        got = _calib_disk_load(("k", i), dirpath=d)
        if i == 3:
            assert got is None                    # whole but unparseable
        else:
            assert got == tables[i]
    _calib_disk_evict(d, max_entries=3)
    assert len([p for p in os.listdir(d) if p.endswith(".json")]) == 3


# -- capture route ladder ------------------------------------------------------

def test_plan_validate_fault_degrades_to_sequential_schedule():
    sess = Session(fault_plan=FaultPlan.single("plan_validate", times=1))
    g = build_inception_like(n_blocks=2, width=3)
    x = jnp.ones((8, 64), jnp.float32)
    with pytest.warns(DegradationWarning, match="schedule->sequential"):
        model = sess.compile(g)
    assert model.provenance["executable"] == "degraded"
    assert sess.cache_stats()["degraded_routes"] == 1
    assert sess.cache_stats()["exec_entries"] == 0   # degraded → never cached
    np.testing.assert_allclose(
        np.asarray(model({"x": x})[0]),
        np.asarray(run_sequential_uncompiled(g, {"x": x})[0]),
        rtol=1e-5, atol=1e-5)
    assert any(d["site"] == "plan_validate"
               for d in model.explain()["degraded"])
    # fault disarmed → next build compiles the real schedule and caches it
    healthy = sess.compile(g)
    assert healthy.provenance["executable"] == "miss"
    assert sess.cache_stats()["exec_entries"] == 1
    assert sess.compile(g).provenance["executable"] == "hit"


def test_kernel_compile_fault_routes_branch_gemm_to_vmap():
    sess = Session(gemm_kernel="pallas",
                   fault_plan=FaultPlan.single("kernel_compile", times=-1))
    g = build_inception_like(n_blocks=2, width=3)
    x = jnp.ones((8, 64), jnp.float32)
    model = sess.compile(g)
    stats = model.executable.program_stats()
    assert stats["n_branch_gemm"] == 0 and stats["n_vmap"] > 0
    assert model.provenance["executable"] == "degraded"
    assert sess.cache_stats()["degraded_routes"] >= 1
    assert sess.cache_stats()["exec_entries"] == 0
    np.testing.assert_allclose(
        np.asarray(model({"x": x})[0]),
        np.asarray(run_sequential_uncompiled(g, {"x": x})[0]),
        rtol=1e-5, atol=1e-5)
    assert any(d["action"] == "branch_gemm->vmap"
               for d in model.explain()["degraded"])


def test_grouped_gemm_route_fault_degrades_to_sequential_steps():
    sess = Session(fault_plan=FaultPlan.single("grouped_gemm_route",
                                               times=-1))
    g = build_ragged_graph((8, 24, 16))
    model = sess.compile(g)
    stats = model.executable.program_stats()
    assert stats["n_grouped_gemm"] == 0
    assert model.provenance["executable"] == "degraded"
    inputs = _inputs_for(g)
    got = model(inputs)
    ref = run_sequential_uncompiled(g, inputs,
                                    output_ids=model.executable.output_ids)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    assert any(d["action"] == "grouped_gemm->sequential"
               for d in model.explain()["degraded"])


def test_kernel_wrappers_fall_back_to_reference_on_injected_launch_failure():
    from repro.kernels.branch_gemm.ops import branch_gemm
    from repro.kernels.branch_gemm.ref import branch_gemm_ref
    from repro.kernels.grouped_gemm.ops import grouped_gemm_parts
    from repro.kernels.grouped_gemm.ref import grouped_gemm_ref

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 128)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 128, 128)) * 0.1, jnp.float32)
    with activate(FaultPlan.single("kernel_compile", times=1)):
        with pytest.warns(DegradationWarning, match="einsum reference"):
            out = branch_gemm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(branch_gemm_ref(x, w)),
                               rtol=1e-5, atol=1e-5)

    xs = [jnp.asarray(rng.standard_normal((m, 128)) * 0.1, jnp.float32)
          for m in (8, 24)]
    with activate(FaultPlan.single("grouped_gemm_route", times=1)):
        with pytest.warns(DegradationWarning, match="einsum reference"):
            outs = grouped_gemm_parts(xs, w)
    for i, (o, x_i) in enumerate(zip(outs, xs)):
        ref = grouped_gemm_ref(x_i, w[i:i + 1], (x_i.shape[0],))
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# -- serving engine fault isolation --------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_config
    from repro.models import make_model

    cfg = get_config("llama3.2-1b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _run_engine(model, params, fault_plan=None, n_requests=3, max_tokens=4):
    from repro.serving import InferenceEngine, Request

    engine = InferenceEngine(model, params, max_slots=n_requests, max_len=32,
                             fault_plan=fault_plan)
    for rid in range(n_requests):
        engine.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                              max_tokens=max_tokens))
    done = {r.rid: r for r in engine.run()}
    return engine, done


def test_engine_poisoned_request_fails_alone(small_model):
    from repro.serving import RequestState

    cfg, model, params = small_model
    _, clean = _run_engine(model, params)
    # corrupt-mode decode_step poisons slot 0's logits on the first decode
    # tick — a poisoned request, co-batched with two healthy ones
    plan = FaultPlan.single("decode_step", mode="corrupt", times=1, arg=0)
    engine, done = _run_engine(model, params, fault_plan=plan)
    assert len(done) == 3
    assert done[0].state is RequestState.FAILED
    assert "non-finite" in done[0].error
    assert engine.fault_stats["failed_requests"] == 1
    for rid in (1, 2):
        assert done[rid].state is RequestState.DONE
        assert done[rid].output == clean[rid].output   # co-batch unaffected


def test_engine_watchdog_falls_back_to_eager_decode(small_model):
    from repro.serving import RequestState

    cfg, model, params = small_model
    _, clean = _run_engine(model, params)
    plan = FaultPlan.single("decode_step", mode="raise", times=1)
    with pytest.warns(DegradationWarning, match="decode watchdog"):
        engine, done = _run_engine(model, params, fault_plan=plan)
    assert engine._use_compiled is False               # latched
    assert engine.fault_stats["watchdog_fallbacks"] == 1
    assert len(done) == 3
    for rid in range(3):
        assert done[rid].state is RequestState.DONE
        assert done[rid].output == clean[rid].output   # eager == jitted


def test_cached_decode_fn_diagnoses_garbage_collected_model():
    import gc

    from repro.configs import get_config
    from repro.models import make_model
    from repro.models.transformer import init_decode_caches
    from repro.serving.engine import _cached_decode_fn

    cfg = get_config("llama3.2-1b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    fn = _cached_decode_fn(model)
    caches = init_decode_caches(cfg, 1, 8)
    del model
    gc.collect()
    with pytest.raises(RuntimeError, match="garbage-collected"):
        fn(params, caches, jnp.zeros((1,), jnp.int32),
           jnp.zeros((1,), jnp.int32))


# -- differential property: single-site fault == fault-free outputs ------------

_GRAPH_SITES = ("kernel_compile", "plan_validate", "calibration_measure",
                "calib_disk_read", "calib_disk_write")


def _check_single_site_fault_preserves_outputs(seed, site):
    rng = np.random.default_rng(seed)
    g = build_inception_like(n_blocks=1 + seed % 3, width=2 + seed % 2,
                             seed=seed)
    x = jnp.asarray(rng.standard_normal((8, 64)) * 0.1, jnp.float32)
    calib_inputs = {n.op_id: x for n in g if n.fn is None}
    ref = run_sequential_uncompiled(g, {"x": x})
    if site == "calib_disk_read":
        # the read site only fires on a populated tier
        Session().calibrate(g, calib_inputs)
    cfg = SessionConfig(gemm_kernel="pallas",
                        load_calibration=(site == "calib_disk_read"),
                        fault_plan=FaultPlan.single(site, times=-1))
    sess = Session(cfg)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DegradationWarning)
        model = sess.compile(g, inputs=calib_inputs)
        got = model({"x": x})
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    # the degradation is never silent: provenance reports it somewhere
    stats = sess.cache_stats()
    reported = (stats["degraded_routes"] + stats["calib_degraded_analytic"]
                + stats["calib_disk_errors"])
    assert reported >= 1, (site, stats)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), site=st.sampled_from(_GRAPH_SITES))
    def test_any_single_site_fault_matches_fault_free_run(seed, site):
        _check_single_site_fault_preserves_outputs(seed, site)
else:
    @pytest.mark.parametrize("site", _GRAPH_SITES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_any_single_site_fault_matches_fault_free_run(seed, site):
        _check_single_site_fault_preserves_outputs(seed, site)
