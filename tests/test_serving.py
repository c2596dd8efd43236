"""Serving engine: completion, continuous batching, greedy consistency."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.runtime import DegradationWarning
from repro.serving import InferenceEngine, Request, RequestState
from repro.serving.sampler import sample_token


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_engine_completes_all_requests(small_model):
    cfg, model, params = small_model
    engine = InferenceEngine(model, params, max_slots=2, max_len=64)
    for rid in range(5):
        engine.submit(Request(rid=rid, prompt=[1, 2, 3, 4 + rid], max_tokens=6))
    done = engine.run()
    assert len(done) == 5
    assert all(len(r.output) == 6 for r in done)


def test_engine_greedy_matches_manual_decode(small_model):
    """Engine output (batched slots) == manual prefill+decode loop."""
    cfg, model, params = small_model
    prompt = [5, 9, 2, 7, 1]
    max_tokens = 5

    engine = InferenceEngine(model, params, max_slots=2, max_len=64)
    engine.submit(Request(rid=0, prompt=prompt, max_tokens=max_tokens))
    # a second concurrent request exercises slot interference
    engine.submit(Request(rid=1, prompt=[3, 3, 3], max_tokens=max_tokens))
    done = {r.rid: r for r in engine.run()}

    # manual loop
    toks = jnp.asarray([prompt], jnp.int32)
    logits, caches, _ = model.prefill(params, {"tokens": toks},
                                   cache_len=64 + cfg.meta_tokens)
    out = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    while len(out) < max_tokens:
        logits, caches = model.decode(params, jnp.asarray([out[-1]], jnp.int32),
                                      caches, jnp.asarray([pos], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
        pos += 1
    assert done[0].output == out


def test_eos_terminates(small_model):
    cfg, model, params = small_model
    engine = InferenceEngine(model, params, max_slots=1, max_len=64)
    # probe: first greedy token becomes the eos so the request ends at len 1
    logits, _, _ = model.prefill(params, {"tokens": jnp.asarray([[1, 2, 3]], jnp.int32)},
                              cache_len=64 + cfg.meta_tokens)
    eos = int(jnp.argmax(logits[0]))
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_tokens=32, eos_id=eos))
    done = engine.run()
    assert len(done) == 1 and len(done[0].output) == 1


def test_engine_reschedule_hits_calibration_cache(small_model):
    """First engine profiles its step graph once; a second engine sharing
    the session (same model structure + batch geometry) and an in-place
    re-schedule both hydrate from the calibration cache — zero re-timing."""
    from repro.core import Session
    from conftest import count_measure_calls

    cfg, model, params = small_model
    sess = Session()
    with count_measure_calls() as timing:
        e1 = InferenceEngine(model, params, max_slots=2, max_len=32,
                             session=sess)
        p1 = e1.calibrate_schedule(n_layers=1)
        assert timing["n"] == 1 and p1 is e1.schedule_plan

        e2 = InferenceEngine(model, params, max_slots=2, max_len=32,
                             session=sess)
        p2 = e2.calibrate_schedule(n_layers=1)   # warm: cache-served
        p1b = e1.calibrate_schedule(n_layers=1)  # re-schedule: also warm
    assert timing["n"] == 1, "serving re-schedules must not re-time"
    assert p2.order == p1.order == p1b.order
    stats = sess.cache_stats()
    assert stats["calib_misses"] == 1 and stats["calib_hits"] == 2


def test_engine_without_session_uses_default(small_model):
    """Engines constructed without an explicit session share the process
    default (the legacy module-global behavior)."""
    from repro.core import default_session
    from conftest import count_measure_calls

    cfg, model, params = small_model
    with count_measure_calls() as timing:
        e1 = InferenceEngine(model, params, max_slots=2, max_len=32)
        e1.calibrate_schedule(n_layers=1)
        e2 = InferenceEngine(model, params, max_slots=2, max_len=32)
        e2.calibrate_schedule(n_layers=1)
    assert timing["n"] == 1
    assert default_session().cache_stats()["calib_hits"] >= 1


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "hymba-1.5b"])
def test_calibrate_schedule_measures_ssm_archs(arch):
    """rwkv/hybrid exports used to carry cost-only scan operators and forced
    calibrate_schedule down the measured→analytic rung; the traced-kernel
    exporter threads real payloads through those builders, so measured
    calibration now runs end to end with no degradation."""
    import warnings

    from repro.core import Session
    from repro.runtime import DegradationWarning

    cfg = get_config(arch, smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    sess = Session()
    engine = InferenceEngine(model, params, max_slots=2, max_len=32,
                             session=sess)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DegradationWarning)
        plan = engine.calibrate_schedule(n_layers=2)
    assert plan is engine.schedule_plan
    assert plan.n_streams >= 1
    scan = ".wkv_scan" if arch.startswith("rwkv") else ".mamba_scan"
    assert any(n.name.endswith(scan) for n in plan.graph)
    stats = sess.cache_stats()
    assert stats["calib_degraded_analytic"] == 0
    assert stats["calib_misses"] == 1           # measurement really ran
    assert plan.graph.calibration_fp is not None


def test_calibrate_schedule_works_on_routed_moe():
    """MoE engines export the routed (ragged) fan-out with real
    dispatch/combine payloads, so measured calibration — previously
    impossible for MoE — now runs end to end."""
    from repro.core import Session

    cfg = get_config("kimi-k2-1t-a32b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    engine = InferenceEngine(model, params, max_slots=2, max_len=32,
                             session=Session())
    plan = engine.calibrate_schedule(n_layers=2)
    assert plan is engine.schedule_plan
    assert any(".dispatch" in n.name for n in plan.graph)
    assert all(n.cost.measured_us is not None
               for n in plan.graph if n.fn is not None)


def test_sampler_modes():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    assert int(sample_token(logits, jax.random.key(0))[0]) == 1  # greedy
    t = sample_token(logits, jax.random.key(0), temperature=1.0, top_k=2)
    assert int(t[0]) in (1, 2)
    t = sample_token(logits, jax.random.key(0), temperature=1.0, top_p=0.5)
    assert int(t[0]) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batch_sampler_draws_what_per_slot_sampling_draws(dtype):
    """The engine's one jitted sampler over the batch gives each row the
    token ``sample_token`` gives that row alone with ``fold_in(sub, i)``
    and its temperature, and flags the rows that are not finite."""
    from repro.serving.engine import _batch_sampler

    logits = jax.random.normal(jax.random.key(3), (5, 97)).astype(dtype)
    logits = logits.at[3, 7].set(jnp.nan)
    temps = np.asarray([0.0, 0.7, 1.3, 0.0, 2.0], np.float32)
    sample = _batch_sampler()
    for seed in range(4):
        rng = jax.random.key(seed)
        want_rng, sub = jax.random.split(rng)
        want = [int(sample_token(logits[i:i + 1],
                                 jax.random.fold_in(sub, i),
                                 float(temps[i]))[0]) for i in range(5)]
        got_rng, tokens, finite = sample(rng, logits, temps, True)
        assert (jax.random.key_data(got_rng)
                == jax.random.key_data(want_rng)).all()
        assert tokens.tolist() == want
        assert finite.tolist() == [True, True, True, False, True]
    rng = jax.random.key(0)
    _, greedy, _ = sample(rng, logits, np.zeros(5, np.float32), False)
    assert greedy.tolist() == jnp.argmax(logits, axis=-1).tolist()


# =========================================================================
# Paged KV cache (tentpole): differential vs the dense slab
# =========================================================================

def _terminal_map(done):
    return {r.rid: (r.state, tuple(r.output)) for r in done}


def test_paged_engine_matches_dense_on_overload_trace(small_model):
    """Byte-identical token streams: same trace, same admission policy, same
    seed — the paged engine must emit exactly what the dense engine does,
    through preemptions, sheds and expiries."""
    from benchmarks.bench_serving import _drive, build_trace
    from repro.serving import AdmissionConfig

    cfg, model, params = small_model
    trace = build_trace(n=12, seed=7)

    def run(paged):
        engine = InferenceEngine(
            model, params, max_slots=2, max_len=64, seed=3,
            admission=AdmissionConfig(policy="edf", preemption=True),
            paged_kv=paged, page_size=16)
        done = _drive(engine, trace)
        return engine, _terminal_map(done)

    dense_engine, dense = run(False)
    paged_engine, paged = run(True)
    assert paged_engine.paged
    assert paged == dense
    # every page returned to the pool once the trace drained
    assert paged_engine.pool.used_pages == 0
    assert paged_engine.health()["paged"]["holders"] == 0


def test_paged_resume_skips_reprefill(small_model):
    """A preempted paged request keeps its pages and resumes without
    re-prefilling; the dense engine re-runs the whole prefix."""
    from repro.serving import AdmissionConfig

    cfg, model, params = small_model

    def run(paged):
        engine = InferenceEngine(
            model, params, max_slots=1, max_len=32, seed=5,
            admission=AdmissionConfig(policy="edf", preemption=True),
            paged_kv=paged, page_size=4)
        low = Request(rid="low", prompt=[5, 6, 7], max_tokens=12, priority=0)
        engine.submit(low)
        for _ in range(4):
            engine.step()
        engine.submit(Request(rid="hi", prompt=[9, 9], max_tokens=3,
                              priority=3, ttl=4))
        done = engine.run(200)
        return engine, _terminal_map(done)

    dense_engine, dense = run(False)
    paged_engine, paged = run(True)
    assert paged == dense
    assert dense_engine.fault_stats["preemptions"] == 1
    assert paged_engine.fault_stats["preemptions"] == 1
    # dense pays a full re-prefill of prompt+output on resume; paged resumes
    # from its retained pages
    assert dense_engine.fault_stats["reprefilled_tokens"] > 0
    assert paged_engine.fault_stats["reprefilled_tokens"] == 0
    assert paged_engine.fault_stats["page_resumes"] == 1
    assert paged_engine.fault_stats["resumed_tokens"] > 0
    assert paged_engine.pool.used_pages == 0


def test_page_exhaustion_feeds_admission(small_model):
    """An undersized pool sheds/requeues instead of corrupting state: every
    request goes terminal and the pool drains."""
    from repro.serving import TERMINAL_STATES

    cfg, model, params = small_model
    engine = InferenceEngine(model, params, max_slots=3, max_len=32, seed=2,
                             paged_kv=True, page_size=4, num_pages=6)
    reqs = [Request(rid=f"r{i}", prompt=[7, 8, 9, 1, 2], max_tokens=10)
            for i in range(4)]
    for r in reqs:
        engine.submit(r)
    done = engine.run(300)
    assert len(done) == 4
    assert all(r.state in TERMINAL_STATES for r in reqs)
    assert sum(r.state is RequestState.DONE for r in reqs) >= 1
    assert engine.fault_stats["page_exhaustions"] > 0
    assert engine.pool.used_pages == 0


def test_prefix_sharing_cow_is_transparent(small_model):
    """Two requests with the same prompt share prefix pages; COW keeps the
    token streams identical to the unshared run."""
    cfg, model, params = small_model
    prompt = [3, 1, 4, 1, 5, 9]

    def run(sharing):
        engine = InferenceEngine(model, params, max_slots=2, max_len=32,
                                 seed=11, paged_kv=True, page_size=4,
                                 prefix_sharing=sharing)
        engine.submit(Request(rid="a", prompt=list(prompt), max_tokens=6))
        engine.submit(Request(rid="b", prompt=list(prompt), max_tokens=6))
        done = engine.run(200)
        return engine, _terminal_map(done)

    plain_engine, plain = run(False)
    shared_engine, shared = run(True)
    assert shared == plain
    assert plain_engine.pool.stats["shared_hits"] == 0
    assert shared_engine.pool.stats["shared_hits"] > 0
    # the shared partial page is copied before either writer extends it
    assert shared_engine.pool.stats["cow_copies"] >= 1
    assert shared_engine.pool.used_pages == 0


def test_block_table_fault_lands_on_dense_gather_rung(small_model):
    """An injected block-table fault degrades the tick to the dense-gather
    rung — same outputs as the fault-free run, provenance recorded."""
    from repro.runtime.faults import FaultPlan

    cfg, model, params = small_model

    def run(spec):
        plan = FaultPlan.parse(spec) if spec else None
        engine = InferenceEngine(model, params, max_slots=2, max_len=32,
                                 seed=3, paged_kv=True, page_size=4,
                                 fault_plan=plan)
        for i in range(3):
            engine.submit(Request(rid=f"r{i}", prompt=[4, 5, 6, 7],
                                  max_tokens=5))
        done = engine.run(200)
        return engine, _terminal_map(done)

    clean_engine, clean = run(None)
    with pytest.warns(DegradationWarning, match="dense-gather"):
        faulty_engine, faulty = run("block_table_build:raise:1")
    assert faulty == clean
    assert all(s[0] is RequestState.DONE for s in faulty.values())
    assert faulty_engine.fault_stats["block_table_faults"] == 1
    assert faulty_engine.fault_stats["paged_decode_fallbacks"] == 1


def test_page_release_fault_leaks_with_provenance(small_model):
    """A failed release leaks the pages (counted, capacity lost) instead of
    double-freeing or corrupting the free list."""
    from repro.runtime.faults import FaultPlan

    cfg, model, params = small_model
    engine = InferenceEngine(model, params, max_slots=2, max_len=32, seed=3,
                             paged_kv=True, page_size=4,
                             fault_plan=FaultPlan.parse("page_release:raise:1"))
    for i in range(3):
        engine.submit(Request(rid=f"r{i}", prompt=[4, 5, 6, 7], max_tokens=5))
    done = engine.run(200)
    assert all(r.state is RequestState.DONE for r in done)
    assert engine.fault_stats["page_release_faults"] == 1
    leaked = engine.pool.stats["leaked_pages"]
    assert leaked > 0
    assert engine.pool.used_pages == leaked        # resident but unheld


def test_paged_matches_dense_on_mla_moe_smoke():
    """The MLA latent-page path (DeepSeek-style) emits the same streams as
    the dense engine."""
    cfg = get_config("deepseek-v3-671b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))

    def run(paged):
        engine = InferenceEngine(model, params, max_slots=2, max_len=32,
                                 seed=9, paged_kv=paged, page_size=4)
        engine.submit(Request(rid="a", prompt=[3, 17, 42, 9], max_tokens=5))
        engine.submit(Request(rid="b", prompt=[11, 2], max_tokens=5))
        return _terminal_map(engine.run(200))

    assert run(True) == run(False)


def test_paged_kv_bytes_beat_dense_when_overcommitted(small_model):
    """Sizing the pool below slot capacity parity is the memory win: the
    paged cache is strictly smaller at equal max_slots."""
    cfg, model, params = small_model
    dense = InferenceEngine(model, params, max_slots=4, max_len=64)
    pages_per_req = -(-(64 + cfg.meta_tokens) // 16)
    paged = InferenceEngine(model, params, max_slots=4, max_len=64,
                            paged_kv=True, page_size=16,
                            num_pages=1 + 2 * pages_per_req)
    assert paged.kv_cache_bytes() < dense.kv_cache_bytes()
    assert dense.health()["paged"] is None
    assert paged.health()["paged"]["free_pages"] == 2 * pages_per_req


def test_paged_unsupported_family_degrades_to_dense():
    """A recurrent-state family cannot page; the engine says so once and
    serves on the dense slab."""
    cfg = get_config("rwkv6-1.6b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    with pytest.warns(DegradationWarning, match="paged_kv unavailable"):
        engine = InferenceEngine(model, params, max_slots=1, max_len=32,
                                 paged_kv=True)
    assert not engine.paged
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_tokens=3))
    done = engine.run(100)
    assert done[0].state is RequestState.DONE


def test_serve_paged_returns_fallback_ledger():
    """``serve(paged_kv=True)`` runs the paged engine and returns what a
    caller needs to tell a clean run from a degraded one: the engine's
    fault counters and the recorded fallbacks."""
    from repro.launch.serve import serve

    res = serve("qwen2-0.5b", n_requests=3, max_tokens=4, slots=2,
                paged_kv=True)
    assert res["completed"] == 3 and res["total_tokens"] == 12
    assert res["fault_stats"]["paged_decode_fallbacks"] == 0
    assert res["fault_stats"]["failed_requests"] == 0
    assert res["degradations"] == []


# tiny MHA, GQA and MLA stacks: the page pool layouts the engine serves.
# The MLA stack takes dense FFNs: a dense engine's re-prefill on resume
# routes an MoE layer's tokens under another capacity than decode did.
POOL_LAYOUTS = [("minicpm-2b", 4, False), ("qwen2-0.5b", 4, False),
                ("deepseek-v3-671b", 4, False), ("llama3.2-1b", 128, True)]


@pytest.fixture(scope="module", params=POOL_LAYOUTS,
                ids=lambda p: f"{p[0]}-ps{p[1]}" + ("-kernels" if p[2] else ""))
def pool_model(request):
    import dataclasses

    arch, page_size, kernels = request.param
    cfg = get_config(arch, smoke=True)
    if cfg.mla is not None:
        cfg = dataclasses.replace(cfg, family="dense", moe=None)
    params = make_model(cfg).init(jax.random.key(0))
    return make_model(cfg, use_kernels=kernels), params, page_size


def _admit_cow_preempt_resume(model, params, paged, page_size, plan=None):
    """Two requests on one prompt (prefix pages shared, copied on write),
    then a deadline-critical one that preempts the lower-priority of them,
    which resumes from its pages."""
    from repro.runtime.faults import FaultPlan
    from repro.serving import AdmissionConfig

    engine = InferenceEngine(
        model, params, max_slots=2, max_len=40, seed=5,
        admission=AdmissionConfig(policy="edf", preemption=True),
        paged_kv=paged, page_size=page_size, prefix_sharing=paged,
        num_pages=1 + 3 * -(-40 // page_size),   # a preempted slot's too
        fault_plan=FaultPlan.parse(plan) if plan else None)
    prompt = [3, 1, 4, 1, 5, 9, 2]
    engine.submit(Request(rid="a", prompt=list(prompt), max_tokens=10,
                          priority=1))
    engine.submit(Request(rid="b", prompt=list(prompt), max_tokens=10))
    for _ in range(5):
        engine.step()
    engine.submit(Request(rid="hi", prompt=[9, 9, 2], max_tokens=3,
                          priority=3, ttl=8))
    done = engine.run(200)
    return engine, _terminal_map(done)


def test_paged_pool_serves_the_dense_tokens(pool_model):
    """Across admissions, a copy-on-write page and a preemption's resume,
    the paged engine serves the dense engine's greedy tokens, and every
    decode tick hands its donated pools back in place."""
    from repro.runtime import tracing

    model, params, page_size = pool_model
    _, dense = _admit_cow_preempt_resume(model, params, False, page_size)
    t0 = time.perf_counter_ns()
    before = tracing.counters().get("engine.decode.pool_in_place", 0)
    engine, paged = _admit_cow_preempt_resume(model, params, True, page_size)
    assert paged == dense
    assert all(s[0] is RequestState.DONE for s in paged.values())
    assert engine.fault_stats["page_resumes"] == 1
    assert engine.fault_stats["paged_decode_fallbacks"] == 0
    if page_size < 40:                   # a partial shared page to copy
        assert engine.pool.stats["cow_copies"] >= 1
    ticks = [s for s in tracing.spans(since_ns=t0) if s.name == "engine.step"
             and s.attrs.get("kind") == "decode"]
    assert ticks and all(s.attrs["pool_in_place"] for s in ticks)
    counted = tracing.counters()["engine.decode.pool_in_place"] - before
    assert counted == len(ticks)


def test_decode_step_fault_recovers_through_dense_gather(pool_model):
    """An injected fault after the donating step has run: the engine holds
    the pools the step handed back, and the dense-gather rung serves the
    same tokens from them."""
    model, params, page_size = pool_model
    _, clean = _admit_cow_preempt_resume(model, params, True, page_size)
    with pytest.warns(DegradationWarning, match="dense-gather"):
        engine, faulty = _admit_cow_preempt_resume(
            model, params, True, page_size, plan="decode_step:raise:2")
    assert faulty == clean
    assert engine.fault_stats["paged_decode_fallbacks"] == 2
    assert engine.fault_stats["block_table_faults"] == 2
