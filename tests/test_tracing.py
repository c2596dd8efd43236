"""The in-process tracer: the ring, parent links, drops, off mode, and the
spans the serving engine, the captured executable and ``Session.compile``
record where their work happens."""
import collections
import gc
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import build_inception_like
from repro.configs import get_config
from repro.core import Session
from repro.models import make_model
from repro.runtime import tracing
from repro.serving import InferenceEngine, Request

DECODE_CHILDREN = ["engine.decode.prepare", "engine.decode.dispatch",
                   "engine.decode.wait", "engine.decode.sample"]


@pytest.fixture(autouse=True)
def _tracer_on():
    tracing.enable(True)
    tracing.reset()
    yield
    tracing.enable(True)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3.2-1b", smoke=True)
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _scopes(lowered) -> set[str]:
    """Every name-stack component in a lowered program's locations."""
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return {part for n in names for part in n.split("/")}


def _children(spans, parent):
    """Child spans of ``parent``, less any garbage collection inside it."""
    return [s for s in spans
            if s.parent == parent.id and s.name != "python.gc"]


def test_spans_link_parents_and_keep_attributes():
    t0 = time.perf_counter_ns()
    with tracing.span("outer", rid=7) as outer:
        with tracing.span("inner") as inner:
            inner.set(kind="decode")
        tracing.record("waited", t0, t0 + 5, rid=8)
    tracing.count("things", 3)
    tracing.count("things")
    got = {s.name: s for s in tracing.spans(since_ns=t0)}
    assert set(got) >= {"outer", "inner", "waited"}
    assert got["outer"].parent is None and got["outer"].attrs == {"rid": 7}
    assert got["inner"].parent == outer.id
    assert got["inner"].attrs == {"kind": "decode"}
    assert got["waited"].parent == outer.id and got["waited"].t1 == t0 + 5
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert tracing.counters()["things"] == 4
    assert not tracing.spans(since_ns=outer.t1 + 1)
    assert not tracing.spans(until_ns=t0 - 1)


def test_ring_drops_oldest_and_counts_the_drops(monkeypatch):
    monkeypatch.setattr(tracing, "RING", 4)
    monkeypatch.setattr(tracing, "_ring", collections.deque(maxlen=4))
    gc.disable()
    try:
        for i in range(6):
            tracing.record(f"s{i}", 10 * i, 10 * i + 1)
    finally:
        gc.enable()
    assert [s.name for s in tracing.spans()] == ["s2", "s3", "s4", "s5"]
    assert tracing.dropped() == 2
    assert tracing.dropped(since_ns=11) == 2     # s1 ended at 11
    assert tracing.dropped(since_ns=12) == 0     # no dropped span this new
    tracing.reset()
    assert tracing.dropped() == 0 and not tracing.spans()


def test_off_records_nothing_and_shares_one_no_op():
    tracing.enable(False)
    assert not tracing.enabled()
    a, b = tracing.span("x", rid=1), tracing.span("y")
    assert a is b
    with a as sp:
        sp.set(kind="decode")
    tracing.record("z", 0, 1)
    tracing.count("c")
    gc.collect()
    sess = Session()
    model = sess.compile(build_inception_like(n_blocks=2, width=3))
    model({"x": jnp.ones((8, 64), jnp.float32)})
    assert not tracing.spans() and not tracing.counters()
    # the stage spans time themselves with the tracer off too
    ms = model.explain()["stages_ms"]
    assert ms["total"] > 0.0
    assert ms["total"] >= ms["plan"] + ms["compile"] > 0.0
    assert ms["calibrate"] == 0.0                  # not run: no inputs


def test_finished_spans_are_left_out_of_the_collector():
    for i in range(100):
        with tracing.span("s", i=i, kind="decode"):
            pass
        tracing.record("r", 0, 1, rid=i)
    gc.collect()
    kept = [r for r in tracing._ring if r[1] in ("s", "r")]
    assert len(kept) == 200
    # the collection's own span is newer than the pass that untracked these
    assert not any(gc.is_tracked(r) for r in kept)
    assert [s.attrs for s in tracing.spans() if s.name == "s"][-1] == {
        "i": 99, "kind": "decode"}


def test_engine_with_the_tracer_off_stamps_and_records_nothing(small_model):
    cfg, model, params = small_model
    tracing.enable(False)
    engine = InferenceEngine(model, params, max_slots=1, max_len=64,
                             paged_kv=True, page_size=4)
    reqs = [Request(rid=rid, prompt=[1, 2, 3], max_tokens=3)
            for rid in range(2)]
    for req in reqs:
        engine.submit(req)
    assert len(engine.run()) == 2
    assert all(r.queued_ns is None for r in reqs)
    assert not tracing.spans() and not tracing.counters()


@pytest.mark.parametrize("paged", [True, False])
def test_decode_tick_spans_and_queue_waits(small_model, paged):
    cfg, model, params = small_model
    kw = dict(paged_kv=True, page_size=4) if paged else {}
    engine = InferenceEngine(model, params, max_slots=2, max_len=64, **kw)
    t0 = time.perf_counter_ns()
    reqs = [Request(rid=rid, prompt=[1, 2, 3, 4 + rid], max_tokens=4)
            for rid in range(3)]
    for req in reqs:
        engine.submit(req)
    assert len(engine.run()) == 3
    spans = tracing.spans(since_ns=t0)
    steps = [s for s in spans if s.name == "engine.step"]
    decode = [s for s in steps if s.attrs["kind"] == "decode"]
    assert decode and all(s.attrs["active"] >= 1 for s in decode)
    if paged:
        assert all(s.attrs["used_pages"] + s.attrs["free_pages"]
                   == engine.pool.config.num_pages - 1 for s in steps)
    for step in decode:
        kids = _children(spans, step)
        assert [k.name for k in kids] == DECODE_CHILDREN
        assert all(step.t0 <= k.t0 <= k.t1 <= step.t1 for k in kids)
        assert sum(k.t1 - k.t0 for k in kids) <= step.t1 - step.t0
    queued = [s for s in spans if s.name == "engine.queued"]
    assert sorted(s.attrs["rid"] for s in queued) == [0, 1, 2]
    assert all(s.t0 <= s.t1 for s in queued)
    # each wait starts at the stamp the request took on entering the queue
    assert {s.attrs["rid"]: s.t0 for s in queued} == {
        r.rid: r.queued_ns for r in reqs}


def test_admission_spans_count_the_prefill_traces(small_model):
    cfg, model, params = small_model
    engine = InferenceEngine(model, params, max_slots=1, max_len=64,
                             paged_kv=True, page_size=4)
    t0 = time.perf_counter_ns()
    for rid in range(2):
        engine.submit(Request(rid=rid, prompt=[3, 1, 4, 1, 5], max_tokens=2))
    engine.run()
    spans = tracing.spans(since_ns=t0)
    admits = [s for s in spans if s.name == "engine.step"
              and s.attrs["kind"] == "admit"]
    assert len(admits) == 2
    for step in admits:
        kids = {k.name: k for k in _children(spans, step)}
        assert {"engine.admit.prefill", "engine.admit.sample"} <= set(kids)
        pre = kids["engine.admit.prefill"]
        assert pre.attrs["tokens"] == 5
        # the eager prefill traces its scan again on every admission
        assert pre.attrs["traces"] >= 1
        assert {"compiles", "loads"} <= set(pre.attrs)
    c = tracing.compiles()
    assert c["traces"] >= 2 and c["compiles"] + c["loads"] >= 0


def test_captured_call_and_session_compile_spans():
    g = build_inception_like(n_blocks=2, width=3)
    t0 = time.perf_counter_ns()
    model = Session().compile(g, inputs={n.op_id: jnp.ones((8, 64))
                                         for n in g if n.fn is None})
    for _ in range(2):
        jax.block_until_ready(model({"x": jnp.ones((8, 64), jnp.float32)}))
    spans = tracing.spans(since_ns=t0)
    by = collections.defaultdict(list)
    for s in spans:
        by[s.name].append(s)
    (top,) = by["session.compile"]
    stages = {s.name: s for s in _children(spans, top)}
    assert set(stages) == {"session.calibrate", "session.plan",
                           "session.capture"}
    ms = model.explain()["stages_ms"]
    assert ms["total"] == top.ms
    assert ms["calibrate"] == stages["session.calibrate"].ms
    assert ms["plan"] == stages["session.plan"].ms
    assert ms["compile"] == stages["session.capture"].ms
    assert len(by["capture.call"]) == 2
    for call in by["capture.call"]:
        assert [k.name for k in _children(spans, call)] == [
            "capture.bind", "capture.dispatch"]


def test_gc_collections_are_spans():
    t0 = time.perf_counter_ns()
    gc.collect()
    got = [s for s in tracing.spans(since_ns=t0) if s.name == "python.gc"]
    assert any(s.attrs["generation"] == 2 for s in got)
    assert all("collected" in s.attrs for s in got)


def test_named_scopes_reach_the_lowered_programs(small_model):
    cfg, model, params = small_model
    # the Pallas route (page size a lane multiple) reads the pool in place
    engine = InferenceEngine(make_model(cfg, use_kernels=True), params,
                             max_slots=2, max_len=64, paged_kv=True,
                             page_size=128)
    z = jnp.zeros((2,), jnp.int32)
    bt = jnp.zeros((2, engine._pages_per_req), jnp.int32)
    scopes = _scopes(engine._paged_decode.lower(
        params, engine.caches, z, bt, z))
    assert {"pool_write", "attention", "mlp"} <= scopes
    assert "kv_layout" not in scopes        # the pool is in the kernel's layout
    exe = Session().compile(build_inception_like(n_blocks=2, width=3))
    routes = {s.route for s in exe.executable.steps}
    assert routes and routes <= _scopes(
        jax.jit(exe.executable.fn).lower(jnp.ones((8, 64))))


def test_profiler_host_plane_holds_the_engine_spans(small_model, tmp_path):
    from jax.profiler import ProfileData

    cfg, model, params = small_model
    engine = InferenceEngine(model, params, max_slots=1, max_len=64,
                             paged_kv=True, page_size=4)
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_tokens=3))
    engine.step()                           # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        engine.step()
        engine.step()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = collections.Counter(
        ev.name for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU" for line in plane.lines
        for ev in line.events if ev.name.startswith("repro."))
    assert names["repro.engine.step"] == 2
    assert names["repro.engine.decode.wait"] == 2
