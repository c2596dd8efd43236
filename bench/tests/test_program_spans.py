"""The readers of the program's own spans, on a hand-made run: only spans
inside the window count, and a ring that dropped spans newer than the
window's opening, a tracer turned off or a program without one give
nothing."""
import collections
import sys
import time

import pytest

from conftest import ROOT
from harness.main import load_reader
from harness.record import Run
from repro.runtime import tracing

READERS = {"decode_host_ms.serve": "serve", "admit_traces.serve": "serve",
           "gc_pause.serve": "serve", "dispatch_ms.graph": "graph",
           "gc_pause.graph": "graph"}
MS = 1_000_000


def _ticks():
    """One decode tick (a step and its wait), one admission, one captured
    call and one collection, all ended by now and begun at least 2 ms
    after this is called; returns the step's and the wait's durations
    (ns)."""
    with tracing.span("engine.step", kind="decode") as step:
        time.sleep(0.002)
        t = time.perf_counter_ns()
        tracing.record("engine.decode.wait", t - MS, t)
        tracing.record("engine.decode.sample", t - MS // 2, t)
        time.sleep(0.002)
    with tracing.span("engine.step", kind="admit"):
        t = time.perf_counter_ns()
        tracing.record("engine.admit.prefill", t - 1, t, traces=3,
                       compiles=0, loads=1)
    t = time.perf_counter_ns()
    tracing.record("capture.call", t - 2 * MS, t)
    tracing.record("python.gc", t - 3 * MS, t)
    return step.t1 - step.t0, MS


def _run(kind, t_open, t_close):
    return Run(kind=kind, arch=None, dims=None, traffic={}, peak=None,
               traced=True, t_open=t_open, t_close=t_close,
               window_s=t_close - t_open)


@pytest.fixture
def fresh():
    tracing.enable(True)
    tracing.reset()
    yield
    tracing.enable(True)
    tracing.reset()


def _read_all(t_open, t_close):
    return {name: load_reader(ROOT, name)(_run(kind, t_open, t_close))
            for name, kind in READERS.items()}


def test_readers_keep_only_the_window(fresh):
    _ticks()                                   # before the window: ignored
    tracing.record("engine.admit.prefill", 0, 1, traces=50)
    t_open = time.perf_counter()
    step_ns, wait_ns = _ticks()
    step2_ns, _ = _ticks()
    t_close = time.perf_counter()
    got = _read_all(t_open, t_close)
    _ticks()                                   # after the window: ignored
    assert got == _read_all(t_open, t_close)
    window = t_close - t_open
    assert got["decode_host_ms.serve"] == pytest.approx(
        (step_ns + step2_ns - 2 * wait_ns) / 2 / MS)
    assert got["admit_traces.serve"] == 3
    assert got["dispatch_ms.graph"] == pytest.approx(2.0)
    for name in ("gc_pause.serve", "gc_pause.graph"):
        # the two recorded collections, and any real one in the window
        assert got[name] >= 100 * 6e-3 / window


def test_dropped_spans_or_an_idle_tracer_give_nothing(fresh, monkeypatch):
    t_open = time.perf_counter()
    _ticks()
    t_close = time.perf_counter()
    tracing.enable(False)
    assert set(_read_all(t_open, t_close).values()) == {None}
    tracing.enable(True)
    monkeypatch.setattr(tracing, "RING", 3)
    monkeypatch.setattr(tracing, "_ring", collections.deque(
        tracing._ring, maxlen=3))
    _ticks()
    assert tracing.dropped(since_ns=int(t_open * 1e9))
    assert set(_read_all(t_open, t_close).values()) == {None}


def test_a_program_without_the_tracer_gives_nothing(fresh, monkeypatch):
    import repro.runtime

    t_open = time.perf_counter()
    _ticks()
    t_close = time.perf_counter()
    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    assert set(_read_all(t_open, t_close).values()) == {None}
