"""``scripts/trace_spans.py``'s gap charging, held to the harness's own rule
(``harness.trace``) on hand-made spans and on the trace recorded on a TPU
v5e (``data/v5e_tiny.xplane.pb``)."""
import collections
import importlib.util
import os
import shutil

import pytest

from conftest import ROOT
from harness import trace
from repro.runtime import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


@pytest.fixture(scope="module")
def script():
    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(ROOT, "scripts", "trace_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _charge(script, gaps, spans):
    """Idle seconds by the innermost span open at each gap's middle."""
    out = collections.Counter()
    for (s, e), open_ in zip(gaps, script._open_at_middles(gaps, spans)):
        out[open_[-1][0] if open_ else trace.NO_SPAN] += (e - s) * 1e-9
    return out


def test_open_at_middles_lists_the_open_spans_outermost_first(script):
    spans = [("inner", 2 * MS, 4 * MS), ("outer", 0, 10 * MS),
             ("late", 8 * MS, 9 * MS)]
    gaps = [(1 * MS, 2 * MS), (2 * MS, 4 * MS), (3 * MS, 5 * MS),
            (5 * MS, 9 * MS), (10 * MS, 12 * MS)]
    names = [[sp[0] for sp in open_]
             for open_ in script._open_at_middles(gaps, spans)]
    # middles 1.5, 3, 4, 7 and 11 ms: a span that ends at a middle is closed
    assert names == [["outer"], ["outer", "inner"], ["outer"], ["outer"], []]


def test_gaps_match_the_harness_on_the_recorded_trace(script):
    tr = trace.Trace.load(os.path.join(DATA, "v5e_tiny.xplane.pb"))
    lo, hi = tr.spans[0][1] - 5 * MS, tr.spans[-1][2]
    gaps = script._gaps(tr.device_ops, lo, hi)
    assert gaps == sorted(gaps) and all(s < e for s, e in gaps)
    want = trace.reduce(tr, lo, hi).gaps
    assert _charge(script, gaps, tr.spans) == pytest.approx(want)
    # the same spans under the program's prefix are charged alike
    repro = [("repro." + n[len("bench."):], s, e) for n, s, e in tr.spans]
    got = _charge(script, gaps, repro)
    assert {k.replace("repro.", "bench."): v for k, v in got.items()} \
        == pytest.approx(want)


def test_reduce_splits_the_harness_idle_on_the_recorded_trace(script,
                                                              tmp_path):
    path = os.path.join(DATA, "v5e_tiny.xplane.pb")
    tr = trace.Trace.load(path)
    # the harness's spans as the host saw them (perf_counter seconds): the
    # trace's own clock, so the two are tied with no offset
    host = [(n, s * 1e-9, e * 1e-9) for n, s, e in tr.spans]
    t_open, t_close = host[0][1] - 5e-3, host[-1][2]
    run_dir = tmp_path / "plugins" / "profile" / "1"
    run_dir.mkdir(parents=True)
    shutil.copy(path, run_dir / "host.xplane.pb")
    want = trace.summarize(str(tmp_path), host, t_open, t_close)
    tracing.reset()
    out = script._reduce({"path": path, "host_spans": host,
                          "t_open": t_open, "t_close": t_close}, tracing)
    assert out["window_s"] == pytest.approx(want.window_s)
    # the recorded trace holds no program span: all of the idle is charged
    # to none, and by harness span it is the harness's own split
    assert out["repro_annotations"] == 0
    idle = want.window_s - want.busy_s
    assert dict(out["idle_by_repro_span"]) == pytest.approx(
        {"no_program_span": idle})
    by_bench = {k.split(" | ")[0]: v
                for k, v in out["idle_by_bench_and_repro_span"]}
    assert by_bench == pytest.approx(want.gaps)
    assert out["bench_decode_idle"] == {}
    assert out["gc"]["n"] == 0
