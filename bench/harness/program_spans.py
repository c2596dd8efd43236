"""The program's own spans (``repro.runtime.tracing``) inside a run's
window, for the metric readers that read them.

The spans are on the host's ``perf_counter`` clock, the clock of
``run.t_open`` and ``run.t_close``.  A program without the tracer, a
tracer turned off, or a ring that dropped spans newer than the window's
opening gives None: the readers then report nothing."""


def window_spans(run):
    """Spans that started and ended inside [run.t_open, run.t_close], in
    the order they ended; None where the program cannot say."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    if run.window_s <= 0 or not tracing.enabled():
        return None
    lo, hi = int(run.t_open * 1e9), int(run.t_close * 1e9)
    if tracing.dropped(since_ns=lo):
        return None
    return tracing.spans(since_ns=lo, until_ns=hi)


def gc_share(run, kind: str):
    """Share of the window spent in Python's garbage collector (%)."""
    spans = window_spans(run) if run.kind == kind else None
    if spans is None:
        return None
    pause = sum(s.t1 - s.t0 for s in spans if s.name == "python.gc")
    return 100.0 * pause * 1e-9 / run.window_s
