"""dispatch_ms.graph: mean duration of the window's ``capture.call``
spans: binding the inputs and handing the captured program to the device,
host time during which a closed-loop caller's chip waits (program spans,
host clock, ms)."""
from harness.program_spans import window_spans


def read(run):
    spans = window_spans(run) if run.kind == "graph" else None
    calls = [s.t1 - s.t0 for s in spans or () if s.name == "capture.call"]
    if not calls:
        return None
    return 1e-6 * sum(calls) / len(calls)
