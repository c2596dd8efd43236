"""admit_traces.serve: jaxpr traces per admission in the window, from the
``traces`` attribute of each ``engine.admit.prefill`` span (the eager
prefill traces its scan again on every admission; program spans, count)."""
from harness.program_spans import window_spans


def read(run):
    spans = window_spans(run) if run.kind == "serve" else None
    traces = [s.attrs["traces"] for s in spans or ()
              if s.name == "engine.admit.prefill" and "traces" in s.attrs]
    if not traces:
        return None
    return sum(traces) / len(traces)
