"""gc_pause.serve: time in Python's garbage collector (``python.gc``
spans) inside the window, over the window (program spans, host clock, %)."""
from harness.program_spans import gc_share


def read(run):
    return gc_share(run, "serve")
