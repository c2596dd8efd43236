"""pool_in_place.serve: share of the window's paged decode ticks whose
jitted step handed its page pools back in the donated input's buffers,
from the ``pool_in_place`` attribute of each ``engine.step`` span of kind
decode (program spans, %).  A program that does not record the attribute
gives None."""
from harness.program_spans import window_spans


def read(run):
    spans = window_spans(run) if run.kind == "serve" else None
    flags = [bool(s.attrs["pool_in_place"]) for s in spans or ()
             if s.name == "engine.step" and s.attrs.get("kind") == "decode"
             and "pool_in_place" in s.attrs]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
