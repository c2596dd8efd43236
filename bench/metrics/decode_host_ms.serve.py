"""decode_host_ms.serve: host time per decode tick during which the chip
waits: each of the window's ``engine.step`` spans of kind decode less its
``engine.decode.wait`` child (the logits read, the tick's one wait on the
device), averaged over those ticks (program spans, host clock, ms)."""
from harness.program_spans import window_spans


def read(run):
    spans = window_spans(run) if run.kind == "serve" else None
    if spans is None:
        return None
    ticks = {s.id: s.t1 - s.t0 for s in spans if s.name == "engine.step"
             and s.attrs.get("kind") == "decode"}
    if not ticks:
        return None
    for s in spans:
        if s.name == "engine.decode.wait" and s.parent in ticks:
            ticks[s.parent] -= s.t1 - s.t0
    return 1e-6 * sum(ticks.values()) / len(ticks)
