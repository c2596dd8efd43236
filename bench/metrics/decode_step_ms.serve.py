"""decode_step_ms.serve: host time of the window's decode ticks (spans
around engine.step()) over their number."""


def read(run):
    ticks = run.window_ticks("decode")
    if not ticks:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in ticks) / len(ticks)
