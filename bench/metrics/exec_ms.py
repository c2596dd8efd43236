"""exec_ms: the whole window over the captured-forward calls completed in
it (host clock; one caller, each call ended by block_until_ready)."""


def read(run):
    if run.kind != "graph" or not run.calls:
        return None
    return run.window_s / run.calls * 1e3
