"""device_idle.serve: 1 - (union of device-op intervals) / traced window
(device trace, %)."""


def read(run):
    if run.trace is None or run.kind != "serve":
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
