"""output_tokens_per_s: every output token emitted inside the window, of
finished and running requests alike, over the window (host clock)."""


def read(run):
    if run.kind != "serve" or run.window_s <= 0:
        return None
    n = sum(run.t_open <= t <= run.t_close
            for ts in run.token_times.values() for t in ts)
    return n / run.window_s
