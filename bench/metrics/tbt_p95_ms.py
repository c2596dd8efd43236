"""tbt_p95_ms: 95th percentile of every gap between consecutive output
tokens of every request, both tokens inside the window (host clock)."""
import numpy as np


def read(run):
    if run.kind != "serve":
        return None
    gaps = []
    for ts in run.token_times.values():
        w = [t for t in ts if run.t_open <= t <= run.t_close]
        gaps += [b - a for a, b in zip(w, w[1:])]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
