"""What a run records on the host: spans around the calls into the
program, compilations, and the record that metric readers read."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any

import jax

SPAN_PREFIX = "bench."


class Spans:
    """Host spans, kept in memory.  With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation``, so the trace shows what the host was
    doing while the device sat idle."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        name = SPAN_PREFIX + name
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.items.append((name, t0, time.perf_counter()))


class Compiles:
    """Counts JAX's compilation events: programs compiled by the backend,
    programs loaded from the persistent cache instead, and jaxprs traced.
    JAX records a backend-compile event for every request, loads
    included, so ``compiled`` is requests less loads."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "requests",
        "/jax/compilation_cache/cache_hits": "loaded",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self):
        self.n = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.EVENTS:
            self.n[self.EVENTS[name]] += 1

    def _duration(self, name, _secs, **_):
        self._event(name)

    def snapshot(self) -> dict[str, int]:
        return {"compiled": self.n["requests"] - self.n["loaded"],
                "loaded": self.n["loaded"], "traces": self.n["traces"]}

    @staticmethod
    def since(before: dict, after: dict) -> dict[str, int]:
        return {k: after[k] - before[k] for k in after}


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read.  Readers live in
    ``metrics/<name>.py`` and return a number, or None where the run has
    nothing for them."""
    kind: str                                 # "graph" | "serve"
    arch: Any                                 # architectures/<name>.py
    dims: Any
    traffic: dict
    peak: dict
    traced: bool
    setup_s: float = 0.0
    window_s: float = 0.0
    spans: Spans | None = None
    # graph cells
    calls: int = 0
    flops_per_call: float = 0.0
    branch_gemm_steps: list = dataclasses.field(default_factory=list)
    sequential_ms: float | None = None
    opara_ms: float | None = None
    t_open: float = 0.0                       # host clock of the window
    t_close: float = 0.0
    # serve cells: host times of every output token, by request; every
    # engine tick as (kind, start, end, what): "admit" with the prompt
    # length, "decode" with the context length of each active slot
    token_times: dict = dataclasses.field(default_factory=dict)
    ticks: list = dataclasses.field(default_factory=list)
    # traced runs
    trace: Any = None

    def window_ticks(self, kind: str) -> list:
        """Ticks of ``kind`` that ran inside the window."""
        return [tk for tk in self.ticks if tk[0] == kind
                and self.t_open <= tk[1] and tk[2] <= self.t_close]
