"""In-process spans and counters on the host's ``perf_counter`` clock.

The program's own record of where host time goes, beside ``guard.py``'s
process-wide :func:`~repro.runtime.guard.kernel_log`::

    from repro.runtime import tracing

    with tracing.span("engine.step", kind="decode") as sp:
        ...
        sp.set(active=2)
    tracing.spans(since_ns=t0)      # finished spans, oldest first

* A span records its name, start and end (``time.perf_counter_ns()``), the
  innermost span open on its thread when it started (its parent) and a few
  small attributes.  Finished spans go into one bounded ring of
  :data:`RING` entries; the oldest are dropped first and counted
  (:func:`dropped`).  The ring keeps each as one flat tuple of atomic
  values, which Python's garbage collector stops tracking at the first
  collection it survives, so a full ring adds nothing to the collector's
  walks.
* While ``jax.profiler`` traces, each span is also a
  ``jax.profiler.TraceAnnotation`` named ``"repro." + name``, so it lands on
  the trace's host plane on the device trace's clock.
* Python's garbage collections are recorded as ``python.gc`` spans (with
  their generation), and JAX's jaxpr traces, backend compile requests and
  persistent-cache loads are counted (:func:`compiles`).
* No span reads a device array: a span never adds a host-device sync.

The tracer is on from import.  ``enable(False)`` makes :func:`span` return
one shared no-op context manager and stops recording collections and
counters, for operators who want none of its cost.  :func:`timed` spans
time themselves either way, for stage times their caller reports.
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import Any

import jax

RING = 1 << 17          # finished spans kept; older ones are dropped first

_TraceMe = jax.profiler.TraceAnnotation
_clock = time.perf_counter_ns
_ring: collections.deque = collections.deque(maxlen=RING)
_counters: dict[str, int] = {}
_ids = itertools.count(1)
_local = threading.local()
_on = False
_dropped = 0
_dropped_t1 = -1        # end of the newest dropped span
_gc_t0 = 0


class Span:
    """One span.  Open, it is the context manager :func:`span` returns;
    finished, it is what :func:`spans` lists.  Times are
    ``perf_counter_ns`` values; ``parent`` is the id of the enclosing span,
    or None."""

    __slots__ = ("id", "name", "t0", "t1", "parent", "attrs", "_ann", "_rec")

    def __init__(self, name: str, attrs: dict[str, Any], t0: int = 0,
                 t1: int = 0, parent: int | None = None,
                 sid: int | None = None):
        self.id = next(_ids) if sid is None else sid
        self.name, self.attrs = name, attrs
        self.t0, self.t1, self.parent = t0, t1, parent
        self._ann = None
        self._rec = False

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e-6

    def set(self, **attrs: Any) -> None:
        """Add attributes to an open span."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        self._rec = _on
        if _on:
            stack = _stack()
            self.parent = stack[-1].id if stack else None
            stack.append(self)
            if _TraceMe.is_enabled():
                self._ann = _TraceMe("repro." + self.name)
                self._ann.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = _clock()
        if not self._rec:
            return
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _stack().pop()
        _append((self.id, self.name, self.t0, self.t1, self.parent,
                 *itertools.chain.from_iterable(self.attrs.items())))


class _NoSpan:
    """What :func:`span` returns while the tracer is off."""

    __slots__ = ()

    def set(self, **attrs: Any) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NO_SPAN = _NoSpan()


def _stack() -> list[Span]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _append(rec: tuple) -> None:
    """Keep one finished span: ``(id, name, t0, t1, parent)`` followed by
    its attributes' keys and values, one tuple of atomic values."""
    global _dropped, _dropped_t1
    if len(_ring) == RING:
        _dropped += 1
        _dropped_t1 = max(_dropped_t1, _ring[0][3])
    _ring.append(rec)


def span(name: str, **attrs: Any) -> Span | _NoSpan:
    """A context manager that records one span named ``name``."""
    if not _on:
        return _NO_SPAN
    return Span(name, attrs)


def timed(name: str, **attrs: Any) -> Span:
    """:func:`span` for a stage whose time the caller reports itself: the
    span times itself (``.ms``) with the tracer on or off, and records only
    while it is on."""
    return Span(name, attrs)


def record(name: str, t0_ns: int, t1_ns: int, **attrs: Any) -> None:
    """Record a span that began and ended in different calls (such as a
    request's wait in a queue); its parent is the span open now."""
    if not _on:
        return
    stack = _stack()
    _append((next(_ids), name, t0_ns, t1_ns, stack[-1].id if stack else None,
             *itertools.chain.from_iterable(attrs.items())))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def compiles() -> dict[str, int]:
    """JAX's work so far: jaxprs traced, programs compiled by the backend,
    programs loaded from the persistent cache instead."""
    c = _counters
    loads = c.get("jax.cache_loads", 0)
    return {"traces": c.get("jax.traces", 0),
            "compiles": c.get("jax.compile_requests", 0) - loads,
            "loads": loads}


def compiles_since(before: dict[str, int]) -> dict[str, int]:
    """:func:`compiles` less an earlier reading of it."""
    return {k: v - before[k] for k, v in compiles().items()}


def spans(since_ns: int | None = None,
          until_ns: int | None = None) -> list[Span]:
    """Finished spans that started at or after ``since_ns`` and ended at or
    before ``until_ns``, in the order they finished."""
    lo = -1 if since_ns is None else since_ns
    hi = float("inf") if until_ns is None else until_ns
    return [Span(r[1], dict(zip(r[5::2], r[6::2])), r[2], r[3], r[4],
                 sid=r[0])
            for r in list(_ring) if r[2] >= lo and r[3] <= hi]


def counters() -> dict[str, int]:
    return dict(_counters)


def dropped(since_ns: int | None = None) -> int:
    """Spans the ring has dropped; with ``since_ns``, 0 unless one of them
    ended at or after ``since_ns``."""
    if since_ns is not None and _dropped_t1 < since_ns:
        return 0
    return _dropped


def reset() -> None:
    """Forget every span, counter and drop."""
    global _dropped, _dropped_t1
    _ring.clear()
    _counters.clear()
    _dropped, _dropped_t1 = 0, -1


def enabled() -> bool:
    return _on


def enable(on: bool = True) -> None:
    """Turn the tracer on or off (it starts on)."""
    global _on
    _on = bool(on)
    if _on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not _on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = _clock()
    else:
        record("python.gc", _gc_t0, _clock(), generation=info["generation"],
               collected=info["collected"])


_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.traces",
    # recorded for every request, persistent-cache loads included
    "/jax/core/compile/backend_compile_duration": "jax.compile_requests",
    "/jax/compilation_cache/cache_hits": "jax.cache_loads",
}


def _on_jax_event(event: str, *_: Any, **__: Any) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_listener(_on_jax_event)
jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
enable(True)
