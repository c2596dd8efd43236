"""Reduction of one profiler trace to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run on the device.  The host plane ``/host:CPU``
holds the harness's spans (``jax.profiler.TraceAnnotation``, named
``bench.*``) on the same clock.

- busy: the union of the device-op intervals inside the window, averaged
  over the devices;
- per-operation device time: summed by the operation's name, which is
  the HLO instruction's name without its numeric suffix under the name of
  the program (``XLA Modules`` line) that ran it: ``jit_run/fusion``;
- idle gaps: the stretches of the window in which no operation ran on the
  device, each charged to the innermost harness span open at its middle.

Control-flow containers (``while``, ``conditional``, ``call``) span the
operations they run, which the trace also lists; they are left out, so
that no time counts twice.  The device clock of a v5e trace runs about
1.5 ms ahead of the host's, so a gap's span is right to within that.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
NO_SPAN = "no_benchmark_span"
CONTAINERS = {"while", "conditional", "call"}
_SUFFIX = re.compile(r"[.:]\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
_HLO_TEXT = re.compile(r"^%?([\w.\-]+) = ")


def op_name(name: str, module: str | None) -> str:
    """``%fusion.25 = f32[...] fusion(...)`` under module
    ``jit_run(1779...)`` -> ``jit_run/fusion``."""
    m = _HLO_TEXT.match(name)
    base = _SUFFIX.sub("", m.group(1) if m else name)
    if module:
        return f"{_MODULE_ID.sub('', module)}/{base}"
    return base


def _with_modules(ops, modules):
    """Name each op (name, start, end) under the module event that holds
    its start; both lists sorted by start."""
    out, j = [], 0
    for name, s, e in ops:
        while j + 1 < len(modules) and modules[j + 1][1] <= s:
            j += 1
        mod = modules[j][0] if modules and modules[j][1] <= s < modules[j][2] \
            else None
        full = op_name(name, mod)
        if full.rsplit("/", 1)[-1] not in CONTAINERS:
            out.append((full, s, e))
    return out


def union(intervals):
    """Merge [start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    """What the reduction reads from one trace file, times in ns."""
    device_ops: dict          # device plane -> [(name, start, end)]
    spans: list               # [(name, start, end)] harness spans

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        ops, spans = {}, []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {line.name: sorted(
                    ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events), key=lambda x: x[1])
                    for line in plane.lines
                    if line.name in (OPS_LINE, MODULES_LINE)}
                ops[plane.name] = _with_modules(lines.get(OPS_LINE, []),
                                                lines.get(MODULES_LINE, []))
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
        return cls(ops, sorted(spans, key=lambda s: s[1]))


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    ops: dict                 # name -> device seconds (summed over devices)
    gaps: dict                # span name -> idle seconds (first device)

    def op_seconds(self, part: str) -> float:
        """Device seconds of every operation whose name contains ``part``."""
        return sum(v for k, v in self.ops.items() if part in k)

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(tr: Trace, lo: int, hi: int) -> Summary:
    """Reduce ``tr`` over the window [lo, hi) (trace clock, ns)."""
    if not tr.device_ops or not any(tr.device_ops.values()):
        raise ValueError("the trace holds no device operations")
    ops: dict[str, float] = {}
    busy = []
    first = None
    for plane in sorted(tr.device_ops):
        evs = [(n, s, e) for n, s, e in tr.device_ops[plane]
               if e > lo and s < hi]
        for n, s, e in evs:
            ops[n] = ops.get(n, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        merged = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first is None:
            first = merged
    gaps: dict[str, float] = {}
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        open_ = [sp for sp in tr.spans if sp[1] <= mid < sp[2]]
        name = max(open_, key=lambda sp: sp[1])[0] if open_ else NO_SPAN
        gaps[name] = gaps.get(name, 0.0) + (e - s) * 1e-9
    return Summary(busy_s=sum(busy) / len(busy), window_s=(hi - lo) * 1e-9,
                   ops=ops, gaps=gaps)


def find_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def summarize(trace_dir: str, host_spans, t_open: float,
              t_close: float) -> Summary:
    """Reduce the trace in ``trace_dir`` over the window [t_open, t_close]
    (host ``perf_counter`` seconds).  The two clocks are tied by the first
    harness span inside the window, which both record."""
    tr = Trace.load(find_file(trace_dir))
    inside = sorted((s for s in host_spans if t_open <= s[1] <= t_close),
                    key=lambda s: s[1])
    if not inside or not tr.spans:
        raise ValueError("no harness span in the window to tie the clocks")
    name, t0, _ = inside[0]
    first = next(sp for sp in tr.spans if sp[0] == name)
    offset = first[1] - t0 * 1e9
    return reduce(tr, int(t_open * 1e9 + offset), int(t_close * 1e9 + offset))
