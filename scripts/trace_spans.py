#!/usr/bin/env python3
"""Where the chip waits, by the program's own spans.

Three measurements, on a machine with a TPU:

    python3 scripts/trace_spans.py cost [--n 100000]
    python3 scripts/trace_spans.py cell --workload <name> --seed <n> \\
        --seconds <s> --out <dir>
    python3 scripts/trace_spans.py bench --tracer off [--fill] -- \
        <bench/run.py args>

``cost`` times ``repro.runtime.tracing.span`` per span, with the tracer on
and off, with the profiler tracing and with the ring full, and a full
garbage collection with the ring empty and full.

``bench`` runs ``bench/run.py`` with the tracer on or off, to measure
what it costs end to end; ``--fill`` fills the ring first, as a process
that has served for an hour holds it.

``cell`` runs one benchmark cell once with ``--trace 1`` (through
``bench/harness``, unchanged) and keeps its profiler trace.  It writes
``<dir>/<workload>.json`` with:

- ``result``: the benchmark's result line;
- ``idle_by_repro_span``: each idle gap of the device inside the window,
  charged to the innermost ``repro.*`` annotation open at its middle (the
  rule ``bench/harness/trace.py`` applies to the harness's ``bench.*``
  spans), in seconds;
- ``idle_by_bench_and_repro_span``: the same gaps by (innermost ``bench.*``
  span, innermost ``repro.*`` span);
- ``bench_decode_idle``: the idle seconds under ``bench.decode``, and the
  part of them inside a ``repro.engine.decode.*`` span;
- ``decode_step`` / ``capture_call``: from the program's ring, the mean
  ``engine.step`` of the window's decode ticks (beside the harness's
  ``decode_step_ms.serve``) or ``capture.call``, its children's means, and
  the five longest with the collector's time inside them;
- ``gc``: the window's collections, by generation;
- ``pool_in_place``: for a serve cell, the run's decode ticks beside the
  counter ``engine.decode.pool_in_place`` (ticks whose step handed back
  its donated page pools in place), and the same for the window's ticks
  from their ``pool_in_place`` attribute;
- ``device_s_by_scope``: for a serve cell, device seconds of each operation
  of the paged decode step by its name and the ``jax.named_scope`` names in
  its ``op_name`` metadata (read from the compiled step's HLO).

``cell`` is a stopgap: ``bench/harness/trace.py`` charges idle gaps to the
harness's ``bench.*`` spans only, so ``_gaps`` and ``_open_at_middles``
repeat its rule for ``repro.*`` spans, and ``bench/tests/test_trace_spans.py``
holds them to it on a recorded v5e trace.  Once ``trace.py`` charges gaps to
``repro.*`` spans too, the copies go.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import io
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = ("pool_write", "attention", "latent_attention", "mlp")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")    # an event named by its HLO


def cost(n: int) -> dict:
    import jax
    from repro.runtime import tracing

    def per_span_ns() -> float:
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tracing.span("cost.outer", kind="decode"):
                pass
        return (time.perf_counter_ns() - t0) / n

    out = {"n": n}
    per_span_ns()                                        # warm
    out["on_ns"] = per_span_ns()
    tracing.enable(False)
    out["off_ns"] = per_span_ns()
    tracing.enable(True)
    d = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(d):
            out["on_profiled_ns"] = per_span_ns()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    out["full_gc_ms_empty"] = _full_gc_ms()
    fill(tracing)
    gc.collect()                      # the pass that stops tracking them
    out["full_gc_ms_full"] = _full_gc_ms()
    out["on_full_ns"] = per_span_ns()
    tracing.reset()
    return out


def _full_gc_ms(repeat: int = 5) -> float:
    """Least time of a full garbage collection (ms)."""
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter_ns()
        gc.collect()
        best = min(best, (time.perf_counter_ns() - t0) * 1e-6)
    return best


def fill(tracing) -> None:
    """Fill the ring with spans that ended long before any window."""
    for i in range(tracing.RING):
        tracing.record("fill", i, i + 1, kind="decode", active=2)


def _gaps(device_ops, lo, hi):
    """Idle stretches [s, e) of the first device inside [lo, hi)."""
    from harness import trace

    plane = sorted(device_ops)[0]
    merged = trace.union(trace.clip(
        [(s, e) for _, s, e in device_ops[plane] if e > lo and s < hi],
        lo, hi))
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def _open_at_middles(gaps, spans):
    """For each gap (in order of start), the spans (name, start, end) open
    at its middle, outermost first."""
    spans = sorted(spans, key=lambda sp: sp[1])
    i, active, out = 0, [], []
    for s, e in gaps:
        mid = (s + e) / 2
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] > mid]
        out.append(list(active))
    return out


def _hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> the named scopes in its op_name metadata."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                         r"metadata=\{[^}]*op_name=\"([^\"]*)\"", hlo_text,
                         re.M):
        parts = m.group(2).split("/")
        out[m.group(1)] = "/".join(p for p in parts if p in SCOPES) or "-"
    return out


def _profile(spans, parents, gc_spans) -> dict:
    """Mean duration of ``parents`` and of each kind of child (ms), and the
    five longest with the collector's time inside each."""
    ids = {sp.id for sp in parents}
    kids = collections.defaultdict(float)
    for sp in spans:
        if sp.parent in ids:
            kids[sp.name] += sp.ms / len(parents)
    top = sorted(parents, key=lambda sp: -sp.ms)[:5]
    return {"n": len(parents),
            "mean_ms": sum(sp.ms for sp in parents) / len(parents),
            "children_mean_ms": dict(kids),
            "longest": [{"ms": sp.ms, "attrs": sp.attrs, "gc_ms": sum(
                g.ms for g in gc_spans if sp.t0 <= g.t0 and g.t1 <= sp.t1)}
                for sp in top]}


def _step_ops(path, lo, hi):
    """(instruction name, start, end) of every operation the paged decode
    step (module ``jit__step``) ran on the first device inside [lo, hi)."""
    from harness import trace
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: sorted(
            ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for ev in line.events), key=lambda x: x[1])
            for line in plane.lines}
        mods = [m for m in lines.get(trace.MODULES_LINE, [])
                if m[0].startswith("jit__step(")]
        out, j = [], 0
        for name, s, e in lines.get(trace.OPS_LINE, []):
            if e <= lo or s >= hi:
                continue
            while j + 1 < len(mods) and mods[j + 1][1] <= s:
                j += 1
            if mods and mods[j][1] <= s < mods[j][2]:
                m = _INSTRUCTION.match(name)
                out.append((m.group(1) if m else name, max(s, lo),
                            min(e, hi)))
        return out
    return []


def cell(args) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    keep_dir = tempfile.mkdtemp(prefix="trace_spans_")
    try:
        return _cell(args, keep_dir)
    finally:
        shutil.rmtree(keep_dir, ignore_errors=True)


def _cell(args, keep_dir) -> dict:
    import jax
    from harness import main as hmain
    from harness import trace
    from repro.runtime import tracing
    import repro.serving.engine as engine_mod

    kept = {}
    summarize = trace.summarize

    def keep_trace(trace_dir, host_spans, t_open, t_close):
        kept.update(path=os.path.join(keep_dir, "trace.xplane.pb"),
                    host_spans=host_spans, t_open=t_open, t_close=t_close)
        shutil.copy(trace.find_file(trace_dir), kept["path"])
        return summarize(trace_dir, host_spans, t_open, t_close)

    trace.summarize = keep_trace
    cached = engine_mod._donated_paged_decode_fn

    def keep_step(model):
        fn = cached(model)
        kept.update(model=model, step=fn)

        def step(*a):
            if "step_args" not in kept:
                kept["step_args"] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding), a)
            return fn(*a)
        return step

    engine_mod._donated_paged_decode_fn = keep_step
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = hmain.main(["--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", str(args.seconds),
                             "--trace", "1"])
    finally:
        trace.summarize = summarize
        engine_mod._donated_paged_decode_fn = cached
    lines = [x for x in buf.getvalue().splitlines() if x.startswith("{")]
    out = {"workload": args.workload, "seed": args.seed, "rc": rc,
           "result": json.loads(lines[-1]) if lines else None}
    if "path" not in kept:
        return out
    try:
        out.update(_reduce(kept, tracing))
    except Exception as exc:        # keep the result line
        out["error"] = repr(exc)
    return out


def _reduce(kept, tracing) -> dict:
    from harness import trace
    from jax.profiler import ProfileData

    out = {}
    t_open, t_close = kept["t_open"], kept["t_close"]
    tr = trace.Trace.load(kept["path"])
    inside = sorted((s for s in kept["host_spans"]
                     if t_open <= s[1] <= t_close), key=lambda s: s[1])
    name, t0, _ = inside[0]
    first = next(sp for sp in tr.spans if sp[0] == name)
    offset = first[1] - t0 * 1e9
    lo, hi = int(t_open * 1e9 + offset), int(t_close * 1e9 + offset)
    repro = []
    for plane in ProfileData.from_file(kept["path"]).planes:
        if plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        repro.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    by_repro = collections.Counter()
    by_pair = collections.Counter()
    in_decode = collections.Counter()
    gaps = _gaps(tr.device_ops, lo, hi) if tr.device_ops else []
    for (s, e), bench, prog in zip(gaps, _open_at_middles(gaps, tr.spans),
                                   _open_at_middles(gaps, repro)):
        b = bench[-1][0] if bench else "no_benchmark_span"
        r = prog[-1][0] if prog else "no_program_span"
        by_repro[r] += (e - s) * 1e-9
        by_pair[f"{b} | {r}"] += (e - s) * 1e-9
        if b == "bench.decode":
            in_decode["all"] += (e - s) * 1e-9
            if any(sp[0].startswith("repro.engine.decode.") for sp in prog):
                in_decode["in_decode_child"] += (e - s) * 1e-9
    out.update(window_s=(hi - lo) * 1e-9, repro_annotations=len(repro),
               idle_by_repro_span=by_repro.most_common(),
               idle_by_bench_and_repro_span=by_pair.most_common(20),
               bench_decode_idle=dict(in_decode))
    spans = tracing.spans(int(t_open * 1e9), int(t_close * 1e9))
    gc_spans = [sp for sp in spans if sp.name == "python.gc"]
    decodes = [sp for sp in tracing.spans() if sp.name == "engine.step"
               and sp.attrs.get("kind") == "decode"]
    if decodes:
        in_window = [sp for sp in decodes if sp.t0 >= int(t_open * 1e9)
                     and sp.t1 <= int(t_close * 1e9)]
        out["pool_in_place"] = {
            "decode_ticks": len(decodes),
            "counter": tracing.counters().get("engine.decode.pool_in_place"),
            "window_ticks": len(in_window),
            "window_in_place": sum(bool(sp.attrs.get("pool_in_place"))
                                   for sp in in_window)}
    for key, parents in (
            ("decode_step", [sp for sp in spans if sp.name == "engine.step"
                             and sp.attrs.get("kind") == "decode"]),
            ("capture_call", [sp for sp in spans
                              if sp.name == "capture.call"])):
        if parents:
            out[key] = _profile(spans, parents, gc_spans)
    out["gc"] = {"n": len(gc_spans), "s": sum(sp.ms for sp in gc_spans) / 1e3,
                 "max_ms": max((sp.ms for sp in gc_spans), default=0.0),
                 "by_generation": dict(collections.Counter(
                     sp.attrs["generation"] for sp in gc_spans))}
    if "step_args" in kept:
        hlo = kept["step"].lower(*kept["step_args"]).compile().as_text()
        scopes = _hlo_scopes(hlo)
        dev = collections.Counter()
        for inst, s, e in _step_ops(kept["path"], lo, hi):
            dev[f"{trace.op_name(inst, None)} @ {scopes.get(inst, '?')}"] \
                += (e - s) * 1e-9
        out["device_s_by_scope"] = dev.most_common(25)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("cost")
    c.add_argument("--n", type=int, default=100_000)
    w = sub.add_parser("cell")
    w.add_argument("--workload", required=True)
    w.add_argument("--seed", type=int, required=True)
    w.add_argument("--seconds", type=float, default=51)
    w.add_argument("--out", required=True)
    b = sub.add_parser("bench")
    b.add_argument("--tracer", choices=("on", "off"), required=True)
    b.add_argument("--fill", action="store_true")
    b.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.cmd == "bench":
        sys.path.insert(0, os.path.join(ROOT, "bench"))
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from harness.main import main as bench_main
        from repro.runtime import tracing
        tracing.enable(args.tracer == "on")
        if args.fill:
            fill(tracing)
        return bench_main([a for a in args.rest if a != "--"])
    if args.cmd == "cost":
        sys.path.insert(0, os.path.join(ROOT, "src"))
        print(json.dumps(cost(args.n)), flush=True)
        return 0
    os.makedirs(args.out, exist_ok=True)
    out = cell(args)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out.get(k) for k in (
        "workload", "rc", "error", "idle_by_repro_span", "bench_decode_idle",
        "decode_step", "capture_call", "gc", "pool_in_place")}), flush=True)
    return 0 if out["rc"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
