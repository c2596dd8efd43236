"""One run of one cell: set-up, a measured window, the check, one line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration (a file of
sizes that names its architecture, ``architectures/<architecture>.py``)
and its traffic mix (``traffic/<mix>.json``).  With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries its per-layer
metrics, each computed by ``metrics/<metric>.py``.  ``correct`` compares
what the window produced with the float32 reference against the limits in
``limits/<workload>.json``.  ``--control 1`` also prints the reading of
the control (the reference in float8) for calibrating those limits.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def process_start_epoch() -> float:
    """Wall-clock time this process started, from /proc."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        btime = next(int(x.split()[1]) for x in f if x.startswith("btime"))
    return btime + start_ticks / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Ctx:
    """State of one run, handed to the code that runs the cell's kind."""

    def __init__(self, args, root, spec, cell, require_chip):
        from . import model, record, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.traced, self.control = bool(args.trace), bool(args.control)
        conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
        config = model.load_config(os.path.join(root, conf["file"]))
        self.arch = model.load_architecture(root, config["architecture"])
        self.dims = self.arch.dims(conf["name"], config)
        self.mcfg = self.arch.program_config(self.dims)
        self.traffic = traffic.load_traffic(os.path.join(
            root, "bench", "traffic", cell["traffic"] + ".json"))
        with open(os.path.join(root, "bench", "limits",
                               cell["name"] + ".json")) as f:
            self.limits = json.load(f)
        import jax
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.hw, peak = None, None
        if require_chip:
            from repro.core.profiler import hardware_for
            from .peaks import peak_for
            self.hw, peak = hardware_for(dev.device_kind), peak_for(
                dev.device_kind)
        self.compiles = record.Compiles()
        self.run = record.Run(kind=self.traffic["kind"], arch=self.arch,
                              dims=self.dims, traffic=self.traffic, peak=peak,
                              traced=self.traced,
                              spans=record.Spans(self.traced))
        self.phases: list[tuple[str, float]] = []
        self.checks: dict[str, dict] = {}
        self.attempted = self.failed = self.fallbacks = 0
        self.memory_peak = None
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_open = self.t_close = 0.0
        self.window_compiles: dict = {}
        self.setup_compiles: dict = {}
        self.log = log

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases.append((name, time.perf_counter() - t0))

    def measure(self, window):
        """Open the window, run ``window()``, close it.  Compilations are
        counted on both sides of the opening."""
        import jax
        self.setup_compiles = self.compiles.snapshot()
        if self.traced:
            jax.profiler.start_trace(self.trace_dir)
        self.run.setup_s = time.time() - PROC_START
        self.t_open = time.perf_counter()
        out = window()
        self.t_close = time.perf_counter()
        self.run.window_s = self.t_close - self.t_open
        self.run.t_open, self.run.t_close = self.t_open, self.t_close
        if self.traced:
            jax.profiler.stop_trace()
        self.window_compiles = self.compiles.since(
            self.setup_compiles, self.compiles.snapshot())
        return out

    def read_memory(self) -> None:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None

    def compare(self, name: str, value: float, limit=None) -> None:
        """Record a number compared with its limit; without ``limit`` the
        cell's ``limits/<workload>.json`` gives it."""
        if limit is None:
            limit = self.limits[name]["limit"]
        self.checks[name] = {"value": value, "limit": limit}


def load_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str, traced: bool) -> list[dict]:
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the float8 control (calibration only)")
    return ap.parse_args(argv)


PROC_START = time.time()


def main(argv=None, root: str = ROOT, require_chip: bool = True) -> int:
    global PROC_START
    try:
        PROC_START = process_start_epoch()
    except (OSError, ValueError, IndexError, StopIteration):
        pass
    t_main = time.time()
    args = parse(argv)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    jax.devices()
    t_backend = time.time()
    if require_chip:
        n = len(jax.devices()) if jax.default_backend() == "tpu" else 0
        if n < cell["chips"]:
            log(f"needs {cell['chips']} TPU chip(s); JAX backend "
                f"{jax.default_backend()!r} has {n}")
            return 2
        cache = os.path.join(root, ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # no eviction: the directory belongs to this checkout, and eviction's
        # bookkeeping files fail on some sandboxed file systems
        jax.config.update("jax_compilation_cache_max_size", -1)
        from repro.kernels import interpret_mode
        if interpret_mode():
            log("Pallas kernels would run in interpret mode")
            return 2
    ctx = Ctx(args, root, spec, cell, require_chip)
    # set-up before the cell's own phases: the interpreter and its imports,
    # JAX's backend (the chip's runtime), then the harness and the program
    ctx.phases[:0] = [("interpreter", t_main - PROC_START),
                      ("jax_backend", t_backend - t_main),
                      ("harness_init", time.time() - t_backend)]
    try:
        return finish(ctx, spec, cell, root)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)


def finish(ctx, spec, cell, root) -> int:
    from . import trace
    from repro.runtime.guard import kernel_log

    # a traffic mix's kind names the module that runs it: harness/<kind>.py
    kind = importlib.import_module(f"{__package__}.{ctx.traffic['kind']}")
    try:
        kind.run_cell(ctx)
    except Exception:
        traceback.print_exc()
        log("the run raised; no result")
        return 1
    ctx.fallbacks += len(kernel_log())
    run = ctx.run
    log("setup phases: " + ", ".join(f"{n} {s!r} s" for n, s in ctx.phases))
    log(f"compiles before the window: {ctx.setup_compiles}; "
        f"inside the window: {ctx.window_compiles}")
    device = dict(ctx.device, memory_peak_bytes=ctx.memory_peak)
    result = {}
    if ctx.traced:
        try:
            run.trace = trace.summarize(ctx.trace_dir, run.spans.items,
                                        ctx.t_open, ctx.t_close)
        except ValueError:
            if ctx.hw is not None:      # on the chip a trace must read
                raise
            log("the trace holds no TPU operations (not on a chip)")
    if run.trace is not None:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        result["breakdown"] = run.trace.breakdown()
    metrics = {}
    for m in cell_metrics(spec, cell["name"], ctx.traced):
        value = load_reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # a fallback means the window did not run the configured path
    ctx.compare("fallbacks", ctx.fallbacks, limit=0)
    correct = all(c["value"] <= c["limit"] for c in ctx.checks.values())
    for name, c in ctx.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    checks = {k: {"value": (v["value"] if math.isfinite(v["value"])
                            else str(v["value"])), "limit": v["limit"]}
              for k, v in ctx.checks.items()}
    line = {"correct": correct, "attempted": ctx.attempted or run.calls,
            "failed": ctx.failed, "metrics": metrics, "device": device}
    line.update(result)
    line["check"] = checks
    print(json.dumps(line), flush=True)
    return 0
