"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json-dir DIR]

Sections:
    table1_scheduler     Alg. 1 vs Nimble scheduling cost        (Table 1)
    fig5a_inference      4-policy inference speedups             (Fig. 5a)
    fig5b_utilization    utilization proxy + stream counts       (Fig. 5b/1)
    fig2_launch_order    depth-first vs Opara order              (Fig. 2)
    fig8_throughput      throughput vs batch size                (Fig. 8)
    sec5_3_overhead      profiling + scheduling overhead         (§5.3)
    wallclock            real CPU wall-clock eager/jit/fused     (Fig. 5a mech.)
    serving_overload     admission tier vs FIFO under overload   (serving tier)

Structured output: sections that track the perf trajectory additionally
write machine-diffable JSON (``BENCH_scheduler.json`` — per-workload
scheduling cost + plan-cache hit time; ``BENCH_inference.json`` — makespan
per policy + schedule/capture wall time) so regressions between PRs show
up as a JSON diff.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _write_json(path: str, payload: dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow wallclock section")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_*.json trajectory files")
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bench_inference, bench_launch_order, bench_overhead,
                   bench_scheduler, bench_throughput, bench_utilization,
                   bench_wallclock)

    sections = [
        ("table1_scheduler", bench_scheduler.run),
        ("fig5a_inference", bench_inference.run),
        ("fig5b_utilization", bench_utilization.run),
        ("fig2_launch_order", bench_launch_order.run),
        ("fig8_throughput", bench_throughput.run),
        ("sec5_3_overhead", bench_overhead.run),
    ]
    if not args.quick:
        sections.append(("wallclock", bench_wallclock.run))
        # real model inference on an overload trace — skipped in --quick so
        # the CI bench gate's wall-clock envelope is untouched
        from . import bench_serving
        sections.append(("serving_overload", bench_serving.run))

    from repro.core import reset_default_session

    failures = 0
    ran: set[str] = set()
    for name, fn in sections:
        if args.only and args.only != name:
            continue
        # sections that go through the default session start cold — one
        # section's warm plan cache must not flatter another's timings
        reset_default_session()
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        t0 = time.perf_counter()
        try:
            for row in fn():
                print(row)
            print(f"# {name} done in {time.perf_counter() - t0:.1f}s")
            ran.add(name)
        except Exception as e:                      # pragma: no cover
            import traceback
            traceback.print_exc()
            print(f"# {name} FAILED: {e}")
            failures += 1

    # perf-trajectory JSON (diffable across PRs).  Partial runs (--only)
    # merge into an existing file instead of clobbering the other
    # sections' records with empty lists.
    if "table1_scheduler" in ran or "sec5_3_overhead" in ran:
        path = os.path.join(args.json_dir, "BENCH_scheduler.json")
        payload = _read_json(path)
        if "table1_scheduler" in ran:
            payload["workloads"] = list(bench_scheduler.RECORDS)
        if "sec5_3_overhead" in ran:
            payload["overhead"] = list(bench_overhead.RECORDS)
        _write_json(path, payload)
    if "fig5a_inference" in ran:
        _write_json(os.path.join(args.json_dir, "BENCH_inference.json"),
                    {"workloads": bench_inference.RECORDS})
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
