"""End-to-end serving entry point (continuous batching).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --requests 8 --max-tokens 16

The CLI serves the smoke-size configuration with the reference attention
paths.  ``serve(..., smoke=False, use_kernels=True, paged_kv=True)`` serves
the published widths through the Pallas kernels and the paged KV cache (the
path ``chip_smoke.py`` checks on a TPU).

Multi-tenant overload mode: ``--tenants N`` spreads the requests over N
tenants — each with its own isolated :class:`repro.core.Session` so
per-tenant shed/expire/preempt provenance lands on that tenant's
``guard_log`` — and ``--overload`` arms the admission tier (bounded queue,
per-tenant quotas, mixed priorities and tick deadlines) against a burst
trace, printing the goodput/shed/expiry ledger instead of falling over.
"""
from __future__ import annotations

import argparse
import copy
import sys
import time

import jax
import numpy as np

from ..configs import get_config
from ..models import Model
from ..serving import (AdmissionConfig, InferenceEngine, Request,
                       RequestState, TERMINAL_STATES)

# One page is one KV tile of the paged-decode kernel, which needs a lane
# multiple (128); the reference path reads any page size.
PAGE_SIZE = 128


def serve(arch: str, n_requests: int, max_tokens: int, slots: int = 4,
          max_len: int = 128, temperature: float = 0.0,
          calibrate: bool = False, tenants: int = 1,
          overload: bool = False, max_queue: int | None = None,
          tenant_quota: int | None = None, ttl: int | None = None,
          smoke: bool = True, use_kernels: bool = False,
          paged_kv: bool = False) -> dict:
    """Serve ``n_requests`` seeded requests and return the ledger.

    Besides the counts, the result carries the engine's ``fault_stats`` and
    ``degradations``: the fallback events recorded on the serving and
    tenant sessions."""
    cfg = get_config(arch, smoke=smoke)
    model = Model(cfg, use_kernels=use_kernels)
    params = model.init(jax.random.key(0))
    # one explicit Session for the whole serving process: every engine this
    # driver spins up shares its measured-profile / schedule caches.  Each
    # tenant additionally gets an ISOLATED Session (PR 4: cheap, composable
    # compilation state) that collects that tenant's degradation provenance.
    from ..core import Session
    session = Session()
    tenant_names = [f"tenant{i}" for i in range(max(1, tenants))]
    tenant_sessions = {name: Session() for name in tenant_names}
    admission = AdmissionConfig(max_queue=max_queue,
                                tenant_quota=tenant_quota)
    engine = InferenceEngine(model, params, max_slots=slots, max_len=max_len,
                             session=session, calibrate=calibrate,
                             admission=admission,
                             tenant_sessions=tenant_sessions,
                             paged_kv=paged_kv, page_size=PAGE_SIZE)
    if calibrate and engine.schedule_plan is not None:
        p = engine.schedule_plan
        stats = session.cache_stats()
        # non-profileable archs degrade to the analytic cost model inside
        # calibrate_schedule (one DegradationWarning) — surface it here too
        mode = ("analytic (degraded)" if stats["calib_degraded_analytic"]
                else "measured")
        print(f"[serve] opara schedule [{mode}]: streams={p.n_streams} "
              f"waves={p.waves.n_waves} (calibration "
              f"{stats['calib_misses']} timed / "
              f"{stats['calib_hits']} cached)")
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for rid in range(n_requests):
        prompt = rng.integers(1, cfg.vocab_size, size=rng.integers(4, 12)).tolist()
        req = Request(rid=rid, prompt=prompt, max_tokens=max_tokens,
                      temperature=temperature,
                      tenant=tenant_names[rid % len(tenant_names)])
        if overload:
            # mixed priorities and tick-TTLs: the admission tier sheds /
            # expires / preempts deterministically instead of queueing
            # forever — every request still ends in a terminal state
            req.priority = rid % 3
            req.ttl = ttl if ttl is not None else max_tokens * 2 + 8
        engine.submit(req)
    done = engine.drain()
    wall = time.perf_counter() - t0
    by_state = {s.value: 0 for s in TERMINAL_STATES}
    for r in done:
        by_state[r.state.value] += 1
    assert all(r.state in TERMINAL_STATES for r in done), \
        "engine returned a non-terminal request"
    total_tokens = sum(len(r.output) for r in done)
    result = {
        "completed": by_state["done"],
        "failed": by_state["failed"],
        "shed": by_state["shed"],
        "expired": by_state["expired"],
        "total_tokens": total_tokens,
        "wall_s": wall,
        "tok_per_s": total_tokens / wall if wall > 0 else 0.0,
    }
    ledger = {
        "fault_stats": copy.deepcopy(engine.fault_stats),
        "degradations": session.guard_log.as_dicts() + [
            e for s in tenant_sessions.values() for e in s.guard_log.as_dicts()],
    }
    for r in done[:8]:
        if r.state is RequestState.DONE:
            print(f"[serve] rid={r.rid} {r.tenant} prompt_len={len(r.prompt)} "
                  f"out={r.output[:8]}{'...' if len(r.output) > 8 else ''}")
        else:
            print(f"[serve] rid={r.rid} {r.tenant} {r.state.value.upper()}: "
                  f"{r.error}")
    if tenants > 1 or overload:
        for name in tenant_names:
            stats = engine.fault_stats["by_tenant"].get(name, {})
            events = len(tenant_sessions[name].guard_log)
            print(f"[serve] {name}: {stats} ({events} provenance events)")
        print(f"[serve] health: {engine.health()}")
    print(f"[serve] {result}")
    return {**result, **ledger}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--calibrate", action="store_true",
                    help="measured-profile Opara schedule of the step graph")
    ap.add_argument("--tenants", type=int, default=1,
                    help="spread requests over N isolated tenants")
    ap.add_argument("--overload", action="store_true",
                    help="arm the admission tier: priorities + deadlines")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound on the admission queue (shed beyond)")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="max queued requests per tenant")
    ap.add_argument("--ttl", type=int, default=None,
                    help="per-request deadline in ticks from submission")
    args = ap.parse_args(argv)
    from ..runtime.compile_cache import use_compile_cache
    use_compile_cache()
    res = serve(args.arch, args.requests, args.max_tokens, args.slots,
                calibrate=args.calibrate, tenants=args.tenants,
                overload=args.overload, max_queue=args.max_queue,
                tenant_quota=args.tenant_quota, ttl=args.ttl)
    terminal = (res["completed"] + res["failed"] + res["shed"]
                + res["expired"])
    ok = (terminal == args.requests
          and (res["completed"] == args.requests
               or args.overload or args.max_queue is not None))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
