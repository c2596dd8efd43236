#!/usr/bin/env python3
"""Drive the system's two main paths once on one TPU chip and check them.

    python3 chip_smoke.py [--seed N]

Both phases run qwen2-0.5b at its published widths (24 layers, d_model
896, 14 query heads over 2 KV heads, d_ff 4864, vocab 151936) with random
bf16 weights made from ``--seed``, in this one process.

1. Opara capture path.  The whole forward graph is exported at batch 4 x
   128 tokens, compiled by a ``Session`` into one executable and run.  At
   least one fused step must take the ``branch_gemm`` Pallas route.  The
   logits are compared with the same graph run op by op
   (``run_sequential_uncompiled``) and with the model's jitted prefill.
2. Serving path.  ``launch.serve.serve`` serves 8 seeded requests on 4
   slots (max_len 256) through the Pallas kernels and the paged KV cache;
   every request must finish DONE.  Then the kernel path and the reference
   path (``use_kernels=False``) are fed one teacher-forced token stream
   (batch 4, 128-token prompts, 8 decode steps) and their logits compared
   at the prefill and at each paged decode step.

A fallback that fired anywhere fails the run: the kernel ladder log, a
session's ``guard_log``, the executable's ``degradations`` and the
engine's fallback and failure counters must all stay empty.

Logits are compared in float32 with the bf16 tolerance ``TOL``: the largest
absolute difference may be at most ``TOL`` times the largest absolute
reference logit.  bf16 keeps 8 significant bits (a relative step of
2**-8, about 0.4%); two correct bf16 programs that round in a different
order drift by a few steps over 24 layers, while a wrong mask, position or
page moves logits by their own size.

With no TPU the script prints no result and exits 2.  Otherwise its last
line is one JSON object, ``{"ok": ..., "device": {...}}``; a failed check
or phase makes it ``"ok": false`` and the exit code 1.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time
import traceback

TOL = 5e-2
ARCH = "qwen2-0.5b"
CAPTURE_BATCH, CAPTURE_SEQ = 4, 128
SLOTS, N_REQUESTS, MAX_LEN, MAX_TOKENS = 4, 8, 256, 16
TF_PROMPT, TF_STEPS = 128, 8
FALLBACK_COUNTERS = ("watchdog_fallbacks", "paged_decode_fallbacks",
                     "decode_faults", "failed_requests")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_close(name: str, got, ref, failures: list[str]) -> None:
    """Record a failure unless ``got`` is finite, shaped like ``ref`` and
    max |got - ref| <= TOL * max |ref| (in float32)."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        failures.append(f"{name}: shape {got.shape} != {ref.shape}")
        return
    if not bool(np.isfinite(got).all()):
        failures.append(f"{name}: non-finite values")
        return
    err = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))
    log(f"{name}: max|diff|/max|ref| = {err!r} (tolerance {TOL})")
    if not err <= TOL:
        failures.append(f"{name}: relative error {err!r} > {TOL}")


def capture_phase(cfg, params, seed: int, hw) -> list[str]:
    import jax
    import jax.numpy as jnp

    from repro.core import Session
    from repro.core.capture import run_sequential_uncompiled
    from repro.models import Model
    from repro.models.opgraph_export import build_lm_opgraph

    failures: list[str] = []
    tokens = jax.random.randint(jax.random.key(seed + 1),
                                (CAPTURE_BATCH, CAPTURE_SEQ), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    t0 = time.perf_counter()
    g = build_lm_opgraph(cfg, batch=CAPTURE_BATCH, seq=CAPTURE_SEQ,
                         params=params)
    log(f"capture: exported {len(g)} operators in "
        f"{time.perf_counter() - t0!r} s")
    sess = Session(hw=hw)
    t0 = time.perf_counter()
    compiled = sess.compile(g)
    log(f"capture: scheduled and lowered in {time.perf_counter() - t0!r} s "
        f"(waves={compiled.plan.waves.n_waves})")
    exe = compiled.executable
    names = [g.nodes[o].name for o in exe.output_ids]
    inputs = {"tokens": tokens}
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(inputs))
    t_first = time.perf_counter() - t0
    # after the first call, so that the kernel grid of the traced shapes
    # is counted too
    stats = exe.program_stats()
    log(f"capture: program_stats {stats}")
    if not stats["n_branch_gemm"] > 0:
        failures.append("capture: no step took the branch_gemm Pallas route")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(inputs))
    t_call = time.perf_counter() - t0
    log(f"capture: first call (trace + compile + run) {t_first!r} s; "
        f"one timed call {t_call!r} s (information, not a metric)")
    logits = out[names.index("logits")]
    seq = run_sequential_uncompiled(g, inputs, exe.output_ids)
    check_close("capture vs op-by-op", logits,
                seq[names.index("logits")], failures)
    del seq
    ref, _, _ = jax.jit(Model(cfg).prefill)(params, inputs)
    check_close("capture vs jit(model.prefill), last position",
                logits[:, -1], ref, failures)
    if len(exe.degradations) or compiled.degradations or len(sess.guard_log):
        failures.append(
            f"capture: fallbacks fired: {exe.degradations.as_dicts()} "
            f"{compiled.degradations} {sess.guard_log.as_dicts()}")
    return failures


def _paged_prefill(model, params, tokens, maxp: int, page_size: int):
    """Prefill ``tokens`` [B, S] and lay each row's cache out on ``maxp``
    pages of its own (page 0 stays the null page).  Returns (last logits,
    paged caches, block table)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_decode.ref import positions_to_pages
    from repro.models.attention import pool_rows

    b = tokens.shape[0]
    prefill = jax.jit(functools.partial(model.prefill,
                                        cache_len=maxp * page_size))
    logits, cache, _ = prefill(params, {"tokens": tokens})
    pages = model.init_paged_caches(1 + b * maxp, page_size)

    def place(paged, dense):             # dense [L, B, maxp*ps, KVH, D]
        blocks = positions_to_pages(dense, page_size)
        blocks = blocks.reshape(dense.shape[0], b * maxp, *blocks.shape[3:])
        return paged.at[:, 1:].set(blocks.astype(paged.dtype))

    rows = [pool_rows(model.cfg, c) for c in cache]
    pages = jax.tree_util.tree_map(place, pages, rows)
    bt = 1 + jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)
    return logits, pages, bt


def teacher_forced(cfg, params, seed: int, page_size: int, failures) -> None:
    """Kernel path vs reference path on one teacher-forced stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Model

    stream = jax.random.randint(jax.random.key(seed + 2),
                                (SLOTS, TF_PROMPT + TF_STEPS), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    maxp = -(-(TF_PROMPT + TF_STEPS) // page_size)
    runs = {}
    for name, use_kernels in (("kernels", True), ("reference", False)):
        model = Model(cfg, use_kernels=use_kernels)
        logits, pages, bt = _paged_prefill(model, params,
                                           stream[:, :TF_PROMPT], maxp,
                                           page_size)
        step = jax.jit(model.paged_decode)
        per_step = [logits]
        for i in range(TF_STEPS):
            pos = jnp.full((SLOTS,), TF_PROMPT + i, jnp.int32)
            logits, pages, _ = step(params, stream[:, TF_PROMPT + i],
                                    pages, bt, pos)
            per_step.append(logits)
        runs[name] = [np.asarray(x, np.float32) for x in per_step]
    for i, (got, ref) in enumerate(zip(runs["kernels"], runs["reference"])):
        where = "prefill" if i == 0 else f"decode step {i}"
        check_close(f"serving kernels vs reference, {where}", got, ref,
                    failures)
    agree = np.mean([np.mean(a.argmax(-1) == b.argmax(-1))
                     for a, b in zip(runs["kernels"], runs["reference"])])
    log(f"serving: greedy-token agreement kernels vs reference {agree!r} "
        "(information: bf16 near-ties may flip)")


def serving_phase(cfg, seed: int) -> list[str]:
    import jax

    from repro.launch.serve import PAGE_SIZE, serve
    from repro.models import Model

    failures: list[str] = []
    t0 = time.perf_counter()
    res = serve(ARCH, n_requests=N_REQUESTS, max_tokens=MAX_TOKENS,
                slots=SLOTS, max_len=MAX_LEN, smoke=False, use_kernels=True,
                paged_kv=True)
    log(f"serving: {res['completed']}/{N_REQUESTS} done, "
        f"{res['total_tokens']} tokens in {time.perf_counter() - t0!r} s "
        "(compilation included)")
    if res["completed"] != N_REQUESTS:
        failures.append("serving: not every request DONE: " + str(
            {k: res[k] for k in ("completed", "failed", "shed", "expired")}))
    fired = {k: res["fault_stats"][k] for k in FALLBACK_COUNTERS
             if res["fault_stats"][k]}
    if fired:
        failures.append(f"serving: fallback counters fired: {fired}")
    if res["degradations"]:
        failures.append(f"serving: fallbacks recorded: {res['degradations']}")
    # the engine's weights: serve() initialises from key 0
    params = Model(cfg).init(jax.random.key(0))
    teacher_forced(cfg, params, seed, PAGE_SIZE, failures)
    return failures


def run(seed: int, hw) -> list[str]:
    """Both phases in this process; the failures of every check."""
    import jax

    from repro.configs import get_config
    from repro.models import Model
    from repro.runtime.guard import kernel_log

    cfg = get_config(ARCH)
    failures: list[str] = []
    try:
        params = Model(cfg).init(jax.random.key(seed))
        failures += capture_phase(cfg, params, seed, hw)
    except Exception as exc:
        traceback.print_exc()
        failures.append(f"capture: raised {exc!r}")
    params = None
    gc.collect()
    try:
        failures += serving_phase(cfg, seed)
    except Exception as exc:
        traceback.print_exc()
        failures.append(f"serving: raised {exc!r}")
    if len(kernel_log()):
        failures.append(f"kernel fallbacks: {kernel_log().as_dicts()}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.core.profiler import hardware_for
    from repro.kernels import interpret_mode
    from repro.runtime.compile_cache import use_compile_cache

    if interpret_mode():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 2
    log(f"compile cache: {use_compile_cache()}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    try:
        hw = hardware_for(dev.device_kind)
    except ValueError as exc:
        failures = [str(exc)]
    else:
        failures = run(seed=args.seed, hw=hw)
    for f in failures:
        log(f"FAILED {f}")
    result = {"ok": not failures, "device": device}
    if failures:
        result["failures"] = failures
    print(json.dumps(result), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
