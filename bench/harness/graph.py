"""Graph cells: one caller in a closed loop over the Opara-captured
forward (``Session.compile(...)`` of the exported graph), each call ended
by ``block_until_ready``.  The window's calls, and nothing else, run
between the clock reads that bound it."""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import counts
from .model import make_params, seed_key


def _session(ctx, **kw):
    from repro.core import Session
    return Session(hw=ctx.hw, **kw) if ctx.hw is not None else Session(**kw)


def _per_call_ms(fn, inputs, seconds: float) -> float:
    """Closed-loop time per call over at least ``seconds``."""
    n, t0 = 0, time.perf_counter()
    while True:
        jax.block_until_ready(fn({"tokens": inputs[n % len(inputs)]}))
        n += 1
        if time.perf_counter() - t0 >= seconds:
            return (time.perf_counter() - t0) / n * 1e3


def run_cell(ctx) -> None:
    t, dm, run = ctx.traffic, ctx.dims, ctx.run
    b, s = int(t["batch"]), int(t["seq"])
    with ctx.phase("weights"):
        params = jax.block_until_ready(make_params(ctx.mcfg, ctx.seed))
    with ctx.phase("export"):
        g = ctx.arch.export(ctx.mcfg, b, s, params)
        del params
    with ctx.phase("schedule_and_lower"):
        comp = _session(ctx).compile(g)
    exe = comp.executable
    ctx.log(f"program_stats {exe.program_stats()}")
    logits_out = [g.nodes[o].name for o in exe.output_ids].index("logits")
    # shapes of every branch-GEMM step: w [N, K, F] (+ bias [N, F]); the
    # stacked input is [N, *batch, K] with batch = the graph's (b, s)
    run.branch_gemm_steps = [
        counts.branch_gemm(st.consts[0].shape[0], b * s, st.consts[0].shape[1],
                           st.consts[0].shape[2], len(st.consts) > 1)
        for st in exe.steps if st.route == "branch_gemm"]
    run.flops_per_call = ctx.arch.forward_flops(dm, b, s)
    n_in = int(t.get("inputs", 8))
    key = jax.random.fold_in(seed_key(ctx.seed), 1)
    pool = jax.jit(lambda k: jax.random.randint(
        k, (n_in, b, s), 0, dm.vocab, dtype=jnp.int32))(key)
    inputs = [pool[i] for i in range(n_in)]
    with ctx.phase("warm_up"):
        for i in range(2):
            jax.block_until_ready(comp({"tokens": inputs[i]}))

    def window():
        n = 0
        out = None
        while True:
            with run.spans.span("call"):
                out = comp({"tokens": inputs[n % n_in]})
                jax.block_until_ready(out)
            n += 1
            if time.perf_counter() - ctx.t_open >= ctx.seconds:
                return n, out

    run.calls, out = ctx.measure(window)
    last = inputs[(run.calls - 1) % n_in]
    seq = None
    if ctx.traced:
        # the paper's sequential baseline: the same graph, one operator per
        # step, in one jax.jit; built and warmed after the window, so that
        # traced and untraced runs do the same set-up, and timed like the
        # window, alternating sides
        seq = _session(ctx, alloc_policy="sequential",
                       order_policy="topo").compile(g)
        for i in range(2):
            jax.block_until_ready(seq({"tokens": inputs[i]}))
        o, q = [], []
        for _ in range(2):
            o.append(_per_call_ms(comp, inputs, 1.0))
            q.append(_per_call_ms(seq, inputs, 1.0))
        run.opara_ms, run.sequential_ms = float(np.mean(o)), float(np.mean(q))
        ctx.log(f"opara {o} ms/call, sequential {q} ms/call")
    ctx.read_memory()
    ctx.fallbacks += (len(exe.degradations) + len(comp.degradations)
                      + (len(seq.executable.degradations) if seq else 0))
    rows = sorted(np.random.default_rng(ctx.seed).choice(
        b, min(b, int(t.get("check_rows", 2))), replace=False).tolist())
    got = out[logits_out][jnp.asarray(rows)]
    tokens = np.asarray(last)[rows]
    del out, comp, seq, exe, g, inputs, pool, last
    gc.collect()
    t0 = time.perf_counter()
    check(ctx, got, tokens)
    ctx.log(f"the check took {time.perf_counter() - t0!r} s")


def check(ctx, got, tokens) -> None:
    """The window's last call against the float32 reference: the largest
    logit error over the sampled rows, as a share of the reference's
    largest logit."""
    dm, logits = ctx.dims, ctx.arch.logits
    params = make_params(ctx.mcfg, ctx.seed)
    s = tokens.shape[1]
    errs, ctl = [], []
    for r in range(tokens.shape[0]):
        ref = logits(params, tokens[r], np.arange(s), dm, "f32")
        scale = jnp.max(jnp.abs(ref))
        errs.append(float(jnp.max(jnp.abs(got[r] - ref)) / scale))
        if ctx.control:
            c = logits(params, tokens[r], np.arange(s), dm, "fp8")
            ctl.append(float(jnp.max(jnp.abs(c - ref)) / scale))
        del ref
    ctx.compare("logit_err", max(errs))
    if ctx.control:
        ctx.log(f"control logit_err {max(ctl)!r}")
