"""Serve cells: requests through ``InferenceEngine.submit``/``step`` with
the paged KV cache (``paged_kv=True``), the Pallas kernels
(``use_kernels=True``) and greedy sampling.

The harness is the only client and the only clock: it submits each
request when it is due, calls ``step()`` while there is work, and stamps
every output token when the tick that made it returns.  A tick is an
admission (one prefill) when a slot is free and the queue is not empty,
and a decode step otherwise; both are spans of their own.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import numpy as np

from .model import make_params
from .traffic import corpus, requests


def _busy(engine) -> bool:
    return bool(len(engine.admission)
                or any(s is not None for s in engine.slots))


class Client:
    """Drives the engine and records the time of every output token."""

    def __init__(self, engine, run, spans):
        self.engine, self.run, self.spans = engine, run, spans
        self.seen: dict[int, int] = {}
        self.reqs: dict[int, object] = {}
        self.submitted: dict[int, float] = {}

    def submit(self, req) -> None:
        self.reqs[req.rid] = req
        self.submitted[req.rid] = time.perf_counter()
        self.run.token_times.setdefault(req.rid, [])
        self.engine.submit(req)

    def tick(self) -> None:
        eng = self.engine
        active = [i for i, r in enumerate(eng.slots) if r is not None]
        kind = ("admit" if len(active) < eng.max_slots
                and len(eng.admission) > 0 else "decode")
        what = [int(eng.pos[i]) + 1 for i in active] if kind == "decode" else 0
        t0 = time.perf_counter()
        with self.spans.span(kind):
            finished = eng.step()
        now = time.perf_counter()
        for r in [r for r in eng.slots if r is not None] + finished:
            n = len(r.output)
            k = self.seen.get(r.rid, 0)
            if n > k:
                if k == 0:
                    what = len(r.prompt)
                self.run.token_times[r.rid].extend([now] * (n - k))
                self.seen[r.rid] = n
        self.run.ticks.append((kind, t0, now, what))


def build_engine(ctx):
    """Weights, the engine, and a warm-up over every corpus length:
    admission prefill and page scatter at that length, then a decode step
    and the sampler.  Returns (engine, session)."""
    from repro.core import Session
    from repro.models import Model
    from repro.serving import InferenceEngine, Request, RequestState

    t, dm = ctx.traffic, ctx.dims
    with ctx.phase("weights"):
        params = jax.block_until_ready(make_params(ctx.mcfg, ctx.seed))
    with ctx.phase("engine"):
        session = Session(hw=ctx.hw) if ctx.hw is not None else Session()
        engine = InferenceEngine(
            Model(ctx.mcfg, use_kernels=True), params,
            max_slots=int(t["slots"]), max_len=int(t["max_len"]),
            paged_kv=True, page_size=int(t["page_size"]), session=session)
        del params
    with ctx.phase("warm_up"):
        rng = np.random.default_rng(0)
        for k, n in enumerate(corpus(t)):
            engine.submit(Request(rid=-1 - k, max_tokens=2, prompt=rng.integers(
                1, dm.vocab, n).tolist()))
        warm = engine.run(max_ticks=10 * len(corpus(t)) + 10)
        bad = [r.rid for r in warm if r.state is not RequestState.DONE]
        if bad:
            raise RuntimeError(f"warm-up requests not DONE: {bad}")
    return engine, session


def open_loop(client, pending, late, t0: float, seconds: float) -> None:
    """Submit each request of ``pending`` when it falls due, and tick the
    engine while it has work, until ``seconds`` after ``t0``."""
    from repro.serving import Request

    engine = client.engine
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            return
        while pending and t0 + pending[0].arrival_s <= now:
            r = pending.popleft()
            late.append(now - t0 - r.arrival_s)
            client.submit(Request(rid=r.rid, prompt=r.prompt,
                                  max_tokens=r.max_tokens))
        if _busy(engine):
            client.tick()
        else:
            nxt = t0 + pending[0].arrival_s if pending else t0 + seconds
            time.sleep(max(0.0, min(nxt, t0 + seconds) - now))


def check_fits(t: dict, dm, reqs) -> None:
    for r in reqs:
        if len(r.prompt) + r.max_tokens >= t["max_len"] or \
                t["max_len"] > dm.max_positions:
            raise ValueError(f"request {r.rid} does not fit max_len "
                             f"{t['max_len']}")


def run_cell(ctx) -> None:
    from repro.serving import Request, RequestState

    t, dm, run = ctx.traffic, ctx.dims, ctx.run
    reqs = requests(t, ctx.seed, ctx.seconds, dm.vocab)
    check_fits(t, dm, reqs)
    engine, session = build_engine(ctx)
    client = Client(engine, run, run.spans)
    pending = collections.deque(reqs)
    if t["load"] == "backlog":
        with ctx.phase("fill_slots"):
            while pending:
                r = pending.popleft()
                client.submit(Request(rid=r.rid, prompt=r.prompt,
                                      max_tokens=r.max_tokens))
            while len(engine.admission) and any(
                    s is None for s in engine.slots):
                client.tick()
    late: list[float] = []

    ctx.measure(lambda: open_loop(client, pending, late, ctx.t_open,
                                  ctx.seconds))
    adm, dec = run.window_ticks("admit"), run.window_ticks("decode")
    ctx.log(f"window: admission ticks {[t1 - t0 for _, t0, t1, _ in adm]!r} s; "
            f"{len(dec)} decode ticks, {sum(t1 - t0 for _, t0, t1, _ in dec)!r} s")
    if late:
        ctx.log(f"open-loop generator lateness: max {max(late)!r} s, "
                f"median {float(np.median(late))!r} s over {len(late)}")
    ctx.read_memory()
    # a request counts as attempted if it was submitted inside the window
    # or had a token in it
    in_window = [rid for rid, ts in run.token_times.items()
                 if client.submitted[rid] >= ctx.t_open
                 or any(x >= ctx.t_open for x in ts)]
    ctx.attempted = len(in_window)
    ctx.failed = sum(client.reqs[rid].state in (
        RequestState.FAILED, RequestState.SHED, RequestState.EXPIRED)
        for rid in in_window)
    fs = engine.fault_stats
    ctx.fallbacks += (fs["watchdog_fallbacks"] + fs["paged_decode_fallbacks"]
                      + len(session.guard_log))
    done = [client.reqs[rid] for rid in in_window
            if client.reqs[rid].state is RequestState.DONE]
    del engine, client
    gc.collect()
    t0 = time.perf_counter()
    check(ctx, done)
    ctx.log(f"the check took {time.perf_counter() - t0!r} s")


def check(ctx, done) -> None:
    """Served greedy tokens against the float32 reference, teacher-forced
    on each sampled request's prompt and served tokens: the widest gap by
    which a served token's reference logit lies below the reference's
    best at that position."""
    t, dm, logits = ctx.traffic, ctx.dims, ctx.arch.logits
    if not done:
        ctx.compare("token_gap", float("inf"))
        return
    k = min(len(done), int(t.get("check_requests", 4)))
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].output))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + sorted(np.random.default_rng(ctx.seed).choice(
        rest, k - 1, replace=False).tolist() if k > 1 else [])
    params = make_params(ctx.mcfg, ctx.seed)
    gaps, ctl, n_tok = [], [], 0
    for i in pick:
        r = done[i]
        toks = list(r.prompt) + list(r.output[:-1])
        pos = np.arange(len(r.prompt) - 1, len(toks))
        ref = logits(params, toks, pos, dm, "f32")
        best = ref.max(-1)
        served = np.asarray(r.output, np.int32)
        gaps.append(float((best - ref[np.arange(len(pos)), served]).max()))
        n_tok += len(pos)
        if ctx.control:
            c = logits(params, toks, pos, dm, "fp8").argmax(-1)
            ctl.append(float((best - ref[np.arange(len(pos)), c]).max()))
        del ref
    ctx.log(f"checked {n_tok} served tokens of {len(pick)} requests "
            f"(longest {len(done[longest].prompt)}+{len(done[longest].output)})")
    ctx.compare("token_gap", max(gaps))
    if ctx.control:
        ctx.log(f"control token_gap {max(ctl)!r}")
