"""Continuous-batching inference engine with an overload-robust admission tier.

vLLM-style slot scheduler shrunk to the essentials, built on the Model
facade's prefill/decode step functions (which are exactly what the dry-run
lowers at production scale):

  * fixed pool of decode slots sharing one stacked KV cache;
  * prefill admission when a slot frees (prefill and decode interleave —
    one engine tick is either one prefill or one batched decode step);
  * per-request sampling params; EOS / max-token completion;
  * deterministic given (seed, arrival order, deadlines).

In front of the slots sits the admission tier (:mod:`.admission`): a
bounded, per-tenant-quota queue with EDF/priority batch assembly,
load-shedding (terminal ``SHED``), deadline expiry of queued *and* running
requests, and priority preemption of a running request when a
higher-priority one would otherwise miss its deadline.  All of it runs on
the engine's deterministic **tick clock** — no wall time — and every
decision is recorded in ``fault_stats`` (global + per-tenant) and on
``Request.error``.  ``run()`` guarantees every submitted request ends in a
terminal state: leftovers at tick-budget exhaustion are expired, never
silently stranded.

Batched decode across slots is itself operator parallelism — every slot's
decode operators fuse into one wave, so the engine's throughput benefits
from the same horizontal batching Opara applies inside a graph.

``calibrate_schedule()`` ties the engine into the measured-profile
calibration cache of its :class:`repro.core.Session`: the engine's step
graph is profiled once (real timings), and every subsequent engine instance
/ re-schedule sharing that session with the same model structure, batch
geometry and hardware hydrates from the cache instead of re-timing (paper
§3.2, "profile each DNN inference only once").  Engines default to the
process-wide :func:`repro.core.default_session`; a serving fleet that wants
isolated (or differently configured) schedule state passes its own
``session=Session(SessionConfig(...))`` — and per-*tenant* Sessions via
``tenant_sessions=`` so each tenant's shed/expire/preempt provenance lands
in its own ``guard_log``.
"""
from __future__ import annotations

import copy
import functools
import time
import warnings
import weakref
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..kernels.paged_decode.ref import page_positions, positions_to_pages
from ..models import Model
from ..models.attention import dense_cache, pool_rows, write_positions
from ..runtime import tracing
from ..runtime.faults import FaultInjected, FaultPlan
from ..runtime.faults import get_active as _active_faults
from ..runtime.guard import DegradationWarning
from .admission import (AdmissionConfig, AdmissionQueue, Request,
                        RequestState, TERMINAL_STATES, deadline_critical)
from .kv_pool import (KVPagePool, KVPoolConfig, PageExhausted,
                      page_content_keys)
from .sampler import sample_token

__all__ = ["InferenceEngine", "Request", "RequestState", "AdmissionConfig",
           "TERMINAL_STATES"]

# Executable reuse across engine instances (the serving-side analogue of the
# core compiled-plan cache): a jax.jit wrapper created per-engine would
# retrace the decode program for every new engine even when the model is
# unchanged.  Keyed weakly by the model instance so traces die with it.
_DECODE_JIT_CACHE: "weakref.WeakKeyDictionary[Any, Any]" = weakref.WeakKeyDictionary()


def _cached_decode_fn(model: Model):
    fn = _DECODE_JIT_CACHE.get(model)
    if fn is None:
        # close over a weakref, not the model: a strong ref from the cached
        # value would pin the weak key forever and the entry could never be
        # evicted.  At trace time the model is alive (the engine holds it).
        ref = weakref.ref(model)

        def _step(p, c, t, pos):
            m = ref()
            if m is None:
                # a stale cached fn outliving its model used to surface as
                # an opaque AttributeError on None — diagnose it instead
                raise RuntimeError(
                    "decode step: model was garbage-collected; the cached "
                    "decode fn outlived the model it was traced for — "
                    "rebuild the InferenceEngine with a live model")
            return m.decode(p, t, c, pos)

        fn = jax.jit(_step)
        _DECODE_JIT_CACHE[model] = fn
    return fn


_PAGED_JIT_CACHE: "weakref.WeakKeyDictionary[Any, Any]" = weakref.WeakKeyDictionary()


def _cached_paged_decode_fn(model: Model):
    fn = _PAGED_JIT_CACHE.get(model)
    if fn is None:
        ref = weakref.ref(model)        # same weakref discipline as above

        def _step(p, c, t, bt, pos):
            m = ref()
            if m is None:
                raise RuntimeError(
                    "paged decode step: model was garbage-collected; rebuild "
                    "the InferenceEngine with a live model")
            logits, caches, counters = m.paged_decode(p, t, c, bt, pos)
            # outputs first, the state last, as the dense step returns them
            return (logits, counters), caches

        fn = jax.jit(_step)
        _PAGED_JIT_CACHE[model] = fn
    return fn


def _donated_paged_decode_fn(model: Model):
    """The engine's paged decode step: :func:`_cached_paged_decode_fn`
    jitted once more with its page pools (argument 1) donated, so each
    layer writes its row into them in place and the step hands the same
    buffers back.  The donation wraps the cached step rather than living in
    it, so a step that returns the pools it was given hands back live
    buffers, not deleted ones.  Not cached: jit wrappers of one cached step
    share its traces and executables."""
    return jax.jit(_cached_paged_decode_fn(model), donate_argnums=1)


# The pool's other writers, each one jitted update of the donated pools in
# place (an eager ``.at[].set`` would build a second pool beside the first).

@functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
def _write_pages(caches, rows, pages, first: int):
    """Whole pages ``first .. first + len(pages)`` of a batch-1 dense cache,
    as :func:`pool_rows` gives it (leaves [L, 1, T, KVH, D]), into the pool
    pages ``pages`` of every layer."""
    n = pages.shape[0]

    def write(pool, leaf):
        ps = pool.shape[4]
        x = positions_to_pages(leaf[:, 0, first * ps:(first + n) * ps], ps)
        return pool.at[:, pages].set(x.astype(pool.dtype))

    return jax.tree_util.tree_map(write, caches, rows)


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(caches, rows, pages, offs):
    """One position per row: ``rows`` leaves [L, R, KVH, D] at
    ``(pages[r], offs[r])`` of every layer."""
    return jax.tree_util.tree_map(
        lambda pool, r: write_positions(pool, jnp.moveaxis(r, 1, 0), pages,
                                        offs), caches, rows)


@functools.partial(jax.jit, donate_argnums=0)
def _copy_page(caches, dst, src):
    """Page ``src`` duplicated into page ``dst`` across every layer."""
    return jax.tree_util.tree_map(
        lambda pool: pool.at[:, dst].set(pool[:, src]), caches)


def _buffer_ptrs(tree) -> list[int]:
    return [x.unsafe_buffer_pointer() for x in jax.tree_util.tree_leaves(tree)]


def _batch_sampler():
    """One jitted sampler over the whole decode batch, so a tick dispatches
    once and reads its tokens back in one host read.  Row ``i`` draws what
    ``sample_token`` draws on that row alone with key ``fold_in(sub, i)``
    and the slot's temperature (0 is greedy), so the token streams are
    those of sampling slot by slot.  ``drawn`` (static) is False when every
    temperature is 0, and the program then takes the argmax alone.  Built
    per engine, so it traces ``sample_token`` as it stands when the engine
    is made."""

    def sample(rng, logits, temps, drawn):
        rng, sub = jax.random.split(rng)
        tokens = sample_token(logits, sub)
        if drawn:
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                sub, jnp.arange(logits.shape[0]))
            scaled = logits / jnp.where(temps > 0, temps, 1.0).astype(
                logits.dtype)[:, None]
            t = jax.vmap(lambda k, row: sample_token(row[None], k, 1.0)[0])(
                keys, scaled)
            tokens = jnp.where(temps > 0, t, tokens)
        return rng, tokens, jnp.isfinite(logits).all(axis=-1)

    return jax.jit(sample, static_argnums=3)


def _empty_tenant_stats() -> dict[str, int]:
    return {"submitted": 0, "done": 0, "failed": 0, "shed": 0,
            "expired": 0, "preempted": 0}


class InferenceEngine:
    def __init__(self, model: Model, params, max_slots: int = 4,
                 max_len: int = 512, seed: int = 0, calibrate: bool = False,
                 session=None, fault_plan: FaultPlan | None = None,
                 admission: AdmissionConfig | None = None,
                 watchdog_probation: int = 8,
                 tenant_sessions: Mapping[str, Any] | None = None,
                 paged_kv: bool = False, page_size: int = 16,
                 num_pages: int | None = None, prefix_sharing: bool = False,
                 page_bounce_limit: int = 8):
        self.model = model
        self.params = params
        # repro.core.Session owning this engine's schedule/calibration cache
        # state (None → the process-wide default session, so engines share
        # measured profiles the way the module-global caches used to).
        self.session = session
        # per-tenant Sessions (PR 4 isolation): shed/expire/preempt events
        # for a tenant's requests are noted on that tenant's guard_log, so
        # fleets can surface per-tenant degradation provenance
        self.tenant_sessions = dict(tenant_sessions or {})
        # per-engine injection plan (None → $REPRO_FAULT_PLAN, if armed)
        self.fault_plan = fault_plan
        # watchdog latch: once the jitted decode step fails, ticks run the
        # eager (uncompiled, sequential-semantics) step.  After
        # ``watchdog_probation`` clean eager ticks the jitted step is
        # retried ONCE (probation rung); 0 disables probation — the PR 6
        # latch-forever behavior.
        self._use_compiled = True
        self.watchdog_probation = watchdog_probation
        self._eager_clean_ticks = 0
        self.fault_stats = {"decode_faults": 0, "failed_requests": 0,
                            "watchdog_fallbacks": 0, "watchdog_probations": 0,
                            "shed_requests": 0, "expired_requests": 0,
                            "preemptions": 0, "admission_faults": 0,
                            "preempt_faults": 0, "deadline_faults": 0,
                            "page_exhaustions": 0, "page_alloc_faults": 0,
                            "block_table_faults": 0, "page_release_faults": 0,
                            "paged_decode_fallbacks": 0, "page_resumes": 0,
                            "resumed_tokens": 0, "reprefilled_tokens": 0,
                            "by_tenant": {}}
        self.cfg: ModelConfig = model.cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.rng = jax.random.key(seed)
        # deterministic tick clock: one step() == one tick.  Deadlines/TTLs
        # are expressed in ticks — nothing in the overload machinery reads
        # wall time, so every shed/preempt/expire decision replays.
        self.tick = 0
        # admission tier (defaults reproduce the legacy unbounded FIFO for
        # deadline-free single-priority traffic)
        self.admission_cfg = admission if admission is not None \
            else AdmissionConfig()
        self.admission = AdmissionQueue(self.admission_cfg)
        self.accepting = True            # drain() closes admission
        self._terminal: list[Request] = []   # terminal before reaching a slot
        self.slots: list[Request | None] = [None] * max_slots
        self.pos = np.zeros(max_slots, np.int32)
        self.last_token = np.zeros(max_slots, np.int32)
        # paged KV tier: fixed pages + block tables instead of a dense slab.
        # Unsupported combinations degrade to the dense slab with provenance
        # rather than erroring — the ladder's usual posture.
        self.paged = False
        self.prefix_sharing = prefix_sharing
        self.page_bounce_limit = page_bounce_limit
        self.pool: KVPagePool | None = None
        if paged_kv:
            reason = None
            if not model.supports_paged():
                reason = (f"family {self.cfg.family!r} carries recurrent or "
                          "cross-attention state; paged KV needs a "
                          "pure-attention decoder stack")
            else:
                from ..flags import kv_quant
                if kv_quant() and self.cfg.mla is not None:
                    reason = ("kv_quant int8 latent cache is dense-only; "
                              "paged MLA pages the bf16 latent")
            if reason is not None:
                warnings.warn(f"paged_kv unavailable: {reason}; "
                              "using the dense slab cache",
                              DegradationWarning, stacklevel=2)
                if self.session is not None:
                    self.session.note_degradation(
                        "paged_kv", "paged->dense", reason, warn=False)
            else:
                self.paged = True
        if self.paged:
            cache_len = max_len + self.cfg.meta_tokens
            self._pages_per_req = -(-cache_len // page_size)
            if num_pages is None:
                # null page + a full allocation per slot (capacity parity
                # with the dense slab; pass a smaller pool to overcommit)
                num_pages = 1 + max_slots * self._pages_per_req
            self.pool = KVPagePool(KVPoolConfig(num_pages, page_size))
            self.caches = model.init_paged_caches(num_pages, page_size)
            self._paged_decode = _donated_paged_decode_fn(model)
            self._page_bounces: dict[str, int] = {}
        else:
            from ..models.transformer import init_decode_caches
            cache_len = max_len + self.cfg.meta_tokens
            self.caches = init_decode_caches(self.cfg, max_slots, cache_len)
        self._decode = _cached_decode_fn(model)
        self._sample = _batch_sampler()
        self._step_attrs = {}        # the last decode tick's span attributes
        # Measured-mode Opara schedule of this engine's step graph, filled by
        # calibrate_schedule().  Engines for the same (model structure, batch
        # geometry, hardware) share one measured profile via the core
        # calibration cache — the first engine times once, later engines and
        # re-schedules hydrate and hit the warm plan-cache path.
        self.schedule_plan = None
        if calibrate:
            self.calibrate_schedule()

    @property
    def queue(self) -> list[Request]:
        """Read-only view of the queued (PENDING) requests, in arrival
        order — the legacy attribute, now backed by the admission tier."""
        return list(self.admission)

    def calibrate_schedule(self, seq: int = 1, n_layers: int | None = None,
                           repeats: int = 1):
        """(Re-)schedule this engine's step graph with measured timings.

        Exports the model's operator DAG at this engine's decode geometry
        (batch = ``max_slots``), binds zero tokens as profiling inputs, and
        plans through this engine's :class:`repro.core.Session` — so the
        single profiling inference is amortized across every engine sharing
        the session with an identical signature (the paper's "profile each
        DNN inference only once").

        The returned plan (also kept on ``self.schedule_plan``) is
        introspection/analysis state — stream assignment, launch order and
        waves over REAL timings for this engine's step, feeding the
        simulator and benchmarks.  The decode hot path itself keeps
        executing through the jitted step function (XLA already fuses the
        batched decode); the calibration's runtime win is that re-planning
        costs a cache lookup instead of a profiling inference.
        """
        from ..core.session import default_session
        from ..models.opgraph_export import build_lm_opgraph

        sess = self.session if self.session is not None else default_session()
        g = build_lm_opgraph(self.cfg, batch=self.max_slots, seq=seq,
                             params=self.params, n_layers=n_layers)
        # measured calibration replays the graph, so every non-input node
        # needs a payload.  Dense and MoE exports (routed ragged fan-out)
        # are fully payload-backed; cost-only operators without shapes
        # (hybrid mamba, rwkv scan) cannot be bound as profiling inputs —
        # degrade to the analytic cost model (one structured warning +
        # ``cache_stats()["calib_degraded_analytic"]``) instead of failing
        # the serve launch with a shape error.
        unbindable = [n.name for n in g
                      if n.fn is None and n.out_shape is None]
        if unbindable:
            sess.note_degradation(
                "calibration_measure", "measured->analytic",
                f"{self.cfg.name!r} exports {len(unbindable)} cost-only "
                f"operators without payloads (e.g. {unbindable[0]!r}); "
                "scheduling on analytic costs")
            self.schedule_plan = sess.plan(g)
            return self.schedule_plan
        inputs = {n.op_id: jnp.zeros(n.out_shape, jnp.int32)
                  for n in g if n.fn is None}
        sess.calibrate(g, inputs, repeats=repeats)
        self.schedule_plan = sess.plan(g)
        return self.schedule_plan

    # -- faults / provenance plumbing ---------------------------------------------
    def _faults(self) -> FaultPlan | None:
        return (self.fault_plan if self.fault_plan is not None
                else _active_faults())

    def _tenant_stats(self, tenant: str) -> dict[str, int]:
        stats = self.fault_stats["by_tenant"].get(tenant)
        if stats is None:
            stats = self.fault_stats["by_tenant"][tenant] = \
                _empty_tenant_stats()
        return stats

    def _tenant_note(self, req: Request, site: str, action: str,
                     reason: str) -> None:
        """Per-tenant degradation provenance: the tenant's Session (if the
        fleet registered one) records the event on ITS guard_log, so tenant
        dashboards see their own shed/expire/preempt history in isolation."""
        sess = self.tenant_sessions.get(req.tenant)
        if sess is not None:
            sess.note_degradation(site, action, reason, warn=False)

    # -- terminal transitions -----------------------------------------------------
    def _fail(self, req: Request, reason: str) -> Request:
        """Terminal eviction of ONE poisoned request; co-batched requests
        are untouched (their slots, caches and positions stay live)."""
        req.state = RequestState.FAILED
        req.error = reason
        req.finish_tick = self.tick
        self.fault_stats["failed_requests"] += 1
        self._tenant_stats(req.tenant)["failed"] += 1
        self._release_pages(req)
        return req

    def _shed(self, req: Request, reason: str) -> Request:
        """Terminal refusal at the admission tier (load shedding)."""
        req.state = RequestState.SHED
        req.error = reason
        req.finish_tick = self.tick
        self.fault_stats["shed_requests"] += 1
        self._tenant_stats(req.tenant)["shed"] += 1
        self._tenant_note(req, "admission_enqueue", "admit->shed", reason)
        self._release_pages(req)
        return req

    def _expire(self, req: Request, reason: str) -> Request:
        """Terminal deadline/tick-budget expiry (queued or running)."""
        req.state = RequestState.EXPIRED
        req.error = reason
        req.finish_tick = self.tick
        self.fault_stats["expired_requests"] += 1
        self._tenant_stats(req.tenant)["expired"] += 1
        self._tenant_note(req, "deadline_check", "request->expired", reason)
        self._release_pages(req)
        return req

    def _complete(self, req: Request) -> Request:
        req.state = RequestState.DONE
        req.finish_tick = self.tick
        self._tenant_stats(req.tenant)["done"] += 1
        self._release_pages(req)
        return req

    def _release_pages(self, req: Request) -> None:
        """Free ``req``'s KV pages on ANY terminal transition (preemption is
        not terminal — a preempted request keeps its pages and resumes
        without re-prefill).  An injected ``page_release`` fault models a
        lost free: the pages leak (counted, capacity shrinks) instead of
        corrupting the free list."""
        if not self.paged or not self.pool.holds(req.rid):
            return
        faults = self._faults()
        if faults is not None:
            try:
                faults.fire("page_release")
            except FaultInjected as exc:
                self.fault_stats["page_release_faults"] += 1
                n = self.pool.leak(req.rid)
                reason = f"{exc}: {n} pages leaked"
                self._tenant_note(req, "page_release", "release->leaked", reason)
                if self.session is not None:
                    self.session.note_degradation(
                        "page_release", "release->leaked", reason, warn=False)
                self._page_bounces.pop(req.rid, None)
                return
        self.pool.release(req.rid)
        self._page_bounces.pop(req.rid, None)

    def _clear_slot(self, slot: int) -> None:
        self.slots[slot] = None
        self.pos[slot] = 0
        self.last_token[slot] = 0

    # -- API ---------------------------------------------------------------------
    def submit(self, req: Request) -> Request:
        """Offer ``req`` to the admission tier.

        May immediately take the request terminal: SHED (queue bound,
        tenant quota, draining engine, injected admission fault) or FAILED
        (prompt exceeds the KV capacity).  Terminal-at-submit requests are
        still returned by ``run()``/``step()`` — nothing vanishes.
        """
        if req.submit_tick < 0:
            req.submit_tick = self.tick
        if req.deadline is None and req.ttl is not None:
            req.deadline = req.submit_tick + req.ttl
        self._tenant_stats(req.tenant)["submitted"] += 1
        if not self.accepting:
            self._terminal.append(
                self._shed(req, "engine draining: admission closed"))
            return req
        faults = self._faults()
        if faults is not None:
            try:
                faults.fire("admission_enqueue")
            except FaultInjected as exc:
                # overload ladder: an admission-path fault sheds THIS
                # request with provenance instead of crashing the engine
                self.fault_stats["admission_faults"] += 1
                self._terminal.append(self._shed(req, f"{exc}"))
                return req
        # KV-capacity check at admission (not at slot time): a prompt that
        # cannot fit the slot cache used to be spliced anyway — pos[slot]
        # started out of bounds and decode writes silently clamped.  Reject
        # with a diagnosis; need >= 1 decode position after the prompt.
        n_tokens = len(req.prompt) + len(req.output)
        if n_tokens >= self.max_len:
            self._terminal.append(self._fail(req, (
                f"prompt length {n_tokens} exceeds KV capacity "
                f"(max_len={self.max_len} incl. at least one decode "
                "position); rejected at admission")))
            return req
        admitted, shed, reason = self._offer(req)
        for victim in shed:
            self._terminal.append(self._shed(victim, reason))
        return req

    def _offer(self, req: Request):
        """``admission.offer`` that stamps the time ``req`` entered the
        queue."""
        req.queued_ns = time.perf_counter_ns() if tracing.enabled() else None
        return self.admission.offer(req, self.tick)

    def run(self, max_ticks: int = 1000) -> list[Request]:
        """Tick until all work is terminal or ``max_ticks`` is exhausted.

        On tick-budget exhaustion every queued/running leftover is expired
        with ``error="tick budget exhausted"`` — no request ever silently
        vanishes; the returned list covers every submitted request.
        """
        done: list[Request] = []
        for _ in range(max_ticks):
            if not self._work_pending():
                break
            done.extend(self.step())
        done.extend(self._drain_terminal())
        leftovers = self.admission.clear()
        for i, req in enumerate(self.slots):
            if req is not None:
                leftovers.append(req)
                self._clear_slot(i)
        for req in leftovers:
            done.append(self._expire(req, "tick budget exhausted"))
        return done

    def drain(self, max_ticks: int = 1000) -> list[Request]:
        """Engine lifecycle: close admission and finish in-flight work so a
        fleet can rotate this engine out safely.  Requests submitted after
        ``drain()`` begins are shed with a "draining" diagnosis."""
        self.accepting = False
        return self.run(max_ticks)

    def health(self) -> dict[str, Any]:
        """Structured liveness/pressure snapshot for fleet managers."""
        running = sum(1 for s in self.slots if s is not None)
        return {
            "tick": self.tick,
            "accepting": self.accepting,
            "queued": len(self.admission),
            "queued_by_tenant": self.admission.depth_by_tenant(),
            "running": running,
            "free_slots": self.max_slots - running,
            "compiled_decode": self._use_compiled,
            "paged": self.pool.health() if self.paged else None,
            "kv_cache_bytes": self.kv_cache_bytes(),
            "fault_stats": copy.deepcopy(self.fault_stats),
        }

    def kv_cache_bytes(self) -> int:
        """Total bytes held by the KV cache (dense slab or page pool)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self.caches))

    # -- one tick -----------------------------------------------------------------
    def step(self) -> list[Request]:
        with tracing.span("engine.step") as sp:
            self.tick += 1
            out = self._drain_terminal()
            out.extend(self._deadline_sweep())
            free = [i for i, s in enumerate(self.slots) if s is None]
            if tracing.enabled():
                sp.set(active=self.max_slots - len(free))
                if self.paged:
                    sp.set(used_pages=self.pool.used_pages,
                           free_pages=self.pool.free_pages)
            if free and len(self.admission):
                req = self.admission.pop_next()
                if req.queued_ns is not None:
                    tracing.record("engine.queued", req.queued_ns,
                                   time.perf_counter_ns(), rid=req.rid)
                sp.set(kind="admit")
                out.extend(self._admit(free[0], req))
                return out
            sp.set(kind="decode" if len(free) < self.max_slots else "idle")
            if not free and len(self.admission) \
                    and self.admission_cfg.preemption:
                out.extend(self._maybe_preempt())
            self._step_attrs = {}
            out.extend(self._paged_decode_tick() if self.paged
                       else self._decode_tick())
            if self._step_attrs:
                sp.set(**self._step_attrs)
        return out

    def _work_pending(self) -> bool:
        return bool(len(self.admission) or self._terminal
                    or any(s is not None for s in self.slots))

    def _drain_terminal(self) -> list[Request]:
        out, self._terminal = self._terminal, []
        return out

    def _deadline_sweep(self) -> list[Request]:
        """Expire queued requests that can no longer meet their deadline
        and evict running requests whose deadline has passed (reusing the
        per-slot eviction path — co-batched slots stay live)."""
        out: list[Request] = []
        faults = self._faults()
        if faults is not None:
            try:
                faults.fire("deadline_check")
            except FaultInjected:
                # ladder: a faulted sweep skips ONE tick of expiry — every
                # request simply lives one tick longer; nothing crashes
                self.fault_stats["deadline_faults"] += 1
                return out
        for req, reason in self.admission.expire(self.tick):
            out.append(self._expire(req, reason))
        if self.admission_cfg.expire_running:
            for i, req in enumerate(self.slots):
                if req is None or req.deadline is None:
                    continue
                if self.tick > req.deadline:
                    self._clear_slot(i)
                    out.append(self._expire(req, (
                        f"deadline {req.deadline} passed at tick "
                        f"{self.tick} with {len(req.output)} tokens "
                        "generated; slot evicted")))
        return out

    def _maybe_preempt(self) -> list[Request]:
        """Evict the least-important running request when the most urgent
        queued one is deadline-critical and strictly higher priority.  The
        victim returns to the queue PENDING (output retained — it resumes
        by re-prefilling prompt+output on re-admission)."""
        cand = self.admission.peek()
        if cand is None or not deadline_critical(cand, self.tick):
            return []
        running = [(i, req) for i, req in enumerate(self.slots)
                   if req is not None]
        if not running:
            return []
        # least important victim: lowest priority, then most deadline
        # slack (None = infinite), then lowest slot index — deterministic
        slot, victim = min(
            running,
            key=lambda it: (it[1].priority,
                            -(float("inf") if it[1].deadline is None
                              else float(it[1].deadline)), it[0]))
        if victim.priority >= cand.priority:
            return []
        faults = self._faults()
        if faults is not None:
            try:
                faults.fire("slot_preempt")
            except FaultInjected:
                # ladder: a faulted preemption is skipped — the critical
                # request waits (and may expire), the victim keeps running
                self.fault_stats["preempt_faults"] += 1
                return []
        self._clear_slot(slot)
        victim.state = RequestState.PENDING
        victim.preemptions += 1
        self.fault_stats["preemptions"] += 1
        self._tenant_stats(victim.tenant)["preempted"] += 1
        reason = (f"slot {slot} preempted at tick {self.tick} for "
                  f"rid={cand.rid} (priority {cand.priority} > "
                  f"{victim.priority}, deadline {cand.deadline})")
        self._tenant_note(victim, "slot_preempt", "running->requeued", reason)
        admitted, shed, shed_reason = self._offer(victim)
        for req in shed:
            self._terminal.append(
                self._shed(req, f"preempted then {shed_reason}"))
        return []

    def _admit(self, slot: int, req: Request) -> list[Request]:
        req.state = RequestState.RUNNING
        if not req.prompt:
            return [self._fail(req, "empty prompt")]
        # a preempted request resumes by replaying prompt + generated
        # tokens as the prefill stream; generation continues where it left
        # off (same math — the KV it lost is rebuilt, not approximated)
        tokens_list = list(req.prompt) + list(req.output)
        if len(tokens_list) >= self.max_len:
            # unreachable for requests that passed the submit-time check
            # (a preempted slot always sits below max_len - 1), but a
            # silent out-of-bounds splice must never come back
            return [self._fail(req, (
                f"token stream length {len(tokens_list)} exceeds KV "
                f"capacity (max_len={self.max_len}) at slot admission"))]
        if self.paged:
            return self._admit_paged(slot, req, tokens_list)
        if req.output:
            # a dense re-admission rebuilds the whole KV from scratch —
            # count the re-prefilled tokens so the paged path's zero here
            # is a measurable win, not an assertion
            self.fault_stats["reprefilled_tokens"] += len(tokens_list)
        first, cache = self._prefill_first(
            req, tokens_list, self.max_len + self.cfg.meta_tokens)
        if first is None:
            return [self._fail(req, cache)]
        req.output.append(first)
        if (req.eos_id is not None and first == req.eos_id) \
                or len(req.output) >= req.max_tokens:
            return [self._complete(req)]
        # splice the single-request cache into the shared slot cache
        with tracing.span("engine.admit.scatter"):
            self.caches = jax.tree_util.tree_map(
                lambda big, small: _splice(big, small, slot), self.caches,
                cache)
        self.slots[slot] = req
        self.pos[slot] = len(tokens_list)
        self.last_token[slot] = first
        return []

    def _prefill_first(self, req: Request, tokens_list: list[int],
                       cache_len: int):
        """Prefill ``tokens_list`` and sample the request's next token.
        Returns (token, batch-1 cache), or (None, why it failed)."""
        tokens = jnp.asarray([tokens_list], jnp.int32)
        with tracing.span("engine.admit.prefill") as sp:
            before = tracing.compiles() if tracing.enabled() else None
            try:
                logits, cache, counters = self.model.prefill(
                    self.params, {"tokens": tokens}, cache_len=cache_len)
            except Exception as exc:
                # a poisoned prompt must not take the engine down — the
                # queue keeps draining and the decode batch never saw it
                return None, f"prefill failed: {exc!r}"
            logits_h, held = _read(logits, counters)
            finite = bool(np.isfinite(logits_h).all())
            if before is not None:
                # the eager prefill re-traces its scan on every admission
                sp.set(tokens=len(tokens_list),
                       **tracing.compiles_since(before), **held)
        if not finite:
            return None, "prefill produced non-finite logits"
        with tracing.span("engine.admit.sample"):
            self.rng, sub = jax.random.split(self.rng)
            first = int(sample_token(logits, sub, req.temperature)[0])
        return first, cache

    # -- paged KV path ------------------------------------------------------------
    def _admit_paged(self, slot: int, req: Request,
                     tokens_list: list[int]) -> list[Request]:
        """Paged admission: allocate pages, prefill, scatter into pages.

        A preempted request that still holds pages takes the resume
        fast-path — no re-prefill, its KV never left the pool."""
        if self.pool.holds(req.rid) and req.output:
            return self._resume_paged(slot, req, tokens_list)
        ps = self.pool.page_size
        meta = self.cfg.meta_tokens
        n_pos = len(tokens_list) + meta
        had_output = bool(req.output)
        faults = self._faults()
        keys = None
        shared = 0
        if self.prefix_sharing and not req.output:
            keys = page_content_keys(self.cfg.name, ps, tokens_list, meta)
            shared = self.pool.adopt_shared(req.rid, keys, req.tenant)
        try:
            if faults is not None:
                faults.fire("page_alloc")
            self.pool.ensure(req.rid, n_pos, req.tenant)
        except FaultInjected as exc:
            self.fault_stats["page_alloc_faults"] += 1
            return self._page_pressure(req, f"{exc}")
        except PageExhausted as exc:
            self.fault_stats["page_exhaustions"] += 1
            return self._page_pressure(req, str(exc))
        # page-aligned dense intermediate so the scatter below covers every
        # written position without bounds logic
        first, cache = self._prefill_first(req, tokens_list,
                                           self._pages_per_req * ps)
        if first is None:
            return [self._fail(req, cache)]
        req.output.append(first)
        if had_output:
            self.fault_stats["reprefilled_tokens"] += len(tokens_list)
        if (req.eos_id is not None and first == req.eos_id) \
                or len(req.output) >= req.max_tokens:
            return [self._complete(req)]
        with tracing.span("engine.admit.scatter"):
            self._scatter_pages(req, cache, n_pos, skip_pages=shared)
        if keys is not None:
            self.pool.publish_keys(req.rid, keys)
        self.slots[slot] = req
        self.pos[slot] = len(tokens_list)
        self.last_token[slot] = first
        return []

    def _resume_paged(self, slot: int, req: Request,
                      tokens_list: list[int]) -> list[Request]:
        """Resume a preempted request from its retained pages: restore slot
        state and decode ONE token (the tick a dense engine would spend
        re-prefilling).  Other slots' page writes during the batched step
        are value-identical to next tick's — idempotent."""
        pos_i = len(tokens_list) - 1
        wp = pos_i + self.cfg.meta_tokens
        faults = self._faults()
        try:
            if faults is not None:
                faults.fire("page_alloc")
            self.pool.ensure(req.rid, wp + 1, req.tenant)
            page, copy_src = self.pool.writable_page(req.rid, wp)
        except FaultInjected as exc:
            self.fault_stats["page_alloc_faults"] += 1
            return self._page_pressure(req, f"{exc}")
        except PageExhausted as exc:
            self.fault_stats["page_exhaustions"] += 1
            return self._page_pressure(req, str(exc))
        if copy_src is not None:
            self._copy_page(page, copy_src)
        self.slots[slot] = req
        self.pos[slot] = pos_i
        self.last_token[slot] = tokens_list[-1]
        token = jnp.asarray(self.last_token)
        pos = jnp.asarray(self.pos)
        logits = None
        try:
            if faults is not None:
                faults.fire("block_table_build")
            bt = jnp.asarray(self._block_table_array())
            (logits, _), self.caches = self._paged_decode(
                self.params, self.caches, token, bt, pos)
        except Exception as exc:
            logits = self._paged_fallback(exc)
            if logits is None:
                self._clear_slot(slot)
                return [self._fail(
                    req, f"paged resume decode failed: {exc!r}")]
        if not bool(np.isfinite(np.asarray(logits[slot])).all()):
            self._clear_slot(slot)
            return [self._fail(req, "resume decode produced non-finite logits")]
        self.rng, sub = jax.random.split(self.rng)
        nxt = int(sample_token(logits[slot:slot + 1], sub,
                               req.temperature)[0])
        req.output.append(nxt)
        self.fault_stats["page_resumes"] += 1
        self.fault_stats["resumed_tokens"] += len(tokens_list)
        hit_eos = req.eos_id is not None and nxt == req.eos_id
        if hit_eos or len(req.output) >= req.max_tokens \
                or pos_i + 1 >= self.max_len - 1:
            self._clear_slot(slot)
            return [self._complete(req)]
        self.pos[slot] = pos_i + 1
        self.last_token[slot] = nxt
        return []

    def _page_pressure(self, req: Request, reason: str) -> list[Request]:
        """Page exhaustion / allocation fault: release what the request
        held and feed it back to the admission tier (the queue's shed and
        quota machinery owns the overload decision).  A request that
        bounces past ``page_bounce_limit`` — or that cannot fit even an
        empty pool — is shed."""
        self.pool.release(req.rid)       # direct: pressure, not a fault site
        bounces = self._page_bounces.get(req.rid, 0) + 1
        self._page_bounces[req.rid] = bounces
        if bounces > self.page_bounce_limit or not self.pool.holders():
            self._page_bounces.pop(req.rid, None)
            return [self._shed(req, (
                f"page pressure: {reason} "
                f"(bounced {bounces}x, limit {self.page_bounce_limit})"))]
        req.state = RequestState.PENDING
        self._tenant_note(req, "page_alloc", "running->requeued", reason)
        admitted, shed, shed_reason = self._offer(req)
        return [self._shed(victim, f"page pressure requeue: {shed_reason}")
                for victim in shed]

    def _scatter_pages(self, req: Request, cache, n_pos: int,
                       skip_pages: int = 0) -> None:
        """Write a batch-1 dense prefill cache (page-aligned, zeros past
        the prompt) into this request's pages, whole pages at a time
        (skipping pages adopted via prefix sharing — already resident)."""
        table = self.pool.table(req.rid)
        n = -(-n_pos // self.pool.page_size)
        if n <= skip_pages:
            return
        rows = [pool_rows(self.cfg, c) for c in cache]
        self.caches = _write_pages(
            self.caches, rows, jnp.asarray(table[skip_pages:n], jnp.int32),
            skip_pages)

    def _copy_page(self, dst: int, src: int) -> None:
        """Copy-on-write materialization: duplicate page ``src`` into the
        freshly allocated ``dst`` across every layer's leaves."""
        self.caches = _copy_page(self.caches, np.int32(dst), np.int32(src))

    def _block_table_array(self) -> np.ndarray:
        """[max_slots, pages_per_req] int32; unused entries point at the
        null page 0 (decode masks by length, never by table bounds)."""
        bt = np.zeros((self.max_slots, self._pages_per_req), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            table = self.pool.table(req.rid)
            bt[i, :len(table)] = table[:self._pages_per_req]
        return bt

    def _paged_decode_tick(self) -> list[Request]:
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        out: list[Request] = []
        faults = self._faults()
        still = []
        err = ptrs = None
        with tracing.span("engine.decode.prepare"):
            for i in active:
                req = self.slots[i]
                wp = int(self.pos[i]) + self.cfg.meta_tokens
                try:
                    if faults is not None:
                        faults.fire("page_alloc")
                    self.pool.ensure(req.rid, wp + 1, req.tenant)
                    page, copy_src = self.pool.writable_page(req.rid, wp)
                except FaultInjected as exc:
                    self.fault_stats["page_alloc_faults"] += 1
                    self._clear_slot(i)
                    out.extend(self._page_pressure(req, f"{exc}"))
                    continue
                except PageExhausted as exc:
                    self.fault_stats["page_exhaustions"] += 1
                    self._clear_slot(i)
                    out.extend(self._page_pressure(req, str(exc)))
                    continue
                if copy_src is not None:
                    self._copy_page(page, copy_src)
                still.append(i)
            if still:
                token = jnp.asarray(self.last_token)
                pos = jnp.asarray(self.pos)
                if tracing.enabled():       # the pools the step will donate
                    ptrs = _buffer_ptrs(self.caches)
                try:
                    if faults is not None:
                        faults.fire("block_table_build")
                    bt = jnp.asarray(self._block_table_array())
                except Exception as exc:
                    err = exc
        if not still:
            return out
        if err is None:
            try:
                with tracing.span("engine.decode.dispatch"):
                    # rebound before anything can raise: the step donated
                    # the pools it was given
                    (logits, counters), self.caches = self._paged_decode(
                        self.params, self.caches, token, bt, pos)
                    if faults is not None:
                        logits = faults.fire("decode_step", payload=logits)
            except Exception as exc:
                err = exc
        if err is not None:
            counters = {}
            logits = self._paged_fallback(err)
            if logits is None:
                for i in still:
                    req = self.slots[i]
                    self._clear_slot(i)
                    out.append(self._fail(
                        req, f"paged decode failed on both rungs: {err!r}"))
                return out
        out.extend(self._advance_slots(still, logits, counters))
        if err is None and ptrs is not None:
            # read after the tick's host read of its tokens: the step is done
            in_place = _buffer_ptrs(self.caches) == ptrs
            if in_place:
                tracing.count("engine.decode.pool_in_place")
            self._step_attrs["pool_in_place"] = in_place
        return out

    def _paged_fallback(self, exc: Exception):
        """Ladder rung ``paged_decode → dense-gather``: gather the pages
        into a contiguous slab and run the eager dense decode step.  Returns
        logits, or None when the rescue rung itself failed."""
        if isinstance(exc, FaultInjected):
            self.fault_stats["block_table_faults"] += 1
        self.fault_stats["paged_decode_fallbacks"] += 1
        warnings.warn(
            f"paged decode failed ({exc!r}); falling back to the "
            "dense-gather decode step", DegradationWarning, stacklevel=3)
        if self.session is not None:
            self.session.note_degradation(
                "paged_decode", "paged->dense-gather", repr(exc), warn=False)
        try:
            return self._dense_gather_decode()
        except Exception:
            return None

    def _dense_gather_decode(self):
        """Gather every slot's pages into a dense [L,B,T,...] slab, run the
        eager dense decode, write ONLY the newly written position back
        into the pages.  Built without firing fault sites — the rescue rung
        must not re-inject."""
        bt_np = self._block_table_array()
        bt = jnp.asarray(bt_np)
        ps = self.pool.page_size
        dense = [dense_cache(self.cfg, tuple(page_positions(pool[:, bt])
                                             for pool in stack))
                 for stack in self.caches]
        logits, new_dense = self.model.decode(
            self.params, jnp.asarray(self.last_token), dense,
            jnp.asarray(self.pos))
        rows = [i for i, r in enumerate(self.slots) if r is not None]
        if rows:
            wp = np.array([int(self.pos[i]) + self.cfg.meta_tokens
                           for i in rows], np.int32)
            rows_a = np.array(rows, np.int32)
            new_rows = [tuple(leaf[:, rows_a, wp]
                              for leaf in pool_rows(self.cfg, stack))
                        for stack in new_dense]
            self.caches = _write_rows(self.caches, new_rows,
                                      bt_np[rows_a, wp // ps], wp % ps)
        return logits

    def _decode_tick(self) -> list[Request]:
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        with tracing.span("engine.decode.prepare"):
            token = jnp.asarray(self.last_token)
            pos = jnp.asarray(self.pos)
        logits = None
        faults = self._faults()
        if self._use_compiled:
            try:
                with tracing.span("engine.decode.dispatch"):
                    logits, caches = self._decode(self.params, self.caches,
                                                  token, pos)
                    if faults is not None:
                        # raise mode → watchdog; corrupt mode → one poisoned
                        # slot (NaN row), caught per-slot below.  Fired only
                        # on the compiled path so the eager rescue never
                        # re-injects.
                        logits = faults.fire("decode_step", payload=logits)
                self.caches = caches
            except Exception as exc:
                # step watchdog: latch onto the eager (uncompiled) step —
                # the batch keeps draining.  The probation rung below may
                # retry the jitted step after enough clean eager ticks.
                self.fault_stats["decode_faults"] += 1
                self.fault_stats["watchdog_fallbacks"] += 1
                self._use_compiled = False
                self._eager_clean_ticks = 0
                warnings.warn(
                    f"decode watchdog: jitted step failed ({exc!r}); "
                    "falling back to the eager decode step",
                    DegradationWarning, stacklevel=2)
                if self.session is not None:
                    self.session.note_degradation(
                        "decode_step", "jitted->eager", repr(exc), warn=False)
                logits = None
        if logits is None:
            try:
                with tracing.span("engine.decode.dispatch", eager=True):
                    logits, self.caches = self.model.decode(
                        self.params, token, self.caches, pos)
            except Exception as exc:
                # both rungs failed: fail the co-batch explicitly rather
                # than crash mid-tick with slots in limbo
                failed = []
                for i in active:
                    req = self.slots[i]
                    self._clear_slot(i)
                    failed.append(self._fail(
                        req, f"decode failed on both rungs: {exc!r}"))
                return failed
            # probation rung: after N clean eager ticks, un-latch and retry
            # the jitted step once next tick instead of staying eager
            # forever.  If it fails again the watchdog re-latches (counters
            # keep the history); 0 disables probation.
            if not self._use_compiled and self.watchdog_probation > 0:
                self._eager_clean_ticks += 1
                if self._eager_clean_ticks >= self.watchdog_probation:
                    self._use_compiled = True
                    self._eager_clean_ticks = 0
                    self.fault_stats["watchdog_probations"] += 1
                    if self.session is not None:
                        self.session.note_degradation(
                            "decode_step", "eager->jitted (probation)",
                            f"{self.watchdog_probation} clean eager ticks; "
                            "retrying the jitted decode step", warn=False)
        return self._advance_slots(active, logits)

    def _advance_slots(self, active: list[int], logits,
                       counters=None) -> list[Request]:
        """Sampling/completion tail shared by the dense and paged decode
        ticks (one batch sampler, identical rng discipline → identical
        token streams).  ``counters`` are the step's, if it returned any:
        read with the tokens, and kept for the tick's span."""
        temps = np.zeros(len(self.slots), np.float32)
        for i in active:
            temps[i] = self.slots[i].temperature
        # the one place a decode tick waits for the device: the batch's
        # tokens and finiteness flags, with the step's counters
        with tracing.span("engine.decode.wait"):
            self.rng, tokens, finite = self._sample(
                self.rng, logits, temps, bool(temps.any()))
            (tokens, finite_rows), self._step_attrs = _read(
                (tokens, finite), counters)
        with tracing.span("engine.decode.sample"):
            finished: list[Request] = []
            for i in active:
                req = self.slots[i]
                if not bool(finite_rows[i]):
                    # poisoned request: evict THIS slot only; the other
                    # slots' logits and cache rows are intact and keep
                    # decoding
                    self.fault_stats["decode_faults"] += 1
                    finished.append(self._fail(
                        req, "decode produced non-finite logits"))
                    self._clear_slot(i)
                    continue
                t = int(tokens[i])
                req.output.append(t)
                self.pos[i] += 1
                self.last_token[i] = t
                hit_eos = req.eos_id is not None and t == req.eos_id
                if hit_eos or len(req.output) >= req.max_tokens \
                        or self.pos[i] >= self.max_len - 1:
                    finished.append(self._complete(req))
                    self._clear_slot(i)
        return finished


def _read(out, counters):
    """One host read of ``out`` (an array or a tuple of them) and of the
    step's counters, if any: an expert share's ``moe_held`` comes back as
    the span attributes ``moe_pairs_held`` and ``moe_experts_hit`` (else
    the dict is empty)."""
    if not counters:
        return jax.device_get(out), {}
    out_h, (pairs, hit) = jax.device_get((out, counters["moe_held"]))
    return out_h, {"moe_pairs_held": int(pairs), "moe_experts_hit": int(hit)}


def _splice(big, small, slot: int):
    """Insert a batch-1 cache leaf into the shared cache at `slot`.

    Leaves are [L, B, ...] (stacked per layer); `small` comes from a batch-1
    prefill whose sequence axis may be shorter than the slot cache (padded
    by Model.prefill to the engine's max_len).
    """
    if big.ndim != small.ndim:
        raise ValueError(f"cache rank mismatch {big.shape} vs {small.shape}")
    return jax.lax.dynamic_update_index_in_dim(
        big, small[:, 0].astype(big.dtype), slot, axis=1)
