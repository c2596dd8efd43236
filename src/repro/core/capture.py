"""Graph Capturer (paper §3.4) — scheduled DAG → ONE jitted executable.

The CUDA-Graph analogue on TPU is AOT compilation: executing the whole wave
schedule inside a single ``jax.jit`` region removes per-op dispatch exactly
like replaying a captured graph removes kernel-launch overhead.

Two-phase **program compiler** (the Nimble insight — move every scheduling
decision ahead of time so the replay path does zero per-op work):

Phase 1, ``_lower`` (capture time, runs once per plan):
  * every wave is resolved into a flat list of :class:`Step`s — either one
    payload call or one fused stacked call;
  * per-branch constants (weights) of stacked groups are stacked **once**
    into device arrays held *outside* the trace, so re-tracing never
    re-stacks and the jaxpr sees them as hoisted constants;
  * GEMM-kind fusion groups whose payloads declare ``meta["payload"] ==
    "matmul"`` are routed to the ``branch_gemm`` Pallas kernel (interpret
    mode on CPU, MXU tiles on TPU) with a ``vmap`` fallback for
    non-tileable shapes or oversized interpret-mode grids;
  * matmul groups whose branches share ``(K, F)`` but differ in row count
    (the MoE expert fan-out with unequal routed token counts) cannot be
    ``jnp.stack``-ed — they lower to ONE ``grouped_gemm`` step instead:
    branch inputs are concatenated with a capture-time offset table and the
    ragged Pallas kernel walks a tile→group map (ref fallback inside the
    wrapper keeps it a single fused op on non-tileable shapes);
  * each op gets a slot in a flat list environment and each slot a
    precomputed last-use step, so intermediates are dropped as soon as
    they are dead (list indexing replaces dict hashing in the hot loop).

Phase 2, ``run`` (trace/replay): walks the pre-lowered step list — no
grouping decisions, no const re-stacking, no dict lookups.

Execution semantics are unchanged from the wave model:
  * waves run in order;
  * within a wave, fusion groups of size > 1 execute as ONE stacked op
    (batched GEMM / vmapped payload / ragged grouped GEMM) — the
    horizontal-fusion realization of streams;
  * singleton groups run as-is; XLA still sees them inside one program and
    can interleave their DMA with neighbouring waves' compute.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from ..kernels import INTERPRET_GRID_LIMIT as _INTERPRET_GRID_LIMIT
from ..kernels.branch_gemm.ops import grid_steps, launch_grid
from ..runtime import tracing
from ..runtime.faults import FaultPlan, get_active as _active_faults
from ..runtime.guard import DegradationLog
from .fusion import WaveSchedule
from .graph import OpGraph

# Routing targets for a lowered step.
_CALL = "call"                  # single payload call
_VMAP = "vmap"                  # stacked group via vmapped payload
_BRANCH_GEMM = "branch_gemm"    # stacked group via the Pallas fused GEMM
_GROUPED_GEMM = "grouped_gemm"  # ragged-M group via the grouped Pallas GEMM

# _INTERPRET_GRID_LIMIT (imported above): in interpret mode (CPU) a Pallas
# grid is unrolled at trace time; beyond that many grid points the vmap
# fallback compiles and runs faster.  ONE constant shared with the kernel
# wrappers so their internal ref fallbacks agree with the route decision.


@dataclasses.dataclass
class Step:
    """One pre-lowered execution step (all decisions made at capture time)."""

    route: str                          # _CALL | _VMAP | _BRANCH_GEMM |
                                        # _GROUPED_GEMM
    fn: Callable[..., Any] | None       # payload (vmapped for _VMAP)
    arg_slots: tuple                    # _CALL: (slot, ...) positional args
                                        # stacked: per-arg tuple of branch slots
    consts: tuple                       # hoisted constants (stacked: device
                                        # arrays stacked ONCE at capture time)
    out_slots: tuple[int, ...]          # one slot per branch (singles: one)
    free_slots: tuple[int, ...]         # slots dead after this step
    op_ids: tuple[int, ...]             # provenance (tests / debugging)
    group_sizes: tuple[int, ...] = ()   # _GROUPED_GEMM: per-branch row counts
                                        # (the capture-time offset table)
    grid: int | None = None             # _BRANCH_GEMM: kernel grid steps a
                                        # call, set when the program traces


@dataclasses.dataclass
class CapturedGraph:
    """Executable artifact. Call with a dict {input_name: array}."""

    graph: OpGraph
    schedule: WaveSchedule
    input_ids: list[int]
    output_ids: list[int]
    fn: Callable[..., Any]           # python callable (uncompiled)
    jitted: Callable[..., Any]       # jit'd single-program executable
    steps: list[Step] = dataclasses.field(default_factory=list)
    # input names in input_ids order, precomputed at capture time so the
    # replay path does no per-call graph walks
    input_names: tuple[str, ...] = ()
    # capture-time route fallbacks (branch_gemm→vmap, grouped→sequential)
    # plus any call-time jitted→sequential rescue — read by
    # Session.cache_stats()["degraded_routes"] and CompiledModel.explain()
    degradations: DegradationLog = dataclasses.field(
        default_factory=DegradationLog)

    def __post_init__(self) -> None:
        if not self.input_names:
            self.input_names = tuple(
                self.graph.nodes[i].name for i in self.input_ids)

    def __call__(self, inputs: Mapping[str, Any]) -> list[Any]:
        with tracing.span("capture.call"):
            with tracing.span("capture.bind"):
                args = self._bind(inputs)
            try:
                # until the program is enqueued; the caller blocks on it
                with tracing.span("capture.dispatch"):
                    return self.jitted(*args)
            except Exception as exc:
                # bottom rung of the ladder: the compiled program failed to
                # trace/launch — replay per-op in topo order (the
                # differential harness's own ground truth).  If that fails
                # too, the original error was real: surface it, not the
                # fallback's.
                try:
                    outs = run_sequential_uncompiled(self.graph, inputs,
                                                     self.output_ids)
                except Exception:
                    raise exc
                self.degradations.note("execute", "jitted->sequential",
                                       repr(exc), warn=True)
                return outs

    def call_uncompiled(self, inputs: Mapping[str, Any]) -> list[Any]:
        args = self._bind(inputs)
        return self.fn(*args)

    def _bind(self, inputs: Mapping[str, Any]) -> list[Any]:
        args = []
        for name in self.input_names:
            if name not in inputs:
                raise KeyError(f"missing input {name!r}")
            args.append(inputs[name])
        if len(inputs) != len(self.input_names):
            # a typo'd name would otherwise pass silently whenever the real
            # input happens to be bound too — fail loudly instead
            unknown = sorted(set(inputs) - set(self.input_names))
            if unknown:
                raise KeyError(
                    f"unrecognized input name(s) {unknown}; expected "
                    f"{sorted(self.input_names)}")
        return args

    def program_stats(self) -> dict[str, float]:
        """Route counts; once the program has been traced, also
        ``branch_gemm_grid``: the grid steps a call of the traced shapes
        launches across its ``branch_gemm`` steps (the kernel's own tile
        rule, :func:`repro.kernels.branch_gemm.ops.launch_grid`)."""
        routes = [s.route for s in self.steps]
        stats = {
            "n_steps": float(len(self.steps)),
            "n_single": float(routes.count(_CALL)),
            "n_vmap": float(routes.count(_VMAP)),
            "n_branch_gemm": float(routes.count(_BRANCH_GEMM)),
            "n_grouped_gemm": float(routes.count(_GROUPED_GEMM)),
        }
        grids = [s.grid for s in self.steps if s.route == _BRANCH_GEMM]
        if None not in grids:
            stats["branch_gemm_grid"] = float(sum(grids))
        return stats


def _branch_input_shapes(
    graph: OpGraph, group: Sequence[int], arg: int = 0,
) -> list[tuple[int, ...] | None]:
    """Declared ``out_shape`` of each branch's ``arg``-th input producer
    (``None`` where the builder did not declare one)."""
    return [graph.nodes[graph.nodes[g].inputs[arg]].out_shape for g in group]


def _uniform_group(graph: OpGraph, group: Sequence[int]) -> bool:
    """Shared eligibility core for BOTH fused routes (stacked and grouped):
    every op has a payload, the same fuse_sig and arity, and per-branch
    constants of identical shapes AND dtypes (``jnp.stack`` over mixed
    dtypes would silently promote, so the fused group would return a
    different dtype than unfused execution)."""
    if len(group) < 2:
        return False
    first = graph.nodes[group[0]]
    if first.fn is None or first.fuse_sig is None:
        return False
    c0 = first.meta.get("consts", ())
    arity0 = len(first.inputs)
    for g in group:
        n = graph.nodes[g]
        if n.fuse_sig != first.fuse_sig or n.fn is None:
            return False
        if len(n.inputs) != arity0:
            return False
        cg = n.meta.get("consts", ())
        if len(cg) != len(c0):
            return False
        if any(jnp.shape(a) != jnp.shape(b) for a, b in zip(cg, c0)):
            return False
        if any(jnp.result_type(a) != jnp.result_type(b)
               for a, b in zip(cg, c0)):
            return False
    return True


def _stack_consts(graph: OpGraph, group: Sequence[int]) -> tuple:
    """Const hoisting: per-branch constants stacked ONCE at capture time,
    outside the trace — jax.jit sees ready-made device constants."""
    nodes = [graph.nodes[o] for o in group]
    n_consts = len(nodes[0].meta.get("consts", ()))
    return tuple(
        jnp.stack([jnp.asarray(n.meta["consts"][c]) for n in nodes])
        for c in range(n_consts))


def _can_stack(graph: OpGraph, group: Sequence[int]) -> bool:
    """A group is stackable if it is uniform (:func:`_uniform_group`) and
    no two branches *declare* different input shapes (``jnp.stack`` at run
    time needs equal shapes; ragged matmul groups take the grouped route
    instead).

    Contract: branch-varying parameters (weights) must be declared in
    ``meta["consts"]`` — the capturer stacks them alongside the inputs and
    executes ONE fused payload.  Ops whose closures hide differing state
    must leave ``fuse_sig=None``.
    """
    if not _uniform_group(graph, group):
        return False
    for a in range(len(graph.nodes[group[0]].inputs)):
        known = {s for s in _branch_input_shapes(graph, group, a)
                 if s is not None}
        if len(known) > 1:
            return False
    return True


def _gemm_routable(graph: OpGraph, group: Sequence[int]) -> bool:
    """True iff the stacked group can go to the fused branch-GEMM kernel.

    Contract (explicit opt-in, no payload guessing): every node declares
    ``meta["payload"] == "matmul"`` — payload semantics are exactly
    ``x @ w (+ b)`` with ``consts == (w,)`` or ``(w, b)``, ``w.ndim == 2``.
    """
    for g in group:
        n = graph.nodes[g]
        if n.meta.get("payload") != "matmul" or len(n.inputs) != 1:
            return False
        consts = n.meta.get("consts", ())
        if len(consts) not in (1, 2):
            return False
        if jnp.ndim(consts[0]) != 2:
            return False
        if len(consts) == 2 and jnp.ndim(consts[1]) != 1:
            return False
    return True


def _ragged_group_sizes(
    graph: OpGraph, group: Sequence[int],
) -> tuple[int, ...] | None:
    """Per-branch row counts for the grouped ragged-M GEMM route, or
    ``None`` when the group does not qualify.

    Qualifying groups are matmul-marked (``_gemm_routable``) with uniform
    const shapes/dtypes, whose branch inputs all *declare* 2-D
    ``[M_i, K]`` shapes sharing K but differing in at least one M — the
    unequal-token MoE expert fan-out.  Equal-M groups stay on the stacked
    path (``_can_stack``), which is strictly cheaper.
    """
    if not (_gemm_routable(graph, group) and _uniform_group(graph, group)):
        return None
    shapes = _branch_input_shapes(graph, group)
    if any(s is None or len(s) != 2 for s in shapes):
        return None
    k = jnp.shape(graph.nodes[group[0]].meta["consts"][0])[0]
    if any(s[1] != k for s in shapes):
        return None
    sizes = tuple(int(s[0]) for s in shapes)
    if len(set(sizes)) < 2:
        return None   # uniform M: the stacked path handles it
    # mixed input dtypes would promote under jnp.concatenate
    dtypes = {graph.nodes[graph.nodes[g].inputs[0]].out_dtype
              for g in group}
    dtypes.discard(None)
    if len(dtypes) > 1:
        return None
    return sizes


def _pick_gemm_route(w: jax.Array, n_branches: int, gemm_kernel: str,
                     m: int | None = None) -> str:
    """Decide Pallas vs vmap for an eligible GEMM group (capture time).

    The interpret-mode grid estimate runs the SAME tile rule as the
    ``branch_gemm`` wrapper (``grid_steps`` over ``select_tiles``, at the
    weights' dtype), so the decision counts the grid the kernel would
    actually launch — including the M dimension when the branch input
    shape is declared.  ``m=None`` (undeclared shape) counts the grid of
    an 8-row input, a single row tile — an optimistic floor, so a graph
    that wants the exact decision should declare ``out_shape`` on branch
    inputs.  Non-tileable shapes go to the kernel wrapper's einsum-ref
    fallback, which is one fused op with no unrolled grid.
    """
    if gemm_kernel == "vmap":
        return _VMAP
    if gemm_kernel == "pallas":
        return _BRANCH_GEMM
    # "auto": on TPU always take the fused kernel; on CPU (interpret mode)
    # only when the unrolled grid stays small.
    from ..kernels import interpret_mode

    if not interpret_mode():
        return _BRANCH_GEMM
    k, f = w.shape
    # 0 steps where not tileable: the einsum-ref fallback, fused, no grid
    grid_points = grid_steps(n_branches, m if m is not None else 8, k, f,
                             w.dtype.itemsize)
    return _BRANCH_GEMM if grid_points <= _INTERPRET_GRID_LIMIT else _VMAP


def _branch_gemm_step() -> Callable[..., Any]:
    """Build the fused-GEMM callable for one stacked group.

    The executor calls it ``fn(x_stacked, *step.consts)`` — the pre-stacked
    weights ``w: [N, K, F]`` (and optionally bias ``b: [N, F]``) flow in
    through ``Step.consts``.  The input arrives stacked ``x: [N, *batch,
    K]``; batch dims are flattened for the kernel's [N, M, K] @ [N, K, F]
    contract and restored after.
    """
    def fused(x: jax.Array, w: jax.Array, *rest: jax.Array) -> jax.Array:
        from ..kernels.branch_gemm.ops import branch_gemm

        n, k, f = w.shape[0], w.shape[1], w.shape[2]
        batch_shape = x.shape[1:-1]
        out = branch_gemm(x.reshape(n, -1, k), w)
        out = out.reshape((n,) + batch_shape + (f,))
        if rest:  # bias [N, F] broadcast over batch dims
            b = rest[0]
            out = out + b.reshape((n,) + (1,) * len(batch_shape) + (f,))
        return out

    return fused


def _grouped_gemm_step(group_sizes: tuple[int, ...]) -> Callable[..., Any]:
    """Build the ragged fused-GEMM callable for one grouped step.

    The executor calls it ``fn([x_0, ..., x_{N-1}], *step.consts)`` with
    the per-branch 2-D inputs UNstacked (their row counts differ); the fn
    hands the parts straight to the grouped kernel wrapper — which pads
    each to the row tile and concatenates ONCE — and gets one output per
    branch back.  ``group_sizes`` is the capture-time offset table the
    trace-time shapes must honor.
    """
    def fused(xs: Sequence[jax.Array], w: jax.Array,
              *rest: jax.Array) -> list[jax.Array]:
        from ..kernels.grouped_gemm.ops import grouped_gemm_parts

        for x, m in zip(xs, group_sizes):
            assert x.shape[0] == m, (
                f"branch rows {x.shape[0]} != captured size {m}")
        outs = grouped_gemm_parts(list(xs), w)
        if rest:  # per-branch bias [N, F]
            b = rest[0]
            outs = [o + b[i] for i, o in enumerate(outs)]
        return outs

    return fused


def _validate_waves(graph: OpGraph, schedule: WaveSchedule) -> None:
    """The capturer's input contract, packer-agnostic: waves must partition
    the graph and every producer must sit in a strictly earlier wave.  Both
    :func:`repro.core.fusion.build_waves` and ``repack_waves`` guarantee
    this; the check catches hand-built or corrupted schedules before they
    lower into a program that reads uninitialized slots."""
    wave_of: dict[int, int] = {}
    for w in schedule.waves:
        for op in w.op_ids:
            if op in wave_of:
                raise ValueError(f"op {op} appears in waves {wave_of[op]} "
                                 f"and {w.index}")
            wave_of[op] = w.index
    if set(wave_of) != set(graph.nodes):
        missing = set(graph.nodes) - set(wave_of)
        raise ValueError(f"wave schedule does not cover ops {sorted(missing)[:5]}")
    for node in graph:
        for p in node.inputs:
            if wave_of[p] >= wave_of[node.op_id]:
                raise ValueError(
                    f"dependency {p}->{node.op_id} not satisfied: producer in "
                    f"wave {wave_of[p]}, consumer in wave {wave_of[node.op_id]}")


def _single_steps(graph: OpGraph, group: Sequence[int],
                  slot_of: dict[int, int]) -> list[Step]:
    """Per-op call steps — the fallback floor every fused route degrades to
    (semantically identical to unfused execution by construction)."""
    out: list[Step] = []
    for op in group:
        node = graph.nodes[op]
        if node.fn is None:
            continue
        out.append(Step(
            route=_CALL, fn=node.fn,
            arg_slots=tuple(slot_of[p] for p in node.inputs),
            consts=tuple(node.meta.get("consts", ())),
            out_slots=(slot_of[op],), free_slots=(),
            op_ids=(op,)))
    return out


def _lower_group(
    graph: OpGraph,
    group: Sequence[int],
    slot_of: dict[int, int],
    gemm_kernel: str,
    faults: FaultPlan | None,
    log: DegradationLog,
) -> list[Step]:
    """Lower one fusion group down the route ladder:
    grouped_gemm → branch_gemm → vmap → per-op sequential.

    Injected faults (sites ``kernel_compile`` / ``grouped_gemm_route``) and
    REAL construction failures (a const that won't stack, a kernel that
    won't build) take the same recovery edge: the next-slower route that
    computes the identical function, recorded in ``log``."""
    if _can_stack(graph, group):
        nodes = [graph.nodes[o] for o in group]
        arity = len(nodes[0].inputs)
        arg_slots = tuple(
            tuple(slot_of[n.inputs[a]] for n in nodes)
            for a in range(arity)
        )
        try:
            consts = _stack_consts(graph, group)
        except Exception as exc:
            log.note("kernel_compile", "stacked->sequential", repr(exc),
                     warn=True)
            return _single_steps(graph, group, slot_of)
        if _gemm_routable(graph, group):
            # _can_stack guarantees all declared shapes agree — use
            # the first declared one (any branch may omit it)
            shape = next((s for s in
                          _branch_input_shapes(graph, group)
                          if s is not None), None)
            m = (int(math.prod(shape[:-1]))
                 if shape is not None else None)
            route = _pick_gemm_route(
                nodes[0].meta["consts"][0], len(group), gemm_kernel,
                m=m)
            if route == _BRANCH_GEMM and faults is not None:
                try:
                    faults.fire("kernel_compile")
                except Exception as exc:
                    log.note("kernel_compile", "branch_gemm->vmap",
                             repr(exc))
                    route = _VMAP
        else:
            route = _VMAP
        try:
            fn = (_branch_gemm_step() if route == _BRANCH_GEMM
                  else jax.vmap(nodes[0].fn))
        except Exception as exc:
            log.note("kernel_compile", f"{route}->sequential", repr(exc),
                     warn=True)
            return _single_steps(graph, group, slot_of)
        return [Step(
            route=route, fn=fn, arg_slots=arg_slots, consts=consts,
            out_slots=tuple(slot_of[o] for o in group),
            free_slots=(), op_ids=tuple(group))]
    if (gemm_kernel != "vmap"
            and (ragged := _ragged_group_sizes(graph, group)) is not None):
        # ragged-M matmul group: ONE grouped kernel instead of N
        # serialized branches (jnp.stack is impossible here)
        if faults is not None:
            try:
                faults.fire("grouped_gemm_route")
            except Exception as exc:
                log.note("grouped_gemm_route", "grouped_gemm->sequential",
                         repr(exc))
                return _single_steps(graph, group, slot_of)
        nodes = [graph.nodes[o] for o in group]
        try:
            consts = _stack_consts(graph, group)
        except Exception as exc:
            log.note("grouped_gemm_route", "grouped_gemm->sequential",
                     repr(exc), warn=True)
            return _single_steps(graph, group, slot_of)
        return [Step(
            route=_GROUPED_GEMM, fn=_grouped_gemm_step(ragged),
            arg_slots=(tuple(slot_of[n.inputs[0]] for n in nodes),),
            consts=consts,
            out_slots=tuple(slot_of[o] for o in group),
            free_slots=(), op_ids=tuple(group),
            group_sizes=ragged)]
    return _single_steps(graph, group, slot_of)


def _lower(
    graph: OpGraph,
    schedule: WaveSchedule,
    output_ids: Sequence[int],
    gemm_kernel: str = "auto",
    faults: FaultPlan | None = None,
    log: DegradationLog | None = None,
) -> tuple[list[Step], dict[int, int], int, DegradationLog]:
    """Phase 1: wave schedule → pre-lowered step list + slot assignment."""
    slot_of = {op: k for k, op in enumerate(graph.nodes)}
    n_slots = len(slot_of)
    log = log if log is not None else DegradationLog()

    steps: list[Step] = []
    for wave in schedule.waves:
        for group in wave.fusion_groups:
            steps.extend(
                _lower_group(graph, group, slot_of, gemm_kernel, faults, log))

    # dead-slot analysis: a slot is freed right after its last consuming
    # step — or, for outputs nothing ever consumes (and which aren't program
    # outputs), right after its producing step — unless it backs an output.
    keep = {slot_of[o] for o in output_ids}
    last_use: dict[int, int] = {}
    for k, step in enumerate(steps):
        consumed = (step.arg_slots if step.route == _CALL
                    else [s for slots in step.arg_slots for s in slots])
        for s in consumed:
            last_use[s] = k
    free_at: dict[int, list[int]] = {}
    for s, last in last_use.items():
        if s not in keep:
            free_at.setdefault(last, []).append(s)
    for k, step in enumerate(steps):
        dead = [s for s in free_at.get(k, ()) if s not in step.out_slots]
        # unconsumed non-output results die the moment they are produced
        dead += [s for s in step.out_slots
                 if s not in keep and s not in last_use]
        step.free_slots = tuple(dead)
    return steps, slot_of, n_slots, log


def capture(
    graph: OpGraph,
    schedule: WaveSchedule,
    output_ids: Sequence[int] | None = None,
    donate_inputs: bool = False,
    gemm_kernel: str = "auto",
    faults: FaultPlan | None = None,
) -> CapturedGraph:
    """Build the single-program executable from a wave schedule.

    ``gemm_kernel`` routes eligible stacked GEMM groups: ``"auto"`` (Pallas
    on TPU / small interpret grids, vmap otherwise), ``"pallas"`` (always
    the fused kernel, einsum-ref fallback for non-tileable shapes) or
    ``"vmap"`` (always the generic stacked payload).  Ragged-M matmul
    groups take the grouped kernel under ``"auto"``/``"pallas"`` and fall
    back to per-branch calls under ``"vmap"`` (a ragged group cannot be
    vmapped).

    ``faults`` (default: the process-wide plan, if any) arms the
    ``plan_validate`` / ``kernel_compile`` / ``grouped_gemm_route``
    injection sites.  Route-level recovery happens here (see
    :func:`_lower_group`, recorded on ``CapturedGraph.degradations``);
    a ``plan_validate`` failure raises out — :class:`repro.core.Session`
    owns that rung (re-schedule sequential).
    """
    if gemm_kernel not in ("auto", "pallas", "vmap"):
        raise ValueError(f"unknown gemm_kernel {gemm_kernel!r}")
    if faults is None:
        faults = _active_faults()
    if faults is not None:
        # models a corrupted/stale plan arriving at the capturer: the same
        # ValueError surface _validate_waves raises for real corruption
        faults.fire("plan_validate")
    graph.validate()
    _validate_waves(graph, schedule)
    input_ids = [n.op_id for n in graph if n.fn is None]
    if output_ids is None:
        output_ids = graph.leaves()
    output_ids = list(output_ids)

    steps, slot_of, n_slots, deg_log = _lower(
        graph, schedule, output_ids, gemm_kernel, faults=faults)
    input_slots = [slot_of[i] for i in input_ids]
    output_slots = [slot_of[o] for o in output_ids]
    tree_map = jax.tree_util.tree_map

    # Weights enter the program as arguments, not closure constants: a
    # closed-over array is embedded in the HLO as a literal, which at model
    # scale (~1 GB of bf16 weights) bloats compilation past usefulness.
    step_consts = [step.consts for step in steps]

    def run(consts: Sequence[tuple], *args: Any) -> list[Any]:
        env: list[Any] = [None] * n_slots
        for s, a in zip(input_slots, args):
            env[s] = a
        for step, cs in zip(steps, consts):
            # each step's operations carry its route in their metadata
            with jax.named_scope(step.route):
                if step.route == _CALL:
                    out = step.fn(*[env[s] for s in step.arg_slots], *cs)
                    env[step.out_slots[0]] = out
                elif step.route == _GROUPED_GEMM:
                    outs = step.fn([env[s] for s in step.arg_slots[0]], *cs)
                    for k, slot in enumerate(step.out_slots):
                        env[slot] = outs[k]
                else:
                    stacked = [jnp.stack([env[s] for s in slots])
                               for slots in step.arg_slots]
                    outs = step.fn(*stacked, *cs)
                    if step.route == _BRANCH_GEMM:
                        step.grid = launch_grid(stacked[0], cs[0])
                    for k, slot in enumerate(step.out_slots):
                        env[slot] = tree_map(lambda x: x[k], outs)
            for s in step.free_slots:
                env[s] = None
        return [env[s] for s in output_slots]

    jit_kwargs: dict[str, Any] = {}
    if donate_inputs:
        jit_kwargs["donate_argnums"] = tuple(range(1, len(input_ids) + 1))
    return CapturedGraph(
        graph=graph,
        schedule=schedule,
        input_ids=input_ids,
        output_ids=output_ids,
        fn=functools.partial(run, step_consts),
        jitted=functools.partial(jax.jit(run, **jit_kwargs), step_consts),
        steps=steps,
        degradations=deg_log,
    )


def run_sequential_uncompiled(
    graph: OpGraph,
    inputs: Mapping[str, Any],
    output_ids: Sequence[int] | None = None,
) -> list[Any]:
    """Eager per-op execution in topo order — the "stock PyTorch" baseline:
    every op is dispatched separately from Python (launch overhead included).

    ``output_ids`` selects which ops' results are returned (default: the
    graph's leaves) — pass a :class:`CapturedGraph`'s ``output_ids`` so a
    differential comparison reads the SAME outputs the compiled program
    returns instead of silently re-deriving them.
    """
    env: dict[int, Any] = {}
    for i in graph.topological_order():
        node = graph.nodes[i]
        if node.fn is None:
            env[i] = inputs[node.name]
        else:
            consts = node.meta.get("consts", ())
            env[i] = jax.block_until_ready(
                node.fn(*[env[p] for p in node.inputs], *consts))
    if output_ids is None:
        output_ids = graph.leaves()
    return [env[o] for o in output_ids]
