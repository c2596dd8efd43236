"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

For multi-pod runs the ``pod`` axis can carry pipeline stages instead of
data parallelism (``ParallelConfig.pod_axis_role="pipeline"``): each pod
holds a contiguous slice of layers; microbatches stream through with
``ppermute`` hand-offs.  Implemented with ``shard_map`` so the schedule is
explicit (no reliance on GSPMD inferring the pipeline).

This module is exercised by tests on a small host-device mesh and wired as
a launcher option; the default dry-run path keeps pods data-parallel
(DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,            # params with leading [n_stages, layers_per_stage, ...]
    x: jax.Array,                 # [n_micro, mb, ...] microbatched input
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run ``layer_fn`` stacks as a GPipe pipeline over ``axis``.

    stage s applies its layer slice to microbatch m at step t = s + m;
    total steps = n_stages + n_micro - 1.  Hand-off via ppermute ring.
    """
    n_stages = mesh.shape[axis]

    def stage_fn(p_stage, x_all):
        # p_stage arrives [1, layers_per_stage, ...] (stage axis sharded to
        # local size 1) — drop the stage dim.
        # x_all: [n_micro, mb, ...] microbatches (replicated across axis)
        p_stage = jax.tree_util.tree_map(lambda a: a[0], p_stage)
        sid = jax.lax.axis_index(axis)
        n_micro = x_all.shape[0]
        steps = n_stages + n_micro - 1

        def apply_stack(h):
            def body(h, p_l):
                return layer_fn(p_l, h), None
            h, _ = jax.lax.scan(body, h, p_stage)
            return h

        def step(carry, t):
            buf, outs = carry                       # buf: [mb, ...] in-flight
            m = t - sid                             # microbatch index at stage
            active = (m >= 0) & (m < n_micro)
            # stage 0 ingests microbatch t; others use the handed-off buf
            inject = x_all[jnp.clip(t, 0, n_micro - 1)]
            h_in = jnp.where(sid == 0, inject, buf)
            h_out = jnp.where(active, apply_stack(h_in), h_in)
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: o.at[jnp.clip(m, 0, n_micro - 1)].set(h_out),
                lambda o: o,
                outs)
            # hand off to next stage
            buf_next = jax.lax.ppermute(
                h_out, axis, [(j, (j + 1) % n_stages) for j in range(n_stages)])
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(x_all[0])
        outs0 = jnp.zeros_like(x_all)
        (buf, outs), _ = jax.lax.scan(step, (buf0, outs0), jnp.arange(steps))
        # every stage's `outs` is only valid on the last stage; broadcast it
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    in_specs = (P(axis), P())       # params stage-sharded; x replicated
    out_specs = P()
    # replication checking off: stages hold per-device-divergent carries
    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(stage_params, x)


def split_microbatches(x: jax.Array, n_micro: int) -> jax.Array:
    b = x.shape[0]
    assert b % n_micro == 0
    return x.reshape(n_micro, b // n_micro, *x.shape[1:])
