import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

DOC = """Roofline analysis (§Roofline of EXPERIMENTS.md).

Per (arch × shape × mesh) cell, derive the three roofline terms from the
compiled dry-run artifact:

    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

``cost_analysis`` counts ``lax.scan`` bodies ONCE (verified), so totals are
corrected by lowering one BLOCK of each stack separately:

    corrected = full + Σ_stacks (n_i × block_full_i − block_partial_i)

where ``block_full`` forces single-chunk attention (inner scans trip=1 →
exactly counted) and ``block_partial`` uses the production chunking (≈ what
the full program's body-once already contains).  The single-chunk lowering
inflates attention HBM bytes (it round-trips the [S,T] probabilities that
the real flash kernel keeps in VMEM); we subtract that inflation
analytically (3 × fp32 round-trips of [b,h,s,t]) — documented here, visible
in the record as ``attn_bytes_adjustment``.

Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
collective_bytes are per-device (SPMD HLO shapes are per-device), so the
term divides by link_bw only.
"""

import argparse
import contextlib
import json
import sys
from typing import Any

import jax
import jax.numpy as jnp

from ..configs import SHAPES, cell_applicable, get_config, list_archs
from ..configs.base import ModelConfig, ParallelConfig, ShapeCell
from ..core.profiler import V5E, HardwareSpec
from ..models import Model
from ..models.transformer import stack_meta
from ..parallel.sharding import activation_rules, param_shardings
from ..utils import logical_axis_rules
from .dryrun import lower_cell
from .hlo_analysis import cost_dict, parse_collectives
from .mesh import make_production_mesh


# ---------------------------------------------------------------- block costs

@contextlib.contextmanager
def _single_chunk_attention():
    from ..models import attention as att
    prev = att._CHUNK_OVERRIDE
    att._CHUNK_OVERRIDE = "single"
    try:
        yield
    finally:
        att._CHUNK_OVERRIDE = prev


def _block_record(cfg: ModelConfig, cell: ShapeCell, mesh, kind: str,
                  windows, single_chunk: bool) -> dict[str, float]:
    """Lower ONE block (train: fwd+bwd; prefill: fwd; decode: one step) and
    return {flops, bytes, collective_bytes}."""
    from ..models import attention as att
    from ..models.transformer import block_seq, block_step, init_block
    from ..models.attention import init_cache
    from ..models.ssm import mamba_state_init, rwkv_state_init

    b = cell.global_batch
    s = cell.seq_len if cell.step != "decode" else 1
    d = cfg.d_model
    rng = jax.random.key(0)
    p_shapes = jax.eval_shape(lambda k: init_block(k, cfg, kind), rng)
    p_sh = param_shardings(mesh, p_shapes)
    x_sds = jax.ShapeDtypeStruct((b, s, d), cfg.dtype)
    win = windows[0] if windows else 0
    win = win if win > 0 else (1 << 30)
    rules = activation_rules(mesh, cell)

    if cell.step == "train":
        def fn(p, x):
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            def inner(p, x):
                y, _, aux = block_seq(p, x, cfg, positions, jnp.int32(win),
                                      None, False, kind)
                return (y.astype(jnp.float32).mean() + aux["aux_loss"]).sum()
            return jax.grad(inner, argnums=(0, 1))(p, x)
        args = (p_shapes, x_sds)
        in_sh = (p_sh, None)
    elif cell.step == "prefill":
        def fn(p, x):
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            y, cache, _ = block_seq(p, x, cfg, positions, jnp.int32(win),
                                    None, False, kind)
            return y, cache
        args = (p_shapes, x_sds)
        in_sh = (p_sh, None)
    else:  # decode
        length = cell.seq_len + cfg.meta_tokens
        if kind == "rwkv":
            cache = jax.eval_shape(lambda: rwkv_state_init(cfg, b))
        else:
            kv = jax.eval_shape(lambda: init_cache(cfg, b, length))
            if kind == "hybrid":
                ms = jax.eval_shape(lambda: mamba_state_init(cfg, b))
                cache = {"kv": kv, "mamba_conv": ms[0], "mamba_h": ms[1]}
            else:
                cache = kv
        pos_sds = jax.ShapeDtypeStruct((b,), jnp.int32)
        def fn(p, x, cache, pos):
            return block_step(p, x, cache, pos, cfg, jnp.int32(win), kind)
        args = (p_shapes, x_sds, cache, pos_sds)
        in_sh = (p_sh, None, None, None)

    ctx = _single_chunk_attention() if single_chunk else contextlib.nullcontext()
    with mesh, logical_axis_rules(rules, mesh), ctx:
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    cost = cost_dict(compiled)
    coll = parse_collectives(compiled.as_text(), while_multiplier=1.0)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll.total_bytes,
    }


def _attn_bytes_inflation(cfg: ModelConfig, cell: ShapeCell) -> float:
    """fp32 [b,h,s,t] probability round-trips that single-chunk lowering
    claims but real flash keeps in VMEM (3 passes: logits write, read for
    softmax-normalize, p read for PV)."""
    if cell.step == "decode":
        return 0.0
    b, s = cell.global_batch, cell.seq_len + cfg.meta_tokens
    if cfg.family == "ssm":
        return 0.0
    h = cfg.n_heads
    per_layer = 3.0 * 4.0 * b * h * s * s
    if cell.step == "train":
        per_layer *= 2.5      # bwd recompute + ds/dp traffic
    return per_layer


# ---------------------------------------------------------------- terms

def roofline_terms(flops: float, bytes_: float, coll_bytes_per_dev: float,
                   chips: int, hw: HardwareSpec = V5E) -> dict[str, float]:
    compute_s = flops / (chips * hw.peak_flops)
    memory_s = bytes_ / (chips * hw.hbm_bw)
    collective_s = coll_bytes_per_dev / hw.ici_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    return {**terms, "dominant": dominant,
            "roofline_fraction": compute_s / bound if bound > 0 else 0.0,
            "step_time_lower_bound_s": bound}


def model_flops(cfg: ModelConfig, cell: ShapeCell) -> float:
    """6·N_active·D for train, 2·N_active·D for inference (D = tokens)."""
    n = cfg.n_active_params()
    if cell.step == "train":
        return 6.0 * n * cell.global_batch * cell.seq_len
    if cell.step == "prefill":
        return 2.0 * n * cell.global_batch * cell.seq_len
    return 2.0 * n * cell.global_batch          # one token per sequence


# ---------------------------------------------------------------- driver

def analyse_cell(arch: str, shape_id: str, multi_pod: bool = False,
                 pcfg: ParallelConfig | None = None) -> dict[str, Any]:
    cfg = get_config(arch)
    cell = SHAPES[shape_id]
    ok, reason = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "shape": shape_id, "multi_pod": multi_pod,
                "status": "SKIP", "reason": reason}

    rec = lower_cell(arch, shape_id, multi_pod=multi_pod, pcfg=pcfg)
    if rec.get("status") != "OK":
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size

    # compute/memory terms: analytic model (see analytic_cost.py for why);
    # collective term: parsed from the compiled SPMD HLO (per-device bytes,
    # scan-depth multiplier applied in lower_cell).
    from .analytic_cost import cell_cost
    remat = (pcfg or ParallelConfig()).remat != "none" and cell.step == "train"
    ac = cell_cost(cfg, cell, remat=remat)
    coll = rec["collectives"]["total_bytes_per_device"]

    mf = model_flops(cfg, cell)
    terms = roofline_terms(ac.flops, ac.bytes, coll, chips)
    hlo_flops_per_dev = rec["cost"].get("flops", 0.0)
    rec.update(
        analytic={"flops": ac.flops, "bytes": ac.bytes, **ac.detail},
        hlo_flops_per_device=hlo_flops_per_dev,
        hlo_crosscheck_ratio=(hlo_flops_per_dev * chips / ac.flops
                              if ac.flops else 0.0),
        model_flops=mf,
        useful_flops_ratio=mf / ac.flops if ac.flops else 0.0,
        roofline=terms,
    )
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    # §Perf hillclimb knobs (flags REPRO_* come via the environment)
    ap.add_argument("--no-fsdp", action="store_true",
                    help="replicate params over dp axes (inference cells)")
    ap.add_argument("--no-tp", action="store_true",
                    help="disable tensor parallelism (tiny-model cells)")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="Megatron-SP residual-stream sharding")
    ap.add_argument("--ep2d", action="store_true",
                    help="experts sharded data×model (whole-expert ownership)")
    ap.add_argument("--remat", default="block", choices=["none", "block"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--tag", default=None, help="label recorded with --out")
    args = ap.parse_args(argv)
    pcfg = ParallelConfig(fsdp=not args.no_fsdp, remat=args.remat,
                          tensor_parallel=not args.no_tp,
                          sequence_parallel=args.seq_parallel,
                          expert_2d=args.ep2d,
                          grad_compression=args.compression)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    for arch in archs:
        for shape_id in shapes:
            rec = analyse_cell(arch, shape_id, multi_pod=args.multi_pod,
                               pcfg=pcfg)
            if args.tag:
                rec["tag"] = args.tag
            r = rec.get("roofline", {})
            print(f"[roofline] {arch} × {shape_id}: {rec['status']} "
                  + (f"dominant={r.get('dominant')} "
                     f"frac={r.get('roofline_fraction', 0):.3f} "
                     f"c/m/x={r.get('compute_s', 0):.4f}/"
                     f"{r.get('memory_s', 0):.4f}/{r.get('collective_s', 0):.4f}s"
                     if r else ""))
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
