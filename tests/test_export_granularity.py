"""Exporter granularity: every config arch must emit traced-kernel graphs.

The scheduler can only overlap what the exporter exposes: each layer needs
at least one memory-class stage (transpose copies, softmax, weight-stream
DMAs, scans) AND one compute-class stage (GEMMs above the MXU intensity
floor), or the reported speedups for that arch are fictional (ISSUE 10 /
IOS, arxiv 2011.01302).  These tests pin that property for all assigned
archs, plus the cost-accounting invariants of the decomposition:

* per-stage nodes carry their OWN vmem/occupancy — the folded cost of the
  old monolithic attention node equals the field-wise sum (traffic/FLOPs)
  and max (working set) of the stages that replaced it;
* cost-only exports split FF projections into weight-stream + activation
  GEMM pairs, while payload-backed exports stay single-input executable.
"""
import re

import pytest

from repro import configs
from repro.core.profiler import (
    IntensityClass,
    ModelProfiler,
    attention_cost,
    gemm_cost,
)
from repro.models.opgraph_export import (
    _sum_costs,
    build_encdec_opgraph,
    build_lm_opgraph,
)

_LAYER_RE = re.compile(r"^(L\d+|e\d+|d\d+)\.")


def _build_cost_only(arch: str, n_layers: int = 2, seq: int = 32):
    cfg = configs.get_config(arch)
    if cfg.n_dec_layers:
        return build_encdec_opgraph(cfg, 1, seq, n_layers=n_layers)
    return build_lm_opgraph(cfg, 1, seq, n_layers=n_layers)


@pytest.mark.parametrize("arch", configs.list_archs())
def test_every_layer_exports_both_intensity_classes(arch):
    g = _build_cost_only(arch)
    prof = ModelProfiler()
    per_layer: dict[str, set[IntensityClass]] = {}
    for n in g:
        m = _LAYER_RE.match(n.name)
        if m is None or n.cost is None:
            continue
        per_layer.setdefault(m.group(1), set()).add(prof.classify(n))
    assert per_layer, f"{arch}: no per-layer nodes exported"
    for layer, classes in per_layer.items():
        assert IntensityClass.COMPUTE in classes, (
            f"{arch} {layer}: no compute-class stage — nothing to overlap "
            f"memory ops against")
        assert IntensityClass.MEMORY in classes, (
            f"{arch} {layer}: no memory-class stage — nothing to hide "
            f"behind the GEMMs")


@pytest.mark.parametrize("arch", configs.list_archs())
def test_attention_is_decomposed_not_monolithic(arch):
    """No arch may fall back to a single fused attention node: the
    score/context GEMMs and the mask+softmax stage must be separate
    schedulable ops (rwkv has no attention; its scan plays that role)."""
    g = _build_cost_only(arch)
    names = {n.name for n in g}
    if arch.startswith("rwkv"):
        assert any(n.endswith(".wkv_scan") for n in names)
        return
    assert not any(n.endswith(".attn") for n in names), (
        f"{arch}: monolithic attention node survived the refactor")
    for stage in ("scores", "scale_mask", "softmax", "ctx"):
        assert any(n.endswith(f".{stage}") for n in names), (
            f"{arch}: missing decomposed stage {stage!r}")


def test_folded_cost_equals_sum_of_decomposed_stages():
    """Satellite: the stage costs of one decomposed attention block fold
    back (via ``_sum_costs``) into exactly the old monolithic accounting —
    traffic and FLOPs add, working set is the widest phase — and the
    score/context GEMM pair alone carries the full 4·b·h·s·t·d attention
    FLOPs."""
    cfg = configs.get_config("qwen2-0.5b")
    b, s = 1, 32
    nh, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = build_lm_opgraph(cfg, b, s, n_layers=1)
    stages = {n.name.split(".", 1)[1]: n.cost for n in g
              if n.name.startswith("L0.")
              and n.name.split(".", 1)[1] in
              ("qt", "kt", "vt", "scores", "scale_mask", "softmax",
               "ctx", "ctxt")}
    assert len(stages) == 8

    ref = attention_cost(b, s, s, nh, hd, kvh)
    assert stages["scores"].flops + stages["ctx"].flops == ref.flops

    folded = _sum_costs(*stages.values())
    assert folded.flops == sum(c.flops for c in stages.values())
    assert folded.bytes_read == sum(c.bytes_read for c in stages.values())
    assert folded.bytes_written == sum(c.bytes_written for c in stages.values())
    # per-stage working sets are genuinely per-stage, not one folded bound
    vmems = {c.vmem_bytes for c in stages.values()}
    assert len(vmems) > 1, "stages share one folded vmem bound"
    assert folded.vmem_bytes == max(vmems)
    for c in stages.values():
        assert c.vmem_bytes <= folded.vmem_bytes

    # and the profiler sees both classes within the attention block alone
    prof = ModelProfiler()
    classes = {prof.classify(n) for n in g
               if n.name.startswith("L0.") and n.cost is not None}
    assert classes == {IntensityClass.COMPUTE, IntensityClass.MEMORY}


def test_scores_gemm_clears_compute_intensity_floor():
    """The decomposed score GEMM must classify as compute-bound at bench
    sequence lengths — if it fell below the MXU floor the decomposition
    would *remove* overlap opportunities instead of adding them."""
    cfg = configs.get_config("qwen2-0.5b")
    b, s, hd = 1, 32, cfg.head_dim
    c = gemm_cost(b * cfg.n_heads * s, hd, s)
    prof = ModelProfiler()
    assert c.arithmetic_intensity() >= 16.0
    assert prof.hw.machine_balance > 0


def test_cost_only_exports_stream_ff_weights_payload_graphs_do_not():
    """Cost-only graphs price FF weight traffic as explicit prefetchable
    DMA ops rooted at the graph input; payload-backed graphs must instead
    stay fully executable with a single INPUT node (weights in consts)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import make_model

    g = build_lm_opgraph(configs.get_config("qwen2-0.5b"), 1, 32, n_layers=2)
    streams = [n for n in g if n.name.endswith("_wstream")]
    assert len(streams) == 6          # gate/up/down × 2 layers
    root = next(n for n in g if n.name == "tokens")
    for n in streams:
        assert n.inputs == (root.op_id,), "stream must root at the input"
        assert n.cost.flops == 0 and n.cost.bytes_read > 0

    cfg = dataclasses.replace(configs.get_config("qwen2-0.5b", smoke=True),
                              dtype=jnp.float32)
    params = make_model(cfg).init(jax.random.key(0))
    gp = build_lm_opgraph(cfg, 1, 4, params=params, n_layers=2)
    assert not any(n.name.endswith("_wstream") for n in gp)
    assert sum(1 for n in gp if n.fn is None) == 1


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "llama3.2-1b", "glm4-9b"])
def test_payload_graph_matches_model_prefill(arch):
    """A payload-backed dense export computes the model's forward pass
    (rotary positions on q/k, fp32 logits): its last-position logits match
    ``Model.prefill`` within bf16 rounding drift."""
    import jax
    import numpy as np

    from repro.core.capture import run_sequential_uncompiled
    from repro.models import Model

    cfg = configs.get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    g = build_lm_opgraph(cfg, batch=2, seq=8, params=params)
    out = np.asarray(run_sequential_uncompiled(g, {"tokens": tokens})[0])
    ref = np.asarray(model.prefill(params, {"tokens": tokens})[0])
    assert out.dtype == np.float32 and out.shape[:2] == (2, 8)
    assert np.abs(out[:, -1] - ref).max() <= 2.5e-2 * np.abs(ref).max()
