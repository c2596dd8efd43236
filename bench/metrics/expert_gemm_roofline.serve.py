"""expert_gemm_roofline.serve: roofline time of the held experts' SwiGLU
work of every decode tick in the window (``expert_call`` of the pairs that
reached a held expert and the held experts they reached, summed over the
MoE layers: the ``moe_pairs_held`` and ``moe_experts_hit`` attributes of
the tick's ``engine.step`` span), over the device time of the decode
step's trace events that carry the expert kernel's name (device trace,
%).  Nothing where the program does not count its experts' pairs."""
from harness.peaks import roofline_s
from harness.program_spans import window_spans

KERNEL = "moe_mlp_pallas"
DECODE_STEP = "jit__step/"          # the engine's jitted decode step


def read(run):
    if run.trace is None or run.peak is None or run.kind != "serve":
        return None
    spans = window_spans(run)
    ticks = [s.attrs for s in spans or () if s.name == "engine.step"
             and s.attrs.get("kind") == "decode" and "moe_pairs_held" in s.attrs]
    dev = sum(v for k, v in run.trace.ops.items()
              if k.startswith(DECODE_STEP) and KERNEL in k)
    if not ticks or not dev:
        return None
    need = sum(roofline_s(*run.arch.expert_call(
        run.dims, a["moe_pairs_held"], a["moe_experts_hit"]), run.peak)
        for a in ticks)
    return 100.0 * need / dev
