"""branch_gemm_roofline.graph: roofline time of the branch-GEMM steps'
work, counted from their shapes, over the device time of the trace events
that carry the kernel's name (device trace, %)."""
from harness.peaks import roofline_s


def read(run):
    if run.trace is None or not run.branch_gemm_steps or run.peak is None:
        return None
    dev = run.trace.op_seconds("branch_gemm")
    if not dev:
        return None
    per_call = sum(roofline_s(f, b, run.peak) for f, b in run.branch_gemm_steps)
    return 100.0 * per_call * run.calls / dev
