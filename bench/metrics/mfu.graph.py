"""mfu.graph: forward FLOPs per call, from shapes, times the calls in the
traced window, over the window times the chip's bf16 peak (%)."""


def read(run):
    if run.kind != "graph" or run.peak is None or not run.calls:
        return None
    return 100.0 * run.flops_per_call * run.calls / (
        run.window_s * run.peak["bf16_flops_per_s"])
