"""paged_decode_roofline.serve: roofline time of the paged-decode
attention work (q, out and each active row's KV pages, from shapes) of
every decode tick in the traced window, over the device time of the trace
events that carry the kernel's name (device trace, %)."""
from harness.peaks import roofline_s


def read(run):
    if run.trace is None or run.peak is None:
        return None
    dev = run.trace.op_seconds("paged_decode")
    ticks = run.window_ticks("decode")
    if not dev or not ticks:
        return None
    dm = run.dims
    need = sum(roofline_s(*run.arch.paged_decode_call(dm, ctxs), run.peak)
               for _, _, _, ctxs in ticks) * dm.layers
    return 100.0 * need / dev
