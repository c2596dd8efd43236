"""mfu.serve: FLOPs of every token the window processed (admission
prefills and decode steps, from shapes and context lengths) over the
window times the chip's bf16 peak (%)."""


def read(run):
    if run.kind != "serve" or run.peak is None or run.window_s <= 0:
        return None
    dm, arch = run.dims, run.arch
    flops = sum(arch.prefill_flops(dm, n) for *_, n in run.window_ticks("admit"))
    flops += sum(arch.decode_token_flops(dm, c)
                 for *_, ctxs in run.window_ticks("decode") for c in ctxs)
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops_per_s"])
