"""Model FLOP/s utilization: the FLOPs of every token processed in the
traced window (prompt and output) over the traced window times the
chip's peak FLOP/s, in %."""


def read(ctx):
    if ctx.window_s is None:
        return None
    flops = sum(ctx.work(k)[0] for k in ("prefill", "decode"))
    return 100.0 * flops / (ctx.window_s * ctx.peaks["flops_per_s"])
