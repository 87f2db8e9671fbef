"""Model FLOP utilization of serving: the prefill and decode operations the
model's shapes need for the requests of the traced window, over the
window and the chips' int8 peak."""


def read(ctx):
    w, s = ctx.work, ctx.summary
    rate = w["serve_flops"] / (s.window_ns / 1e9)
    return 100.0 * rate / (ctx.chips * ctx.peaks["int8_ops_per_s"])
