"""Model FLOP utilization of training: the forward and backward operations
the model's shapes need (nothing recomputed counted), times the steps in
the traced window, over the window and the chips' int8 peak."""


def read(ctx):
    w, s = ctx.work, ctx.summary
    rate = w["train_flops"] * w["steps"] / (s.window_ns / 1e9)
    return 100.0 * rate / (ctx.chips * ctx.peaks["int8_ops_per_s"])
