"""Least time of the forward int8 weight contractions (each call the larger
of its operations over the int8 peak and its bytes, int8 in and fp32 out,
over HBM's) over the device time of the int8 matmul kernel's events
(``k_int8_matmul_fp`` and ``k_int8_conv_fp`` scopes)."""
import flops


def read(ctx):
    t = ctx.summary.scope_ns("k_int8_matmul_fp", "k_int8_conv_fp") / 1e9
    if not t:
        return None
    least = flops.least_seconds(ctx.work["int8_contractions"], ctx.peaks)
    return 100.0 * least * ctx.work["steps"] / t
