"""Least time of the forward grouped int8 contractions of the held experts
at balanced routing (each call the larger of its operations over the int8
peak and its bytes, int8 in and fp32 out, over HBM's) over the device time
of the grouped kernel's events (``k_int8_gmm`` scope)."""
import flops


def read(ctx):
    t = ctx.summary.scope_ns("k_int8_gmm") / 1e9
    if not t or "gmm_contractions" not in ctx.work:
        return None
    least = flops.least_seconds(ctx.work["gmm_contractions"], ctx.peaks)
    return 100.0 * least * ctx.work["steps"] / t
