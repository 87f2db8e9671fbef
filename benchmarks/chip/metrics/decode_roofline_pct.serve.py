"""Least time of the window's decode steps (per step the larger of its
operations over the int8 peak and of its bytes, int8 weights read once
and the KV cache read, over HBM's) over the device time of the
``decode_step`` program."""
import flops


def read(ctx):
    busy = ctx.summary.program_ns("decode_step") / 1e9
    if not busy:
        return None
    least = flops.least_seconds(ctx.work["decode_contractions"], ctx.peaks)
    return 100.0 * least / busy
