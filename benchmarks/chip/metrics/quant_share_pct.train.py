"""Device time of the quantization sites (``quant_*`` name scopes of
``core/backend.py``) as a share of the device's busy time."""


def read(ctx):
    s = ctx.summary
    return 100.0 * s.scope_ns("quant_") / s.busy_ns if s.busy_ns else None
