"""Device time of the attention core (``qattn_int8_*`` scopes of
``backend.qattention`` and the ``k_attn_fwd`` kernel, backward included)
as a share of the device's busy time."""


def read(ctx):
    s = ctx.summary
    t = s.scope_ns("qattn_int8_", "k_attn_fwd")
    return 100.0 * t / s.busy_ns if s.busy_ns and t else None
