"""Device time of the expert layer (``moe_route``, ``moe_permute``,
``moe_combine`` scopes of ``models/moe.py`` and the grouped contractions'
``qgmm_int8_*`` site, backward included) as a share of the device's busy
time."""


def read(ctx):
    s = ctx.summary
    t = s.scope_ns("moe_", "qgmm_int8_")
    return 100.0 * t / s.busy_ns if s.busy_ns and t else None
