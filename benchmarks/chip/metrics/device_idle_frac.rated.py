"""``device_idle_frac`` in the rated cell, where it bears on latency."""


def read(ctx):
    return ctx["reader"]("device_idle_frac")(ctx)
