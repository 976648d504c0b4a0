"""Seconds the device worker spent lowering and compiling its kernel, as
it reports them (``devices[].lower_s + compile_s``)."""


def read(ctx):
    dev = ctx["device"]
    if not dev:
        return None
    return dev["lower_s"] + dev["compile_s"]
