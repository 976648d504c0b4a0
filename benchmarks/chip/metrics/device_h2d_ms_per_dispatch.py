"""Milliseconds per device dispatch spent copying the zero-padded columns
to the device and launching the program (H2D and launch): the device
worker's ``stream.device.dispatch`` spans in the traced window, their
total over their count."""


def read(ctx):
    spans = (ctx.get("trace") or {}).get("spans") or {}
    n, total_s = spans.get("stream.device.dispatch", (0, 0.0))
    return 1e3 * total_s / n if n else None
