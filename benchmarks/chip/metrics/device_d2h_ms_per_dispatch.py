"""Milliseconds per device dispatch spent waiting for it and reading its
columns back (D2H): the device worker's ``stream.device.sync`` spans in
the traced window, their total over their count."""


def read(ctx):
    spans = (ctx.get("trace") or {}).get("spans") or {}
    n, total_s = spans.get("stream.device.sync", (0, 0.0))
    return 1e3 * total_s / n if n else None
