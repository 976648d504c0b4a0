"""Share of the traced window in which no operation ran on the device:
1 - the union of its busy intervals over the window."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
