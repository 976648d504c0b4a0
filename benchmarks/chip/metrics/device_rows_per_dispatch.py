"""Rows per device dispatch in the traced window: the rows that egressed
in it over the programs the device stage launched in it (one launch per
dispatch, counted on the trace's ``XLA Modules`` line)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["launches"]:
        return None
    return ctx["rows_traced"] / trace["launches"]
