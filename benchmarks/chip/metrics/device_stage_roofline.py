"""The device stage's share of its roofline, in %: the least time the
chip could take for the stage's bytes in the traced window (rows x the
schema's bytes in and out, from ``work.py``, over peak HBM bandwidth),
over the summed device time of every operation its dispatches launched.
The stage moves bytes and does next to no arithmetic, so bandwidth
bounds it."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["op_s"] or not ctx["rows_traced"]:
        return None
    least = ctx["stage_bytes"](ctx["rows_traced"]) / ctx["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * least / trace["op_s"]
