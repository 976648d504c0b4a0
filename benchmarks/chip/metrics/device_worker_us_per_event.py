"""Microseconds of the device worker's busy time per event it took in,
over the window: its ``busy_ns`` counter over its ``rows`` counter."""
import spanreduce


def read(ctx):
    dev = spanreduce.workers(ctx.get("stage_counters"), "device")
    rows = sum(w["rows"] for w in dev)
    return 1e-3 * sum(w["busy_ns"] for w in dev) / rows if rows else None
