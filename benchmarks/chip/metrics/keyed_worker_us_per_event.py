"""Microseconds of keyed-stage busy time per event, over the window: the
keyed workers' summed ``busy_ns`` counters over their summed ``rows``."""
import spanreduce


def read(ctx):
    keyed = spanreduce.workers(ctx.get("stage_counters"), "keyed")
    rows = sum(w["rows"] for w in keyed)
    return 1e-3 * sum(w["busy_ns"] for w in keyed) / rows if rows else None
