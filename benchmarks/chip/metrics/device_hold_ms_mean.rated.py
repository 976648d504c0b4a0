"""Mean milliseconds a unit spent inside the device stage over the
window, from its submission to its publish (batch fill, the inflight
window, H2D, the kernel and D2H): the device worker's ``hold_ns``
counter over its ``hold_units``."""
import spanreduce


def read(ctx):
    dev = spanreduce.workers(ctx.get("stage_counters"), "device")
    units = sum(w["hold_units"] for w in dev)
    return 1e-6 * sum(w["hold_ns"] for w in dev) / units if units else None
