"""Share of the window the device worker spent blocked on a full reorder
window downstream: its ``blocked_ns`` counter over the window."""
import spanreduce


def read(ctx):
    dev = spanreduce.workers(ctx.get("stage_counters"), "device")
    if not dev:
        return None
    return 1e-9 * sum(w["blocked_ns"] for w in dev) / ctx["window_s"]
