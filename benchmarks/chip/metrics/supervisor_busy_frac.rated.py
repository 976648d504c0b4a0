"""Share of the window the parent supervisor spent inside its crank,
sealing and routing pushed units, draining egress and relaying spill
bodies: the sum of its ``supervisor_counters`` over the window."""


def read(ctx):
    counters = ctx.get("stage_counters")
    if not counters:
        return None
    return 1e-9 * sum(counters["supervisor_counters"].values()) \
        / ctx["window_s"]
