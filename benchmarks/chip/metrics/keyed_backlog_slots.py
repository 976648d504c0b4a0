"""Mean queued slots of the exchange ring in front of the keyed stage,
from ``Session.stats()["backlog_slots"]`` sampled every 100 ms over the
window."""


def read(ctx):
    return ctx["ring_backlog"]("keyed")
