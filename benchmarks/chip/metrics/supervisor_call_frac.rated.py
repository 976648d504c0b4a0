"""Share of the window the harness thread, which is also the program's
parent supervisor, spent inside ``Session`` calls (``push``, ``poll``,
``service_once``)."""


def read(ctx):
    if not ctx["session_call_s"]:
        return None
    return ctx["session_call_s"] / ctx["window_s"]
