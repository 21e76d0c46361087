"""Share of the traced window in which the device is idle inside one of
the program's garbage-collection spans (``repro.gc``), in percent
(offline cells)."""


def read(ctx):
    idle = ctx.scopes.idle_in_s({"repro.gc"})
    return None if idle is None else 100.0 * idle / ctx.trace.window_s
