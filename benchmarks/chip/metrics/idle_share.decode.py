"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window.  The window runs from the
first traced scheduler step's start to the last one's end."""


def read(ctx):
    if not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
