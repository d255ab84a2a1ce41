"""Share of the traced window in which no operation runs on the device, in
%: 1 - (union of the device's op intervals) / window, mean over chips."""


def read(ctx):
    if ctx.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns / ctx.trace.window_ns)
