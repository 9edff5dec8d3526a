"""The device's idle share of the traced window, in %: 1 - (union of
device-op intervals / window), the mean over the chips used."""


def read(ctx):
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace_busy_s / ctx.trace_window_s)
