"""Of the device's idle time in the traced window, the % inside at least
one of the program's `otb:` host spans (lib/xplane.py)."""

from benchmarks.lib import xplane


def read(ctx):
    trace = xplane.of_this_run(ctx)
    return xplane.idle_attributed_pct(trace) if trace is not None else None
