"""A kernel's share of its memory roofline, in %: the least time the chip
could take to move the bytes the kernel must move (lib/neg_bytes_model.py /
peak HBM bytes per second of the device_kind) over the device time of the
ops under the kernel's `otb.` scopes inside a reply (lib/xplane.py; median
over the traced replies of the classes).  None where the trace holds no op
under those scopes: a program without the kernel."""

from benchmarks.lib import neg_bytes_model, stats, xplane


def read(ctx, model, scopes, classes=None, peak="hbm_bytes_per_s"):
    trace = xplane.of_this_run(ctx)
    if trace is None:
        return None
    ms = [x for x in xplane.scope_ms_per_statement(trace, scopes, classes)
          or [] if x > 0]
    if not ms:
        return None
    least_s = neg_bytes_model.MODELS[model](ctx.data) / ctx.peaks[peak]
    return 100.0 * least_s / (stats.median(ms) / 1e3)
