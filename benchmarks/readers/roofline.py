"""A statement's share of its memory roofline, in %: the least time the chip
could take to read the bytes the statement must read (bytes model / peak
HBM bytes per second of the device_kind) over the device-busy time inside
the statement's replies (median over the traced replies of the classes)."""

from benchmarks.lib import bytes_model, profile, stats


def read(ctx, model, classes, peak="hbm_bytes_per_s"):
    if ctx.trace is None:
        return None
    busy = [s for _, s in profile.busy_per_annotation(ctx.trace, classes)
            if s > 0]
    if not busy:
        return None
    least_s = bytes_model.MODELS[model](ctx.data) / ctx.peaks[peak]
    return 100.0 * least_s / stats.median(busy)
