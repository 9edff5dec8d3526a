"""The exchange's share of the interconnect's peak, in %: over the traced
statements, the least time the interconnect could take for the bytes one
chip sends in its all_to_all exchanges (lib/exchange_model.py: the
program's own counter over the published per-chip peak) over the time the
ops whose XLA name contains `pattern` took, on the slowest chip.  None where
the program has no such counter or the trace no such op."""

from benchmarks.lib import exchange_model, profile


def read(ctx, pattern, counter="exchange_bytes", peak="ici_bits_per_s"):
    if ctx.trace is None:
        return None
    by_class = exchange_model.sent_bytes_by_class(ctx.step_stats(), counter)
    sent = took_s = 0.0
    for cls, per_statement in by_class.items():
        xs = profile.op_time_per_annotation(ctx.trace, pattern, [cls])
        sent += per_statement * len(xs)
        took_s += sum(xs)
    if not sent or not took_s:
        return None
    return 100.0 * exchange_model.least_seconds(sent, ctx.peaks, peak) \
        / took_s
