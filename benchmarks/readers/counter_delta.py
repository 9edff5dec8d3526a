"""A program counter's growth over the window (see run.py `counters`)."""


def read(ctx, counter):
    if counter not in ctx.counters_before:
        return None
    return ctx.counters_after[counter] - ctx.counters_before[counter]
