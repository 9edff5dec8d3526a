"""Statements completed and correct per second of the window, all clients:
every step that got its reply and passed its check, over the time from the
window's start to the last reply."""


def read(ctx, classes=None):
    if ctx.window_s <= 0:
        return None
    return ctx.correct_statements(classes) / ctx.window_s
