"""Percent of the traced window in which no op ran on chip 0."""


def compute(ctx):
    return 100.0 * (1.0 - ctx.tables.busy_s / ctx.tables.window_s)
