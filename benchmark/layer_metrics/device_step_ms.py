"""Median distance between the starts of consecutive step programs on
chip 0, from the trace: the device's own step time, host gaps
included."""


def compute(ctx):
    return ctx.tables.step_period_s * 1e3
